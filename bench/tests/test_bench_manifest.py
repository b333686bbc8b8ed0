"""BENCHMARK.json: every cell, configuration, traffic mix and metric
resolves to its files by name, names keep to the manifest's rules, and
the command refuses to run without a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import run

ROOT = run.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_and_names(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["paths"] == ["bench"]
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in manifest[k]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    assert {m["name"] for m in manifest["end_to_end"]} >= {"setup_s"}


def test_configs_resolve(manifest):
    for c in manifest["configs"]:
        assert c["file"].startswith("bench/configs/")
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"]
        for key in c["reduced"]:
            assert key in cfg and key in cfg["published"], key


def test_cells_resolve(manifest):
    e2e = {m["name"] for m in manifest["end_to_end"]}
    for w in manifest["workloads"]:
        spec = run.load_cell(w["name"])
        assert spec["config"]["name"] == w["config"]
        assert spec["traffic"]["name"] == w["traffic"]
        assert w["chips"] in (1, 4)
        assert {m["name"] for m in spec["end_to_end"]} >= {"setup_s"}
        assert len(spec["end_to_end"]) >= 2 and spec["per_layer"]
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e
        assert callable(run.metric_reader(m["name"]))
        for w in m.get("workloads", []):
            assert w in {c["name"] for c in manifest["workloads"]}


def _traffic_files():
    """Every traffic file: the mixes of ``bench/traffic`` and the test
    fixtures beside this file that hold an objective."""
    import glob
    for path in sorted(glob.glob(os.path.join(run.BENCH, "traffic", "*.json"))
                       + glob.glob(os.path.join(run.BENCH, "tests",
                                                "*.json"))):
        with open(path) as f:
            mix = json.load(f)
        if "objective" in mix:
            yield os.path.basename(path), mix


def test_search_keys_name_registered_fields():
    import dataclasses
    from repro.core import api  # noqa: F401  (registers the optimizers)
    from repro.core.registries import OPTIMIZERS
    files = list(_traffic_files())
    assert any("search" in mix for _, mix in files)
    for name, mix in files:
        if "search" not in mix:
            continue
        keys = dict(mix["search"])
        entry = OPTIMIZERS.get(keys.pop("optimizer"))
        fields = {f.name for f in dataclasses.fields(entry.params_cls)}
        assert set(keys) <= fields, (name, set(keys) - fields)


def test_unknown_cell_and_device_kind():
    with pytest.raises(run.SetupError):
        run.load_cell("no.such.cell")
    with pytest.raises(run.SetupError):
        run.device_kind_peaks("TPU v0 imaginary")
    assert run.device_kind_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_resample_sizes():
    assert run.resample_sizes(50) == [8, 16, 32, 50]
    assert run.resample_sizes(42) == [8, 16, 32, 42]
    assert run.resample_sizes(15) == [8, 15]
    assert run.resample_sizes(5) == [5]


def _cpu_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


def test_importing_the_harness_loads_no_jax():
    out = subprocess.run(
        [sys.executable, "-c", "import sys; import bench.run; "
         "print('jax' in sys.modules)"],
        cwd=ROOT, env=_cpu_env(), capture_output=True, text=True,
        timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "False"


def _run_cmd(cwd):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "homog64.ga.synth",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=_cpu_env(), capture_output=True, text=True,
        timeout=300)


def test_no_tpu_means_no_result():
    out = _run_cmd(ROOT)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert not out.stdout.strip()


def test_benchmark_files_alone_are_not_enough(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run_cmd(tmp_path)
    assert out.returncode != 0
    assert not out.stdout.strip()
