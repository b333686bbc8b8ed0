"""The check that decides `correct`, on the CPU at a small size: the
program's outputs pass, the control (the reference in bfloat16) fails,
and a run whose scorer is broken underneath comes out not correct; the
br-batched route on one device and on four, and the refusal of an
optimizer the window cannot bound."""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from bench import control, run

HERE = os.path.dirname(os.path.abspath(__file__))


def _spec(config="homog32_small", traffic="ga.synth"):
    """A cell at a test size: a traffic fixture of this directory as it
    is, or a mix of ``bench/traffic`` with 3 warm generations."""
    with open(os.path.join(HERE, config + ".json")) as f:
        cfg = json.load(f)
    fixture = os.path.join(HERE, traffic + ".json")
    if os.path.exists(fixture):
        with open(fixture) as f:
            mix = json.load(f)
    else:
        with open(os.path.join(run.BENCH, "traffic", traffic + ".json")) as f:
            mix = json.load(f)
        mix["warm_generations"] = 3
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    return {"cell": {"chips": 1}, "config": cfg, "traffic": mix,
            "end_to_end": manifest["end_to_end"], "per_layer": []}


def _passes(numbers):
    return all(numbers[k] <= run.LIMITS[k] for k in run.LIMITS)


@pytest.mark.parametrize("config,traffic", [("homog32_small", "ga.synth"),
                                            ("homog32_small", "br8.synth")])
def test_program_passes_and_control_fails(config, traffic):
    rows = control.readings(_spec(config, traffic), [2 ** 31 + 11, 3], 1.5,
                            t_start=time.perf_counter())
    for r in rows:
        assert r["checked"] >= 2 and r["failed"] == 0
        assert _passes(r["program"]), r["program"]
        assert not _passes(r["control"]), r["control"]


def _stale(score):
    """Answers a batch size's first result again and again."""
    first = {}

    def wrapped(batch, *args):
        n = batch["W"].shape[0]
        if n not in first:
            first[n] = score(batch, *args)
        return first[n]
    return wrapped


def _half(score):
    """Scores the first half of a batch and repeats it for the rest."""
    def wrapped(batch, *args):
        out = score(batch, *args)
        n = batch["W"].shape[0]
        idx = np.arange(n) % max(n // 2, 1)
        return {k: v[idx] for k, v in out.items()}
    return wrapped


def _altered(score):
    """One answer changed where it is produced: each placement's C2M
    latency one cycle longer."""
    def wrapped(batch, *args):
        out = dict(score(batch, *args))
        out["lat_c2m"] = out["lat_c2m"] + 1.0
        return out
    return wrapped


@pytest.mark.parametrize("fault", [_stale, _half, _altered])
def test_broken_scorer_is_not_correct(fault, monkeypatch):
    build = run.build

    def broken(*args):
        ref_arch, ev, params, draws = build(*args)
        ev.scorer = fault(ev.scorer)
        return ref_arch, ev, params, draws

    monkeypatch.setattr(run, "build", broken)
    spec = _spec()
    out = run.run_cell(spec, 7, 1.5, False, t_start=time.perf_counter())
    line = run.result_line(spec, out, False)
    assert line["correct"] is False, line["checks"]



@pytest.mark.parametrize("kind,key,factor", [
    ("area", None, 1.01), ("lat", "c2m", 0.5)])
def test_broken_normalizers_are_not_correct(kind, key, factor, monkeypatch):
    """A normalizer altered where the draw produces it: the program's
    costs then divide by it, and norm_gap and cost_gap see it."""
    from repro.core.objective import norms_vec
    build = run.build

    def broken(*args):
        ref_arch, ev, params, draws = build(*args)
        if key is None:
            setattr(ev.norm, kind, getattr(ev.norm, kind) * factor)
        else:
            getattr(ev.norm, kind)[key] *= factor
        ev._norm_vec = norms_vec(ev.norm)
        return ref_arch, ev, params, draws

    monkeypatch.setattr(run, "build", broken)
    spec = _spec()
    out = run.run_cell(spec, 5, 1.5, False, t_start=time.perf_counter())
    line = run.result_line(spec, out, False)
    assert line["correct"] is False, line["checks"]
    for k in ("norm_gap", "cost_gap"):
        assert line["checks"][k]["value"] > run.LIMITS[k], k


def test_unbounded_optimizer_is_refused_before_the_search(monkeypatch):
    """sa-batched has no generation boundary the window can see: the
    harness refuses it before it builds or searches anything."""
    def never(*args):
        raise AssertionError("set-up started")

    monkeypatch.setattr(run, "build", never)
    spec = _spec(traffic="sa.synth")
    with pytest.raises(run.SetupError, match="sa-batched"):
        run.run_cell(spec, 1, 1.5, False, t_start=time.perf_counter())


def test_search_key_builds_the_registered_params():
    from repro.core import api
    spec = _spec(traffic="br8.synth")
    assert run.search_of(spec["config"], spec["traffic"]) == (
        "br-batched", api.BRParams(batch=8))
    assert run.search_of(spec["config"], _spec()["traffic"]) == (
        "ga-batched", api.GAParams(population=12, elitism=4, tournament=4,
                                   p_mutation=0.5))


# Runs br-batched at homog32_small on four host devices (the sharded
# route) and on one (the default route), each with a window of exactly
# one generation (``seconds`` 0: the window closes at the first boundary
# after it opens), and prints whether each run is correct, how many calls
# went through the sharded scorer, and whether both windows scored the
# same W, flags and costs.
_SHARDED = """
import json, sys, time
import numpy as np
sys.path.insert(0, {root!r})
from bench import run
from bench.tests import test_bench_control as t
from repro.sharding import population
calls = []
shard_scorer = population.shard_scorer
def counted(*args):
    fn = shard_scorer(*args)
    def call(*a):
        calls.append(fn.n_devices)
        return fn(*a)
    return call
population.shard_scorer = counted
spec = t._spec(traffic="br8.synth")
out = {{}}
for chips in (4, 1):
    calls.clear()
    cell = dict(spec, cell={{"chips": chips}})
    res = run.run_cell(cell, 2 ** 31 + 5, 0.0, False,
                       t_start=time.perf_counter())
    line = run.result_line(cell, res, False)
    rounds = res["rec"].rounds()
    out[chips] = {{"correct": line["correct"], "count": line["device"]["count"],
                  "sharded": sorted(set(calls)),
                  "generations": res["run"]["generations"],
                  "rounds": [(np.asarray(W), np.asarray(o["connected"]),
                              np.asarray(o["cost"]))
                             for _, _, _, W, o in rounds]}}
a, b = out[4]["rounds"], out[1]["rounds"]
same = len(a) == len(b) > 0 and all(
    np.array_equal(x, y) and x.dtype == y.dtype
    for ra, rb in zip(a, b) for x, y in zip(ra, rb))
print(json.dumps({{"correct": [out[4]["correct"], out[1]["correct"]],
                  "count": out[4]["count"], "rounds": len(a),
                  "sharded": [out[4]["sharded"], out[1]["sharded"]],
                  "generations": [out[4]["generations"],
                                  out[1]["generations"]],
                  "same": same}}))
"""


def test_sharded_route_matches_one_device():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.path.join(run.ROOT, "src")
    out = subprocess.run([sys.executable, "-c",
                          _SHARDED.format(root=run.ROOT)],
                         cwd=run.ROOT, env=env, capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["correct"] == [True, True], got
    assert got["count"] == 4 and got["generations"] == [1, 1], got
    assert got["sharded"] == [[4], []], got
    assert got["rounds"] >= 1 and got["same"], got
