"""The check that decides `correct`, on the CPU at a small size: the
program's outputs pass, the control (the reference in bfloat16) fails,
and a run whose scorer is broken underneath comes out not correct."""
import json
import os
import time

import numpy as np
import pytest

from bench import control, run

HERE = os.path.dirname(os.path.abspath(__file__))


def _spec(config="homog32_small", traffic="ga.synth"):
    with open(os.path.join(HERE, config + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(run.BENCH, "traffic", traffic + ".json")) as f:
        mix = json.load(f)
    mix["warm_generations"] = 3
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    return {"cell": {"chips": 1}, "config": cfg, "traffic": mix,
            "end_to_end": manifest["end_to_end"], "per_layer": []}


def _passes(numbers):
    return all(numbers[k] <= run.LIMITS[k] for k in run.LIMITS)


@pytest.mark.parametrize("config,traffic", [("homog32_small", "ga.synth")])
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
