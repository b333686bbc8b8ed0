#!/usr/bin/env python3
"""Readings for the limits of `correct`: the program's and the control's.

    python3 bench/control.py --workload homog64.ga.synth --seconds 8 \\
        --seeds 11 12 13 --norm-seeds 21 22 23

In one process: set up and run a window as ``run.py`` does, then for
each seed draw the sample a run with that seed checks, and compare with
the float64 reference both what the program produced and what the
control gives on the same placements.  Each norm seed builds the
Evaluator again with its normalizer draw taken from that seed, and
compares the program's normalizers and the control's with the
reference's on that draw.  The control is the reference put in the
program's place one precision lower than the configuration's float32:
Floyd-Warshall distances and path counts held in bfloat16, and every
metric and cost rounded to bfloat16.  It has to fail at least one of the
numbers.

Prints one JSON line per seed, then a summary: the largest reading of
each number over the program's runs (the lower reading of its limit)
and the smallest over the control's (the upper reading).  The benchmark's
own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import reference, run  # noqa: E402


def _bf16():
    import ml_dtypes
    return ml_dtypes.bfloat16


def control_items(refs_low, norms: dict, objective: dict) -> list[dict]:
    """The control's answers: the reference's graph and connectivity,
    with bfloat16 metrics and cost."""
    def low(x):
        return float(np.asarray(x, np.float64).astype(_bf16()))

    out = []
    for ref in refs_low:
        m = {k: low(v) for k, v in ref["metrics"].items()}
        out.append({"W": ref["graph"].full_W().astype(np.float32),
                    "connected": ref["connected"],
                    "metrics": dict(m, cost=low(reference.cost(
                        m, objective, norms)))})
    return out


def control_norms(arch, draws, config: dict, objective: dict) -> dict:
    """The control's normalizers: the reference's on bfloat16 paths."""
    return reference.normalizers(arch, draws, config["norm_samples"],
                                 objective["normalizer"], dtype=_bf16())


def readings(spec: dict, seeds, seconds: float, **kw) -> list[dict]:
    """The program's and the control's numbers for each seed, on one
    window: the search is seeded by its configuration, so every run's
    window holds the same placements and a run's seed draws the sample
    it checks."""
    out = run.run_cell(spec, seeds[0], seconds, False, **kw)
    config, objective = spec["config"], spec["traffic"]["objective"]
    arch = out["arch"]
    low_norms = control_norms(arch, out["draws"], config, objective)
    rows = []
    for seed in seeds:
        items = run.sample(out["rec"], seed)
        refs = run.reference_of(arch, items)
        low = run.reference_of(arch, items, dtype=_bf16())
        prog, failed, widest = run.compare(items, refs, objective,
                                           out["norms"], out["ref_norms"])
        ctrl, _, ctrl_widest = run.compare(
            control_items(low, low_norms, objective), refs, objective,
            low_norms, out["ref_norms"])
        rows.append({"seed": seed, "program": prog, "control": ctrl,
                     "widest": [widest, ctrl_widest],
                     "checked": len(items), "failed": failed,
                     "placements": out["run"]["n_evaluated"]})
    return rows


def norm_readings(spec: dict, norm_seeds) -> list[dict]:
    """norm_gap of the program and of the control on normalizer draws
    taken from other seeds."""
    rows = []
    objective = spec["traffic"]["objective"]
    for s in norm_seeds:
        config = dict(spec["config"], search_seed=s)
        arch, ev, _, draws = run.build(config, spec["traffic"])
        ref = reference.normalizers(arch, draws, config["norm_samples"],
                                    objective["normalizer"])
        gaps = {}
        for who, norms in (("program", run.normalizers(ev)),
                           ("control", control_norms(arch, draws, config,
                                                     objective))):
            gaps[who] = max(reference.rel_gap(norms[k], v)
                            for k, v in ref.items())
        rows.append({"norm_seed": s, "draws": len(draws), **gaps})
    return rows


def summary(rows: list[dict], norm_rows=()) -> dict:
    keys = rows[0]["program"]
    out = {k: {"program_max": max(r["program"][k] for r in rows),
               "control_min": min(r["control"][k] for r in rows),
               "limit": run.LIMITS[k]} for k in keys}
    for r in norm_rows:
        out["norm_gap"]["program_max"] = max(out["norm_gap"]["program_max"],
                                             r["program"])
        out["norm_gap"]["control_min"] = min(out["norm_gap"]["control_min"],
                                             r["control"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--norm-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = run.CACHE_DIR
    spec = run.load_cell(args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 2
    run.enable_cache()
    rows = readings(spec, args.seeds, args.seconds)
    for r in rows:
        print(json.dumps(r), flush=True)
    norm_rows = norm_readings(spec, args.norm_seeds)
    for r in norm_rows:
        print(json.dumps(r), flush=True)
    print(json.dumps({"summary": summary(rows, norm_rows)}), flush=True)
    return 0 if all(math.isfinite(r["program"]["cost_gap"])
                    for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
