"""Pareto frontier engine: device dominance + stacked scalarization grids.

Two sections (PR 5):

* **dominance** — non-dominated masking over a [B, n] cost matrix: the
  brute-force host reference (per-point python/numpy scan, what the
  literature's naive front extraction does) vs the jitted vectorized
  [B, B, n] comparison (``pareto.nondominated_mask``), plus the 2D
  hypervolume sweep.  The device mask is bit-for-bit the host mask
  (asserted here on every measured matrix).
* **grid_sweep** — a TrafficMix/weight scalarization grid run through
  ``run_pareto_sweep``: because objective weights are *runtime* vectors,
  the whole grid shares one compiled scorer and executes in
  ``drive_stacked`` lockstep.  Reports scorer compilations, lockstep
  groups and scorer dispatches vs the same grid unstacked, and the
  resulting front size/hypervolume.

Results go to stdout as BENCH lines and to
``artifacts/bench/pareto_frontier.json``.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

from .common import budget, emit, out_dir


def _dominance_rates(B: int, d: int = 3, reps: int = 5
                     ) -> tuple[float, float, int]:
    """(host_matrices_per_s, device_matrices_per_s, front_size)."""
    from repro.core.pareto import nondominated_mask, nondominated_mask_host
    rng = np.random.default_rng(0)
    Y = (rng.random((B, d)) ** 2).astype(np.float32)
    dev = nondominated_mask(Y)                      # warm the jit
    host = nondominated_mask_host(Y)
    assert np.array_equal(dev, host), "device front != host brute force"

    t_host = np.inf
    for _ in range(max(1, reps // 2)):
        t0 = time.perf_counter()
        nondominated_mask_host(Y)
        t_host = min(t_host, time.perf_counter() - t0)
    t_dev = np.inf
    for _ in range(reps):
        t0 = time.perf_counter()
        np.asarray(nondominated_mask(Y))
        t_dev = min(t_dev, time.perf_counter() - t0)
    return 1.0 / t_host, 1.0 / t_dev, int(dev.sum())


def _grid_sweep_stats(quick: bool) -> dict:
    from repro.core.api import (Budget, ExperimentConfig,
                                clear_scorer_cache)
    from repro.core.pareto import ParetoGridSpec, run_pareto_sweep
    evals = budget(quick, 8, 48)
    cfg = ExperimentConfig(
        arch="homog32", algorithms=("br",), budget=Budget(evals=evals),
        norm_samples=budget(quick, 4, 16), chunk=4,
        params={"br": {"batch": 4}})
    grid = ParetoGridSpec(term_weights={
        "lat": (0.5, 1.0, 2.0), "inv-thr": (0.5, 2.0)})
    clear_scorer_cache()
    t0 = time.perf_counter()
    stacked = run_pareto_sweep(cfg, grid)
    t_stacked = time.perf_counter() - t0
    t0 = time.perf_counter()
    unstacked = run_pareto_sweep(cfg, grid, stack_scoring=False)
    t_unstacked = time.perf_counter() - t0
    (front,) = stacked.fronts
    return dict(
        grid_points=grid.n_points,
        scorers_built=stacked.stats.scorers_built,
        stacked_groups=stacked.stats.stacked_groups,
        stacked_score_calls=stacked.stats.score_calls,
        unstacked_score_calls=unstacked.stats.score_calls,
        stacked_seconds=t_stacked, unstacked_seconds=t_unstacked,
        front_size=len(front.points), n_candidates=front.n_candidates,
        hypervolume=front.hypervolume)


def run(quick: bool = True) -> dict:
    results: dict = {}
    # dominance masks: host brute force vs jitted device comparison
    for B in budget(quick, (64, 256), (256, 1024, 4096)):
        h, d, fs = _dominance_rates(B)
        results[f"dominance_B{B}"] = dict(
            host_per_s=h, device_per_s=d, speedup=d / h, front_size=fs)
        emit(f"pareto_dominance_B{B}_speedup", round(d / h, 1),
             f"{d / h:.1f}x device [B,B,n] mask over host brute force "
             "(bit-for-bit asserted)")
    # one stacked scorer across a whole scalarization grid
    gs = _grid_sweep_stats(quick)
    results["grid_sweep"] = gs
    emit("pareto_grid_scorers_built", gs["scorers_built"],
         f"{gs['grid_points']} scalarizations share one compiled scorer "
         "(weights are runtime)")
    emit("pareto_grid_dispatch_ratio",
         round(gs["unstacked_score_calls"]
               / max(gs["stacked_score_calls"], 1), 2),
         f"{gs['unstacked_score_calls']} unstacked vs "
         f"{gs['stacked_score_calls']} stacked scorer dispatches")
    emit("pareto_grid_front_size", gs["front_size"],
         f"non-dominated of {gs['n_candidates']} candidates; "
         f"hypervolume {gs['hypervolume']:.3f}")
    with open(os.path.join(out_dir(), "pareto_frontier.json"), "w") as f:
        json.dump(results, f, indent=1, default=float)
    return results


def main(quick: bool = True):
    run(quick)


if __name__ == "__main__":
    main(quick=os.environ.get("BENCH_FULL", "") != "1")
