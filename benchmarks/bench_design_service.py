"""Design service: continuous-batching throughput vs sequential runs.

Two sections (PR 6):

* **batching** — N concurrent tenant requests (mixed seeds/objective
  weights, same term structure) through one :class:`DesignEngine` vs the
  same N configs run back-to-back with ``run_experiment``-style
  sequential sweeps.  Reports scorer dispatches (the engine stacks every
  tick's pending generations into one call), requests/s, and the
  streamed-update counts.  Results are bit-for-bit identical either way
  (asserted here on every measured run).
* **shard** — the same engine with the population-axis ``shard_map``
  wrapper on, pinning the single-device fallback's overhead (and, on a
  multi-device host, the scaling path).

Results go to stdout as BENCH lines and to
``artifacts/bench/design_service.json``.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

from .common import budget, emit, out_dir


def _requests(n: int, evals: int, norm_samples: int):
    from repro.core.api import Budget, DesignRequest, ExperimentConfig
    reqs = []
    for i in range(n):
        cfg = ExperimentConfig(
            arch="homog32", algorithms=("br", "ga"),
            budget=Budget(evals=evals), norm_samples=norm_samples,
            chunk=4, seed=i, params={"br": {"batch": 4}})
        reqs.append(DesignRequest(config=cfg, request_id=f"tenant-{i}"))
    return reqs


def _batching_stats(quick: bool) -> dict:
    from repro.core.api import clear_scorer_cache, run_sweep
    from repro.serve.design import DesignEngine
    n = budget(quick, 4, 8)
    evals = budget(quick, 12, 60)
    norm_samples = budget(quick, 4, 16)
    reqs = _requests(n, evals, norm_samples)

    clear_scorer_cache()
    eng = DesignEngine(max_active=n)
    t0 = time.perf_counter()
    for r in reqs:
        eng.submit(r)
    eng.run()
    t_engine = time.perf_counter() - t0
    responses = [eng.result(r.request_id) for r in reqs]

    # Sequential baseline: one isolated sweep per tenant, back-to-back.
    clear_scorer_cache()
    t0 = time.perf_counter()
    seq_calls = 0
    seq_records = []
    for r in reqs:
        sw = run_sweep([r.config], fold_repetitions=False)
        seq_calls += sw.stats.score_calls
        seq_records.extend(sw.records)
    t_seq = time.perf_counter() - t0

    eng_records = [rec for resp in responses for rec in resp.records]
    for a, b in zip(eng_records, seq_records):
        assert a.result.best_cost == b.result.best_cost, \
            "engine result diverged from sequential run"
    updates = [len([u for u in resp.updates if u.kind == "progress"])
               for resp in responses]
    return dict(
        n_requests=n, evals_per_request=evals,
        engine_score_calls=eng.stats.score_calls,
        sequential_score_calls=seq_calls,
        stacked_rounds=eng.stats.stacked_rounds,
        ticks=eng.stats.ticks,
        engine_seconds=t_engine, sequential_seconds=t_seq,
        engine_req_per_s=n / t_engine, sequential_req_per_s=n / t_seq,
        min_progress_updates=min(updates),
        rows_scored=eng.stats.rows_scored)


def _shard_stats(quick: bool) -> dict:
    from repro.serve.design import DesignEngine
    n = budget(quick, 2, 4)
    reqs = _requests(n, budget(quick, 12, 60), budget(quick, 4, 16))
    eng = DesignEngine(max_active=n, shard=True)
    t0 = time.perf_counter()
    for r in reqs:
        eng.submit(r)
    eng.run()
    t = time.perf_counter() - t0
    base = DesignEngine(max_active=n)
    for r in reqs:
        base.submit(r)
    base.run()
    for r in reqs:
        a, b = eng.result(r.request_id), base.result(r.request_id)
        for x, y in zip(a.records, b.records):
            assert x.result.best_cost == y.result.best_cost, \
                "sharded result diverged from unsharded"
    return dict(n_requests=n, devices=eng.stats.shard_devices,
                seconds=t, score_calls=eng.stats.score_calls)


def run(quick: bool = True) -> dict:
    results: dict = {}
    bs = _batching_stats(quick)
    results["batching"] = bs
    emit("design_service_dispatch_ratio",
         round(bs["sequential_score_calls"]
               / max(bs["engine_score_calls"], 1), 2),
         f"{bs['sequential_score_calls']} sequential vs "
         f"{bs['engine_score_calls']} engine scorer dispatches for "
         f"{bs['n_requests']} tenants (bit-for-bit asserted)")
    emit("design_service_req_per_s", round(bs["engine_req_per_s"], 2),
         f"vs {bs['sequential_req_per_s']:.2f} sequential; "
         f"{bs['min_progress_updates']} streamed updates/request min")
    ss = _shard_stats(quick)
    results["shard"] = ss
    emit("design_service_shard_devices", ss["devices"],
         f"population shard_map over {ss['devices']} device(s), "
         "bit-for-bit vs unsharded")
    with open(os.path.join(out_dir(), "design_service.json"), "w") as f:
        json.dump(results, f, indent=1, default=float)
    return results


def main(quick: bool = True):
    run(quick)


if __name__ == "__main__":
    main(quick=os.environ.get("BENCH_FULL", "") != "1")
