"""Benchmark orchestrator: one module per paper table/figure + beyond-paper.

  PYTHONPATH=src python -m benchmarks.run [--full] [--only NAME]

Emits ``BENCH,name,value,derived`` CSV lines and JSON artifacts under
artifacts/bench/; each module's artifact is additionally *merged* into
``BENCH_<name>.json`` at the repo root so the perf trajectory is versioned
alongside the code (artifacts/ is transient).  Merging is section-wise
(recursive on dict values): a run that only exercises a subset of a
module's sections — quick mode skips expensive ones — updates those keys
and preserves the rest, instead of churning the whole versioned file.
Quick mode targets CI budgets; --full approaches the paper's budgets.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import time
import traceback

MODULES = [
    ("fig6_fig12_optimizers", "paper Figs. 6/12: BR/GA/SA vs baseline"),
    ("fig14_15_synthetic", "paper Figs. 14/15: synthetic traffic"),
    ("fig16_18_traces", "paper Figs. 16-18: trace speedups"),
    ("table5_rate", "paper Table V: placements/s + §VII-E area"),
    ("pipeline_throughput", "beyond-paper: device-resident pipeline vs "
                            "host loop (PR 2)"),
    ("pareto_frontier", "beyond-paper: device Pareto fronts + stacked "
                        "scalarization grids (PR 5)"),
    ("design_service", "beyond-paper: continuous-batching design engine "
                       "vs sequential runs (PR 6)"),
    ("netsim_device", "beyond-paper: device netsim rate model vs host "
                      "sim + trace-guided search (PR 8)"),
    ("kernels", "kernel micro-benches"),
    ("bridge_roofline", "beyond-paper: bridge co-design + roofline"),
]


ARTIFACT_DIR = os.path.join("artifacts", "bench")


def _snapshot() -> dict[str, float]:
    return {p: os.path.getmtime(p)
            for p in glob.glob(os.path.join(ARTIFACT_DIR, "*.json"))}


def _merge(old, new):
    """Section-wise merge: new keys win, dict values merge recursively,
    keys only present in ``old`` survive (partial runs must not drop the
    sections they skipped)."""
    out = dict(old)
    for k, v in new.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = _merge(out[k], v)
        else:
            out[k] = v
    return out


def promote_artifacts(before: dict[str, float]) -> list[str]:
    """Merge artifacts written/updated since ``before`` into the repo-root
    ``BENCH_<stem>.json`` (the versioned perf trajectory).  Non-dict or
    unreadable JSON falls back to a plain copy."""
    promoted = []
    for p in glob.glob(os.path.join(ARTIFACT_DIR, "*.json")):
        if p in before and os.path.getmtime(p) <= before[p]:
            continue
        stem = os.path.splitext(os.path.basename(p))[0]
        dst = f"BENCH_{stem}.json"
        merged = None
        if os.path.exists(dst):
            try:
                with open(p) as f:
                    new = json.load(f)
                with open(dst) as f:
                    old = json.load(f)
                if isinstance(new, dict) and isinstance(old, dict):
                    merged = _merge(old, new)
            except (json.JSONDecodeError, OSError):
                merged = None
        if merged is not None:
            with open(dst, "w") as f:
                json.dump(merged, f, indent=1)
        else:
            shutil.copyfile(p, dst)
        promoted.append(dst)
    return promoted


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    t_all = time.monotonic()
    failures = []
    for name, desc in MODULES:
        if args.only and args.only not in name:
            continue
        mod = __import__(f"benchmarks.bench_{name}", fromlist=["main"])
        print(f"\n=== bench_{name}: {desc} ===", flush=True)
        t0 = time.monotonic()
        before = _snapshot()
        try:
            mod.main(quick=not args.full)
            promoted = promote_artifacts(before)
            print(f"=== bench_{name} done in "
                  f"{time.monotonic() - t0:.1f}s"
                  + (f"; promoted {', '.join(promoted)}" if promoted else "")
                  + " ===", flush=True)
        except Exception as e:  # noqa: BLE001 — keep the suite running
            failures.append(name)
            print(f"=== bench_{name} FAILED: {type(e).__name__}: {e} ===")
            traceback.print_exc()
    print(f"\nTOTAL {time.monotonic() - t_all:.1f}s; "
          f"failures: {failures or 'none'}")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
