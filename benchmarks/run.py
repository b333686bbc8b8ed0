"""Benchmark orchestrator: one module per paper table/figure + beyond-paper.

  PYTHONPATH=src python -m benchmarks.run [--full] [--only NAME]

Emits ``BENCH,name,value,derived`` CSV lines and JSON artifacts under
artifacts/bench/ (not versioned).  These are rehearsals on whatever
backend JAX finds; the benchmark measured on the chip is ``bench/run.py``
(``BENCHMARK.json``).  Quick mode targets CI budgets; --full approaches
the paper's budgets.
"""
from __future__ import annotations

import argparse
import os
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
        try:
            mod.main(quick=not args.full)
            print(f"=== bench_{name} done in "
                  f"{time.monotonic() - t0:.1f}s ===", flush=True)
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
