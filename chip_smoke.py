#!/usr/bin/env python3
"""Run the placement search once on a TPU through its normal entry points.

    python3 chip_smoke.py               # one chip: the phases below
    python3 chip_smoke.py --four-chips  # population sharding over 4 chips

One process, one chip.  Phases (paper defaults, seeded):

* homog64 ``ga-batched`` with ``fw-ref`` and with ``fw-tiled``: equal
  ``best_cost`` and ``best_sol`` bit for bit, and the best placement
  re-scored through the host graph builder and the float64 host
  objective within rtol 1e-4;
* hetero64 ``ga-batched`` with ``fw-tiled`` (host corner placement plus
  the batched Boruvka), re-scored the same way;
* homog256 ``br-batched``, one generation with ``fw-tiled``, and one batch
  of homog256 graphs through ``fw-tiled`` and ``fw-ref``: equal D and N
  bit for bit (the blocked-tile kernel at padded V 1536);
* a ``DesignEngine`` serving four mixed requests (homog64, hetero64,
  gw3d64, homog64 with a ``trace-lat`` workload), all ``done``.

Every scorer compiled with a Pallas backend must hold Mosaic kernels
(``tpu_custom_call``) and only the FW kernels of ``repro.kernels.minplus``
— an interpret-mode kernel would lower to plain HLO.  With
``--four-chips`` only the sharded path runs: a homog64 ``ga-batched``
sweep with ``run_sweep(shard=True)`` over four devices against the same
sweep unsharded, records equal bit for bit.

Each phase prints its wall time and the part of it spent tracing,
lowering and compiling.  The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Any failed check exits non-zero without it; so does a run where JAX finds
no TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# Pallas kernels the scorer may lower to Mosaic custom calls (the ``name``
# of each pallas_call in repro.kernels.minplus).
FW_KERNELS = re.compile(r"fw_counts_vmem|fw_tiled_(diag|row_panel|col_panel"
                        r"|outer)")


class SmokeFailure(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# Timing: wall time per phase, with JAX's trace/lower/compile events summed
# separately.  The backend-compile event spans a persistent-cache read as
# well as a compile, so it counts programs built either way.
# ---------------------------------------------------------------------------

_COMPILE = {"s": 0.0, "n": 0}


def _on_event(event: str, secs: float, **_) -> None:
    if event.startswith("/jax/core/compile/"):
        _COMPILE["s"] += secs
        if event == "/jax/core/compile/backend_compile_duration":
            _COMPILE["n"] += 1


def phase(name: str, fn):
    s0, n0, t0 = _COMPILE["s"], _COMPILE["n"], time.perf_counter()
    out = fn()
    wall = time.perf_counter() - t0
    print(f"phase {name}: wall {wall:.3f} s, compile {_COMPILE['s'] - s0:.3f}"
          f" s ({_COMPILE['n'] - n0} backend compiles)", flush=True)
    return out


# ---------------------------------------------------------------------------
# Checks shared by the phases.
# ---------------------------------------------------------------------------

def kernel_names(compiled_text: str) -> list[str]:
    """Names of the Mosaic kernels (tpu_custom_call) in compiled HLO."""
    names = []
    for line in compiled_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            names.append(line.split("=", 1)[0].strip().lstrip("%"))
    return names


def check_kernels(fn, args, what: str) -> list[str]:
    """``fn`` (jitted) must compile to Mosaic FW kernels and no other."""
    names = kernel_names(fn.lower(*args).compile().as_text())
    check(names, f"{what}: no tpu_custom_call — Pallas ran interpreted")
    stray = [n for n in names if not FW_KERNELS.search(n)]
    check(not stray, f"{what}: unexpected Mosaic kernels {stray}")
    return names


def config(arch: str, algo: str, backend: str, *, evals: int, seed: int = 0,
           **kw):
    from repro.core.api import Budget, ExperimentConfig
    return ExperimentConfig(arch=arch, algorithms=(algo,), backend=backend,
                            budget=Budget(evals=evals), seed=seed, **kw)


def ga_evals(arch: str, generations: int) -> int:
    """Evaluation budget of ``generations`` ga-batched generations at the
    arch's paper-default population."""
    from repro.core.api import ExperimentConfig
    p = ExperimentConfig(arch=arch).resolved_params("ga-batched")
    return p.population + generations * (p.population - p.elitism)


def evaluator_for(cfg, norm):
    import numpy as np
    from repro.core.api import make_evaluator, make_rep
    from repro.core.chiplets import resolve_arch
    arch = resolve_arch(cfg.arch, cfg.config)
    rep = make_rep(arch, cfg.arch, cfg.mutation_mode)
    ev = make_evaluator(rep, arch, rng=np.random.default_rng(0),
                        norm_samples=0, chunk=cfg.chunk,
                        backend=cfg.backend, objective=cfg.objective,
                        norm=norm, workload=cfg.workload)
    return rep, ev


def host_rescore(cfg, rec):
    """Best placement -> host graph builder -> scorer metrics -> float64
    host objective; must match the run's in-jit float32 best cost.
    Returns the host cost, the evaluator and the one-row batch."""
    import numpy as np
    from repro.core.objective import objective_cost_host
    from repro.core.topology import stack_graphs
    res = rec.result
    rep, ev = evaluator_for(cfg, res.normalizers)
    g = rep.score_graph(res.best_sol)
    check(g.connected, f"{cfg.arch}: best placement is not connected")
    batch = stack_graphs([g])
    metrics = ev.score_batch(batch)
    host = float(objective_cost_host(metrics, cfg.objective, ev.norm,
                                     batch=batch, vp=rep.layout.Vp)[0])
    check(np.isfinite(res.best_cost) and
          np.isclose(host, res.best_cost, rtol=1e-4, atol=0),
          f"{cfg.arch}: float64 host cost {host!r} vs best_cost "
          f"{res.best_cost!r}")
    return host, ev, batch


def scorer_kernels(ev, batch, what: str) -> list[str]:
    """The Mosaic kernels in the evaluator's compiled scorer."""
    import jax.numpy as jnp
    batch = {k: jnp.asarray(v) for k, v in ev._with_demand(batch).items()}
    return check_kernels(ev.scorer, (batch, jnp.asarray(ev.norm_vec),
                                     jnp.asarray(ev.weights_vec)), what)


def same_records(a, b, what: str) -> None:
    import numpy as np
    check(len(a) == len(b), f"{what}: {len(a)} vs {len(b)} records")
    for x, y in zip(a, b):
        rx, ry = x.result, y.result
        check(rx.best_cost == ry.best_cost,
              f"{what}: best_cost {rx.best_cost!r} vs {ry.best_cost!r}")
        for u, v in zip(rx.best_sol, ry.best_sol):
            check(np.array_equal(np.asarray(u), np.asarray(v)),
                  f"{what}: best_sol differs")
        for k in rx.best_metrics:
            check(np.array_equal(np.asarray(rx.best_metrics[k]),
                                 np.asarray(ry.best_metrics[k])),
                  f"{what}: best_metrics[{k!r}] differs")
        check(rx.n_evaluated == ry.n_evaluated,
              f"{what}: n_evaluated {rx.n_evaluated} vs {ry.n_evaluated}")


def one_record(sweep):
    recs = sweep.records
    check(len(recs) == 1, f"expected one record, got {len(recs)}")
    return recs[0]


# ---------------------------------------------------------------------------
# Phases.
# ---------------------------------------------------------------------------

def homog_backends(arch: str = "homog64", generations: int = 4) -> None:
    from repro.core.api import run_sweep
    evals = ga_evals(arch, generations)
    cfg_ref = config(arch, "ga-batched", "fw-ref", evals=evals)
    cfg_tiled = config(arch, "ga-batched", "fw-tiled", evals=evals)
    ref = one_record(phase(f"{arch} ga-batched fw-ref",
                           lambda: run_sweep([cfg_ref])))
    tiled = one_record(phase(f"{arch} ga-batched fw-tiled",
                             lambda: run_sweep([cfg_tiled])))
    same_records([ref], [tiled], f"{arch} fw-ref vs fw-tiled")
    host, ev, batch = host_rescore(cfg_tiled, tiled)
    names = scorer_kernels(ev, batch, f"{arch} fw-tiled scorer")
    print(f"  {arch}: best_cost {tiled.result.best_cost!r} (fw-ref == "
          f"fw-tiled), float64 host {host!r}, evaluated "
          f"{tiled.result.n_evaluated}, kernels {sorted(set(names))}",
          flush=True)


def hetero(arch: str = "hetero64", generations: int = 4) -> None:
    from repro.core.api import run_sweep
    cfg = config(arch, "ga-batched", "fw-tiled",
                 evals=ga_evals(arch, generations))
    rec = one_record(phase(f"{arch} ga-batched fw-tiled",
                           lambda: run_sweep([cfg])))
    host, _, _ = host_rescore(cfg, rec)
    print(f"  {arch}: best_cost {rec.result.best_cost!r}, float64 host "
          f"{host!r}, evaluated {rec.result.n_evaluated}", flush=True)


def large_tiled(arch: str = "homog256", batch: int = 32,
                fw_batch: int = 4) -> None:
    import jax
    import numpy as np
    from repro.core.api import run_sweep
    from repro.core.proxies import fw_counts_ref
    from repro.kernels.ops import fw_impl_tiled
    cfg = config(arch, "br-batched", "fw-tiled", evals=batch,
                 params={"br-batched": {"batch": batch}})
    rec = one_record(phase(f"{arch} br-batched fw-tiled (1 generation)",
                           lambda: run_sweep([cfg])))
    check(np.isfinite(rec.result.best_cost), f"{arch}: best_cost not finite")
    rep, _ = evaluator_for(cfg, rec.result.normalizers)
    rng = np.random.default_rng(1)
    W = np.stack([rep.score_graph(rep.random(rng)).W
                  for _ in range(fw_batch)])
    tiled_fn, ref_fn = jax.jit(fw_impl_tiled), jax.jit(fw_counts_ref)
    names = check_kernels(tiled_fn, (W,), f"{arch} fw-tiled")

    def both():
        (d1, n1), (d2, n2) = tiled_fn(W), ref_fn(W)
        return [np.asarray(x) for x in (d1, n1, d2, n2)]

    d1, n1, d2, n2 = phase(f"{arch} FW fw-tiled + fw-ref, batch "
                           f"{fw_batch} at V {W.shape[-1]}", both)
    check(np.array_equal(d1, d2), f"{arch}: fw-tiled D != fw-ref D")
    check(np.array_equal(n1, n2), f"{arch}: fw-tiled N != fw-ref N")
    print(f"  {arch}: best_cost {rec.result.best_cost!r}, evaluated "
          f"{rec.result.n_evaluated}; D/N bit-for-bit over "
          f"{fw_batch} graphs, kernels {sorted(set(names))}", flush=True)


def design_engine(generations: int = 2) -> None:
    from repro.core.api import DesignRequest
    from repro.core.chiplets import resolve_arch
    from repro.core.objective import Objective, TermSpec
    from repro.netsim import Workload
    from repro.serve.design import DesignEngine
    wl = Workload.synthetic(resolve_arch("homog64", "baseline").kinds(),
                            "c2m", 0.02)
    trace = Objective().with_terms(TermSpec("trace-lat", weight=0.5))
    reqs = [
        DesignRequest(config(a, "ga-batched", "fw-tiled", seed=s,
                             evals=ga_evals(a, generations), **kw),
                      request_id=rid)
        for rid, a, s, kw in (
            ("homog64", "homog64", 1, {}),
            ("hetero64", "hetero64", 2, {}),
            ("gw3d64", "gw3d64", 3, {}),
            ("homog64-trace-lat", "homog64", 4,
             {"objective": trace, "workload": wl}))]

    def serve():
        eng = DesignEngine(max_active=len(reqs))
        ids = [eng.submit(r) for r in reqs]
        eng.run()
        return eng, [eng.result(i) for i in ids]

    eng, out = phase("DesignEngine, 4 mixed requests", serve)
    for r in out:
        check(r.status == "done",
              f"request {r.request_id}: status {r.status} "
              f"({r.error})")
        check(r.best_cost is not None, f"{r.request_id}: no best_cost")
        print(f"  {r.request_id}: done, best_cost {r.best_cost!r}",
              flush=True)
    print(f"  engine: {eng.stats}", flush=True)


def four_chips(arch: str = "homog64", generations: int = 4) -> None:
    import jax
    from repro.core.api import run_sweep
    n = len(jax.devices())
    cfg = config(arch, "ga-batched", "fw-tiled",
                 evals=ga_evals(arch, generations))
    plain = phase(f"{arch} ga-batched unsharded", lambda: run_sweep([cfg]))
    sharded = phase(f"{arch} ga-batched sharded over {n} devices",
                    lambda: run_sweep([cfg], shard=True))
    check(sharded.stats.shard_devices == n,
          f"sharded over {sharded.stats.shard_devices} devices, not {n}")
    same_records(plain.records, sharded.records,
                 f"{arch} sharded vs unsharded")
    rec = sharded.records[0].result
    print(f"  {arch}: sharded == unsharded over {n} devices, best_cost "
          f"{rec.best_cost!r}, evaluated {rec.n_evaluated}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the population-sharded sweep on 4 chips")
    args = ap.parse_args(argv)
    need = 4 if args.four_chips else 1

    import jax
    from repro.launch.compile_cache import enable_compile_cache
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {devs[0].platform!r})",
              file=sys.stderr)
        return 1
    if len(devs) < need:
        print(f"chip_smoke: needs {need} chips, JAX sees {len(devs)}",
              file=sys.stderr)
        return 1
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"device: {device}", flush=True)

    cache = enable_compile_cache(ROOT)
    warm = os.path.isdir(cache) and bool(os.listdir(cache))
    print(f"compile cache: {cache} ({'warm' if warm else 'cold'})",
          flush=True)
    jax.monitoring.register_event_duration_secs_listener(_on_event)

    t0 = time.perf_counter()
    try:
        if args.four_chips:
            four_chips()
        else:
            homog_backends()
            hetero()
            large_tiled()
            design_engine()
        check("repro.launch.dryrun" not in sys.modules,
              "repro.launch.dryrun was imported (it sets XLA_FLAGS)")
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    print(f"total: {time.perf_counter() - t0:.3f} s, compile "
          f"{_COMPILE['s']:.3f} s", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
