"""Device-resident pipeline throughput: host-loop vs batched path.

PlaceIT's runtime is dominated by placement evaluation (paper Table V); PR 2
moved the *production* side — generate / mutate / merge, link inference and
ScoreGraph assembly — onto the device as fused batched calls
(``optimize.DevicePipeline``).  This bench measures placements per second on
three homogeneous grids for:

* **prep** (the pipeline stage this PR moved on-device): producing a
  scorable ScoreGraph batch from parents / randomness.  Host = per-child
  Python ``merge -> mutate -> score_graph`` (includes the union-find
  connectivity pass); device = one fused ``merge_batch -> mutate_batch ->
  build`` call (connectivity rides the scorer's FW pass, so the device
  number excludes it — see the emitted note).
* **e2e** (prep + proxy scoring with the shared jitted scorer): a full GA
  generation including retry-until-connected (host) / mask-and-resample
  (device).  On CPU both paths are Floyd-Warshall-bound, so this ratio
  mostly tracks the scorer; the prep ratio is the one the refactor targets.

PR 3 extends the same measurement to the heterogeneous path (hetero32):
host per-child corner placement + Kruskal MST vs the batched pipeline
(device operators, vectorized host corner placement, batched Borůvka link
inference + ScoreGraph assembly on device).

PR 4 adds the **objective ranking** section: once a candidate batch is
scored, picking the best placements used to require pulling all nine
metric arrays to the host and running the numpy cost formula + argsort
per call; the objective layer compiles the cost terms into the jitted
scorer, so cost + top-k selection happen on device
(``Evaluator.topk`` / ``proxies.make_ranker``).  The bench isolates that
stage (host metric conversion + ``total_cost`` + argsort vs the jitted
cost+top-k over device-resident metrics) and also reports the fused
end-to-end ranking call.

PR 7 adds the **large_v** section: per-generation seconds and FW-kernel
comparison (pure-XLA reference vs VMEM-resident Pallas vs blocked-tile
Pallas) on 100+-chiplet archs (homog100 / hex127 / homog256), where the
VMEM-resident kernel's ~3*V^2*4B working set stops fitting and
``ops.fw_impl_tiled`` auto-dispatches to the blocked-tile kernel.  It
also fills the e2e gap: every grid (8x8 and 12x12 included) now emits
``e2e_per_s`` numbers with per-grid batch budgets.

PR 9 adds the **arch3d** section: prep throughput for the 3D /
hierarchical families (``repro.arch3d``) — host per-child Python
(merge + mutate + record-walk graph assembly + union-find) vs one fused
device call through the same pluggable ``DevicePipeline._stages``, with
the tier-value vector (TSV / backbone latency multipliers) as a runtime
jit operand.  Target: >= 3x device over host.

Results go to stdout as BENCH lines and to
``artifacts/bench/pipeline_throughput.json``.
"""
from __future__ import annotations

import json
import os
import time

import jax
import numpy as np

import functools

import jax.numpy as jnp

from repro.core.chiplets import homogeneous_arch, paper_arch
from repro.core.cost import total_cost
from repro.core.objective import compile_objective, norms_vec
from repro.core.optimize import DevicePipeline, Evaluator
from repro.core.placement_hetero import HeteroRep
from repro.core.placement_homog import HomogRep
from repro.core.topology import stack_graphs

from .common import budget, emit, out_dir

# grid name -> (R, C, (n_compute, n_memory, n_io)).  Fully occupied, like
# the paper's grids (homog32 packs 40 chiplets onto 8x5): sparse grids make
# connected placements vanishingly rare under the baseline single-PHY
# memory/IO chiplets.
GRIDS = {
    "6x6": (6, 6, (28, 4, 4)),
    "8x8": (8, 8, (52, 6, 6)),
    "12x12": (12, 12, (128, 8, 8)),
}

# Per-grid e2e budgets (quick, full): e2e includes the FW scorer, whose
# cost grows O(V^3) — larger grids need smaller batches to keep the bench
# bounded.  Every grid gets an e2e number in both modes (PR 7: 8x8/12x12
# previously emitted prep-only artifacts).
E2E_N = {"6x6": (16, 64), "8x8": (8, 32), "12x12": (4, 8)}

# 100+-chiplet archs for the large-V section (quick mode runs the first
# only; full mode all).  V here is the scorer's working matrix side
# (Vp + 2*n virtual rows): homog100 -> 552, hex127 -> 702, homog256 ->
# 1440 — the last pads past ops.FW_TILED_AUTO_V, so auto-dispatch takes
# the blocked-tile kernel and the VMEM-resident kernel could not run
# compiled on a 16 MB-VMEM TPU at all.
LARGE_ARCHS = ("homog100", "hex127", "homog256")


def _host_prep_rate(rep, parents, n: int) -> float:
    """Host-loop GA-generation prep: merge + mutate + score_graph each."""
    rng = np.random.default_rng(1)
    best = np.inf
    for _ in range(3):           # best-of-3: single passes are noisy
        idx = rng.integers(len(parents), size=(n, 2))
        t0 = time.perf_counter()
        for a, b in idx:
            child = rep.merge(parents[a], parents[b], rng)
            if rng.random() < 0.5:
                child = rep.mutate(child, rng)
            rep.score_graph(child)
        best = min(best, time.perf_counter() - t0)
    return n / best


def _device_prep_rate(rep, parents, n: int) -> float:
    """One fused merge_batch -> mutate_batch -> build call for n children.
    Reps with runtime weight tiers (``repro.arch3d``) take the tier
    vector as a trailing stage operand."""
    _, _, _gen, _mut, _child, _ = DevicePipeline._stages(rep)
    tiers = getattr(rep, "tier_values", None)
    extra = () if tiers is None else (jnp.asarray(tiers),)
    rng = np.random.default_rng(1)
    idx = rng.integers(len(parents), size=(n, 2))
    ta = np.stack([parents[a][0] for a, _ in idx])
    ra = np.stack([parents[a][1] for a, _ in idx])
    tb = np.stack([parents[b][0] for _, b in idx])
    rb = np.stack([parents[b][1] for _, b in idx])
    key = jax.random.PRNGKey(0)
    jax.block_until_ready(                                    # warm the jit
        _child(key, ta, ra, tb, rb, 0.5, *extra))
    best = np.inf
    for i in range(1, 4):        # best-of-3: single calls are noisy
        t0 = time.perf_counter()
        jax.block_until_ready(
            _child(jax.random.PRNGKey(i), ta, ra, tb, rb, 0.5, *extra))
        best = min(best, time.perf_counter() - t0)
    return n / best


def _e2e_rates(rep, arch, n: int, chunk: int, norm_samples: int = 8
               ) -> tuple[float, float]:
    """Full GA generation incl. scoring + validity: host retry loop vs
    device mask-and-resample.  Returns (host_per_s, device_per_s)."""
    ev = Evaluator(rep, arch, rng=np.random.default_rng(0),
                   norm_samples=norm_samples, chunk=chunk)
    rng = np.random.default_rng(2)
    parents, _ = ev.generate_valid(rep.random, rng, max(4, n // 4))

    def op(r):
        a = parents[int(r.integers(len(parents)))]
        b = parents[int(r.integers(len(parents)))]
        child = rep.merge(a, b, r)
        if r.random() < 0.5:
            child = rep.mutate(child, r)
        return child

    ev.costs([rep.score_graph(parents[0])] * min(n, chunk))   # warm the jit
    t0 = time.perf_counter()
    sols, graphs = ev.generate_valid(op, rng, n)
    ev.costs(graphs)
    host = n / (time.perf_counter() - t0)

    pipe = ev.pipeline()
    idx = rng.integers(len(parents), size=(n, 2))
    pa_t = np.stack([parents[a][0] for a, _ in idx])
    pa_r = np.stack([parents[a][1] for a, _ in idx])
    pb_t = np.stack([parents[b][0] for _, b in idx])
    pb_r = np.stack([parents[b][1] for _, b in idx])
    pipe.sample_children(rng, pa_t, pa_r, pb_t, pb_r, 0.5)    # warm the jit
    t0 = time.perf_counter()
    _, _, m = pipe.sample_children(rng, pa_t, pa_r, pb_t, pb_r, 0.5)
    ev.costs_from(m)
    dev = n / (time.perf_counter() - t0)
    return host, dev


def _hetero_prep_rates(arch_name: str, n: int) -> tuple[float, float]:
    """GA-generation production on a heterogeneous arch: host per-child
    Python (merge + mutate + corner placement + Kruskal MST + ScoreGraph)
    vs the batched path (fused device operators, vectorized host corner
    placement, batched Borůvka link inference + assembly on device).
    Returns (host_per_s, device_per_s)."""
    arch = paper_arch(arch_name, "baseline")
    rep = HeteroRep(arch)
    rng = np.random.default_rng(0)
    parents = [rep.random(rng) for _ in range(16)]

    best = np.inf
    for _ in range(3):
        idx = rng.integers(len(parents), size=(n, 2))
        t0 = time.perf_counter()
        for a, b in idx:
            child = rep.merge(parents[a], parents[b], rng)
            if rng.random() < 0.5:
                child = rep.mutate(child, rng)
            rep.score_graph(child)
        best = min(best, time.perf_counter() - t0)
    host = n / best

    _, _, _gen, _mut, _child, _ = DevicePipeline._stages(rep)
    idx = rng.integers(len(parents), size=(n, 2))
    oa = np.stack([parents[a][0] for a, _ in idx])
    ra = np.stack([parents[a][1] for a, _ in idx])
    ob = np.stack([parents[b][0] for _, b in idx])
    rb = np.stack([parents[b][1] for _, b in idx])
    jax.block_until_ready(
        _child(jax.random.PRNGKey(0), oa, ra, ob, rb, 0.5)[2]["W"])
    best = np.inf
    for i in range(1, 4):
        t0 = time.perf_counter()
        jax.block_until_ready(
            _child(jax.random.PRNGKey(i), oa, ra, ob, rb, 0.5)[2]["W"])
        best = min(best, time.perf_counter() - t0)
    return host, n / best


def _ranking_rates(arch_name: str, n: int, k: int = 4
                   ) -> tuple[float, float, float]:
    """Cost evaluation + best-placement selection over scored batches.

    * **host stage**: the pre-objective hot path, once per optimizer
      round — numpy float64 ``total_cost`` over the scorer's metrics +
      argsort, take k.  (On the CPU backend ``np.asarray`` of a device
      array is zero-copy, so this isolates formula + sort.)
    * **device stage**: what the objective layer fuses into the scorer —
      jitted vmapped cost + ``top_k`` on the device-resident metrics.
    * **fused e2e**: ``Evaluator.topk`` — score + cost + top-k in one
      call (FW-bound on CPU; the stage ratio is the refactor's target).

    Each measurement ranks ``inner`` independent batches so the timed
    quantum is well above scheduler noise; best-of-5 measurements.
    Returns (host_stage_per_s, device_stage_per_s, fused_per_s).
    """
    arch = paper_arch(arch_name, "baseline")
    from repro.core.api import make_rep
    rep = make_rep(arch, arch_name)
    ev = Evaluator(rep, arch, rng=np.random.default_rng(0), norm_samples=8,
                   chunk=16)
    rng = np.random.default_rng(1)
    _, graphs = ev.generate_valid(rep.random, rng, n)
    batch = stack_graphs(graphs)
    inner = 16
    base = {k2: jnp.asarray(v)
            for k2, v in ev.scorer(batch, ev.norm_vec).items()}
    sets = [jax.block_until_ready({k2: v + 0 for k2, v in base.items()})
            for _ in range(inner)]

    def host_stage():
        out = None
        for dm in sets:
            m = {k2: np.asarray(v) for k2, v in dm.items() if k2 != "cost"}
            costs = np.asarray(total_cost(m, arch, ev.norm))
            out = np.argsort(costs)[:k]
        return out

    cobj = compile_objective(ev.objective)
    row = jnp.asarray(norms_vec(ev.norm))

    @functools.partial(jax.jit, static_argnames=("kk",))
    def dev_one(m, kk):
        # Default-objective terms are metrics-only; no graph arrays needed.
        sample = {k2: v for k2, v in m.items() if k2 != "cost"}
        costs = jax.vmap(lambda s: cobj.cost_one(s, row))(sample)
        return jax.lax.top_k(-costs, kk)[1]

    def dev_stage():
        outs = [dev_one(dm, k) for dm in sets]
        jax.block_until_ready(outs)
        return np.asarray(outs[-1])

    def best_of(fn, reps=5, warm=2):
        for _ in range(warm):
            fn()
        best = np.inf
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    total = n * inner
    host_best = best_of(host_stage)
    dev_best = best_of(dev_stage)
    fused_best = best_of(lambda: ev.topk(batch, k=k), reps=3, warm=1)
    return total / host_best, total / dev_best, n / fused_best


def _large_v_section(arch_name: str, gen_n: int, norm_samples: int,
                     time_vmem: bool) -> dict:
    """Per-generation throughput + FW-kernel comparison at 100+-chiplet V.

    * **generation**: one device GA generation (fused sample_children +
      scoring via ``costs_from``) with the "fw-ref" production backend —
      the per-generation seconds the tiled kernel exists to bound.
    * **kernels**: steady-state FW timings on a real placement's W — the
      pure-XLA reference, the VMEM-resident Pallas kernel (skipped when
      its working set cannot fit VMEM, or when ``time_vmem`` is False),
      and the blocked-tile kernel — plus the static VMEM-feasibility
      numbers driving ``ops.fw_impl_tiled``'s auto-dispatch.
    """
    from repro.core.api import make_evaluator, make_rep
    from repro.core.chiplets import resolve_arch
    from repro.kernels.minplus import fw_counts_pallas, fw_counts_tiled_pallas
    from repro.kernels.ops import FW_TILED_AUTO_V
    from repro.kernels import ref

    arch = resolve_arch(arch_name, "baseline")
    rep = make_rep(arch, arch_name)
    ev = make_evaluator(rep, arch, rng=np.random.default_rng(0),
                        norm_samples=norm_samples, chunk=4, backend="fw-ref")
    pipe = ev.pipeline()
    rng = np.random.default_rng(1)
    parents, _ = ev.generate_valid(rep.random, rng, 4)
    idx = rng.integers(len(parents), size=(gen_n, 2))
    pa_t = np.stack([parents[a][0] for a, _ in idx])
    pa_r = np.stack([parents[a][1] for a, _ in idx])
    pb_t = np.stack([parents[b][0] for _, b in idx])
    pb_r = np.stack([parents[b][1] for _, b in idx])

    def generation():
        _, _, m = pipe.sample_children(rng, pa_t, pa_r, pb_t, pb_r, 0.5)
        return ev.costs_from(m)

    generation()                                  # warm the jits
    t0 = time.perf_counter()
    generation()
    gen_s = time.perf_counter() - t0

    W = jnp.asarray(rep.score_graph(parents[0]).W)
    V = int(W.shape[-1])
    Vp128 = max(128, -(-V // 128) * 128)
    vmem_mb = 3 * Vp128 * Vp128 * 4 / 2**20       # W, D, N resident
    fits_vmem = vmem_mb <= 16.0
    out = dict(V=V, padded_V=Vp128, n_chiplets=len(arch.chiplets),
               gen_n=gen_n, seconds_per_generation=gen_s,
               gen_placements_per_s=gen_n / gen_s,
               vmem_required_mb=round(vmem_mb, 1), fits_vmem=fits_vmem,
               auto_dispatch=("vmem" if Vp128 <= FW_TILED_AUTO_V
                              else "tiled"))

    def _time(fn):
        f = jax.jit(fn)
        jax.block_until_ready(f(W)[0])            # compile + warm
        best = np.inf
        for _ in range(2):
            t0 = time.perf_counter()
            jax.block_until_ready(f(W)[0])
            best = min(best, time.perf_counter() - t0)
        return best

    out["fw_ref_s"] = _time(ref.fw_counts_ref)
    out["fw_tiled_s"] = _time(fw_counts_tiled_pallas)
    if fits_vmem and time_vmem:
        out["fw_vmem_s"] = _time(fw_counts_pallas)
    return out


def run(quick: bool = True) -> dict:
    n = budget(quick, 48, 256)
    e2e_norm = budget(quick, 2, 8)
    results: dict = {"n_prep": n}
    for name, (R, C, (nc, nm, ni)) in GRIDS.items():
        arch = homogeneous_arch(nc, nm, ni, "baseline")
        rep = HomogRep(arch, R=R, C=C)
        rng = np.random.default_rng(0)
        parents = [rep.random(rng) for _ in range(16)]
        host = _host_prep_rate(rep, parents, n)
        dev = _device_prep_rate(rep, parents, n)
        results[name] = dict(host_prep_per_s=host, device_prep_per_s=dev,
                             prep_speedup=dev / host)
        emit(f"pipeline_{name}_host_prep_per_s", round(host, 1),
             "per-child python merge+mutate+graph+union-find")
        emit(f"pipeline_{name}_device_prep_per_s", round(dev, 1),
             "one fused device call; connectivity rides the scorer FW")
        emit(f"pipeline_{name}_prep_speedup", round(dev / host, 1),
             f"{dev / host:.1f}x device over host loop")
        e2e_n = budget(quick, *E2E_N[name])
        h2, d2 = _e2e_rates(rep, arch, e2e_n, budget(quick, 8, 16),
                            norm_samples=e2e_norm)
        results[name].update(host_e2e_per_s=h2, device_e2e_per_s=d2,
                             e2e_speedup=d2 / h2, n_e2e=e2e_n)
        emit(f"pipeline_{name}_e2e_speedup", round(d2 / h2, 2),
             "incl. shared FW scorer (FW-bound on CPU; prep ratio is "
             "the refactor's target)")
    # heterogeneous path (PR 3): batched Borůvka link inference vs the
    # per-child host Kruskal+union-find loop
    hn = budget(quick, 32, 128)
    hh, hd = _hetero_prep_rates("hetero32", hn)
    results["hetero32"] = dict(host_prep_per_s=hh, device_prep_per_s=hd,
                               prep_speedup=hd / hh, n_prep=hn)
    emit("pipeline_hetero32_host_prep_per_s", round(hh, 1),
         "per-child python merge+mutate+corner-place+kruskal+graph")
    emit("pipeline_hetero32_device_prep_per_s", round(hd, 1),
         "fused batched ops + vectorized corner place + Boruvka on device")
    emit("pipeline_hetero32_prep_speedup", round(hd / hh, 1),
         f"{hd / hh:.1f}x batched over host loop (target >= 3x)")
    # 3D / hierarchical families (PR 9): stacked grids + gateway
    # backbones through the same pluggable stages.  gw3d64 uses the
    # relay-capable "placeit" config (see arch3d.families).
    from repro.arch3d import make_rep3d
    from repro.core.chiplets import resolve_arch
    a3n = budget(quick, 32, 128)
    arch3d = {}
    for arch_name, config in (("stack3d32", "baseline"),
                              ("gw3d64", "placeit")):
        arch = resolve_arch(arch_name, config)
        rep3 = make_rep3d(arch, arch_name)
        rng = np.random.default_rng(0)
        parents = [rep3.random(rng) for _ in range(16)]
        h3 = _host_prep_rate(rep3, parents, a3n)
        d3 = _device_prep_rate(rep3, parents, a3n)
        arch3d[arch_name] = dict(host_prep_per_s=h3, device_prep_per_s=d3,
                                 prep_speedup=d3 / h3, n_prep=a3n,
                                 config=config)
        emit(f"pipeline_{arch_name}_host_prep_per_s", round(h3, 1),
             "per-child python merge+mutate+record-walk graph+union-find")
        emit(f"pipeline_{arch_name}_device_prep_per_s", round(d3, 1),
             "fused device call; tier values are a runtime jit operand")
        emit(f"pipeline_{arch_name}_prep_speedup", round(d3 / h3, 1),
             f"{d3 / h3:.1f}x device over host loop (target >= 3x)")
    results["arch3d"] = arch3d
    # objective ranking (PR 4): cost evaluation + best-placement selection
    # over a scored candidate batch — host numpy formula + argsort vs the
    # in-scorer compiled objective + device top-k
    rn = budget(quick, 512, 2048)
    rh, rd, rf = _ranking_rates("homog32", rn)
    results["objective_ranking"] = dict(
        n_rank=rn, host_stage_per_s=rh, device_stage_per_s=rd,
        fused_e2e_per_s=rf, stage_speedup=rd / rh)
    emit("objective_ranking_host_stage_per_s", round(rh, 1),
         "metrics->host + numpy total_cost + argsort, per scored batch")
    emit("objective_ranking_device_stage_per_s", round(rd, 1),
         "jitted vmapped objective cost + top_k on device metrics")
    emit("objective_ranking_fused_e2e_per_s", round(rf, 1),
         "Evaluator.topk: score+cost+top-k one call (FW-bound on CPU)")
    emit("objective_ranking_stage_speedup", round(rd / rh, 1),
         f"{rd / rh:.1f}x device cost+top-k over host formula+argsort "
         "(target >= 2x)")
    # large-V section (PR 7): per-generation throughput + FW-kernel
    # comparison in the 100+-chiplet (HexaMesh) regime, where the
    # blocked-tile FW replaces the VMEM-resident kernel
    large_gen_n = {"homog100": (8, 32), "hex127": (8, 16),
                   "homog256": (4, 8)}
    large = {}
    for arch_name in LARGE_ARCHS[:1] if quick else LARGE_ARCHS:
        # per-arch budgets: homog256's V=1440 FW dominates; small n still
        # yields stable per-generation seconds (one fused call either way)
        gen_n = budget(quick, *large_gen_n[arch_name])
        norm = min(e2e_norm, 2) if arch_name == "homog256" else e2e_norm
        sec = _large_v_section(arch_name, gen_n, norm,
                               time_vmem=not quick or arch_name == "homog100")
        large[arch_name] = sec
        emit(f"large_v_{arch_name}_s_per_generation",
             round(sec["seconds_per_generation"], 2),
             f"device generation of {sec['gen_n']} at V={sec['V']} "
             "(fw-ref backend)")
        emit(f"large_v_{arch_name}_fw_tiled_s",
             round(sec["fw_tiled_s"], 3),
             f"blocked-tile FW+counts, one [V,V] at padded V="
             f"{sec['padded_V']}")
        emit(f"large_v_{arch_name}_vmem_required_mb",
             sec["vmem_required_mb"],
             f"VMEM-resident kernel needs this; fits_vmem="
             f"{sec['fits_vmem']}, auto-dispatch={sec['auto_dispatch']}")
    results["large_v"] = large
    # headline: the acceptance metric — GA-generation production on 8x8
    emit("pipeline_8x8_ga_generation_speedup",
         round(results["8x8"]["prep_speedup"], 1),
         "device-resident generate->graph vs host loop (target >= 5x)")
    with open(os.path.join(out_dir(), "pipeline_throughput.json"), "w") as f:
        json.dump(results, f, indent=1, default=float)
    return results


def main(quick: bool = True):
    run(quick)


if __name__ == "__main__":
    main(quick=os.environ.get("BENCH_FULL", "") != "1")
