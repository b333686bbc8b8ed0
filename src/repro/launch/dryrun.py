import os
os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_XLA_EXTRA", "") + " --xla_force_host_platform_device_count=" + os.environ.get("REPRO_DRYRUN_DEVICES", "512")).strip()  # noqa: E501 -- MUST precede any jax import
"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The two lines above MUST run before any jax import — jax locks the device
count at first init.  512 placeholder host devices back the production
meshes: (16, 16) single-pod and (2, 16, 16) multi-pod.

Per cell this driver:
  1. builds the model + step function (train_step / prefill / decode),
  2. attaches in/out shardings from ``repro.sharding.rules``,
  3. ``jit(...).lower(**input_specs).compile()`` — ShapeDtypeStructs only,
     nothing is allocated,
  4. records memory_analysis (fits-in-HBM proof), cost_analysis (FLOPs /
     bytes) and the HLO collective schedule (ops, bytes, axes) to a JSON
     artifact in ``artifacts/dryrun/`` (resumable: existing cells skip).

Usage:
  python -m repro.launch.dryrun --arch qwen3-1.7b --shape train_4k --mesh multi
  python -m repro.launch.dryrun --all [--mesh both] [--force]
"""
import argparse
import dataclasses
import functools
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import SHAPES, all_cells, get_config, input_specs
from ..models.model import build_model, init_cache, init_params
from ..sharding import rules
from ..sharding.partition import MeshInfo, use_sharding
from ..train.optimizer import OptConfig, adamw_init
from .mesh import make_auto_mesh, make_production_mesh

ARTIFACT_DIR = os.path.join("artifacts", "dryrun")

# Per-(arch, shape) execution overrides for the production lowering:
# microbatch count (activation memory) and q-chunk (attention logits), plus
# head padding for TP-unfriendly head counts (llava 56 -> 64; zero-padded,
# function-exact).
OVERRIDES: dict[str, dict] = {
    "grok-1-314b": dict(microbatches={"train_4k": 16}, opt_int8=True,
                        accum_dtype="bfloat16",
                        q_chunk={"train_4k": 2048, "prefill_32k": 2048}),
    "llava-next-34b": dict(pad_heads_to=64,
                           microbatches={"train_4k": 16},
                           q_chunk={"train_4k": 512, "prefill_32k": 512}),
    "recurrentgemma-9b": dict(microbatches={"train_4k": 8},
                              q_chunk={"prefill_32k": 2048}),
    "falcon-mamba-7b": dict(microbatches={"train_4k": 8}),
    "moonshot-v1-16b-a3b": dict(microbatches={"train_4k": 8},
                                q_chunk={"prefill_32k": 2048}),
    "qwen2.5-3b": dict(microbatches={"train_4k": 4},
                       q_chunk={"train_4k": 2048, "prefill_32k": 2048}),
    "qwen3-1.7b": dict(microbatches={"train_4k": 2},
                       q_chunk={"train_4k": 2048, "prefill_32k": 2048}),
    "tinyllama-1.1b": dict(microbatches={"train_4k": 2},
                           q_chunk={"train_4k": 2048,
                                    "prefill_32k": 2048}),
    "smollm-360m": dict(microbatches={},   # §Perf A4: grads reduce once
                        # §Perf A3: seq-sharded attention makes q-chunking
                        # redundant at train (logits already 16x smaller);
                        # chunk-reshape regathers were the last wire driver
                        q_chunk={"prefill_32k": 512},
                        # §Perf A2: 360M params -> replicate weights, run
                        # the whole mesh as 256-way data/sequence parallel
                        replicate_params=True, seq_parallel=True),
    "seamless-m4t-medium": dict(microbatches={"train_4k": 4},
                                q_chunk={"train_4k": 2048,
                                         "prefill_32k": 2048}),
}


def prod_config(arch: str, shape: str, *, scan_layers: bool = False):
    """The exact arch config with production lowering knobs applied."""
    cfg = get_config(arch)
    ov = OVERRIDES.get(arch, {})
    rep: dict = dict(dtype="bfloat16", scan_layers=scan_layers,
                     attn_impl="ref", remat=True)
    if "pad_heads_to" in ov:
        rep["pad_heads_to"] = ov["pad_heads_to"]
    qc = ov.get("q_chunk", {}).get(shape)
    if qc:
        rep["q_chunk"] = qc
    return dataclasses.replace(cfg, **rep), ov.get(
        "microbatches", {}).get(shape, 1)


def mesh_info_for(mesh, global_batch: int) -> MeshInfo:
    """Batch-aware axis roles: B == 1 cells move the data axes into TP."""
    names = mesh.axis_names
    dp = tuple(a for a in names if a in ("pod", "data"))
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    # Multi-pod policy: FSDP stays intra-pod (weight gathers on ICI only);
    # the pod axis carries plain DP (one cross-DCI grad reduce per step).
    fsdp = tuple(a for a in dp if a != "pod") or None
    if global_batch == 1:
        return MeshInfo(mesh=mesh, dp=(), tp=tuple(names))
    if global_batch % dp_size != 0:
        # shed pod axis from dp if that fixes divisibility
        dp2 = tuple(a for a in dp if a != "pod")
        dp_size2 = 1
        for a in dp2:
            dp_size2 *= mesh.shape[a]
        if global_batch % dp_size2 == 0:
            return MeshInfo(mesh=mesh, dp=dp2, tp="model", fsdp_over=dp2)
        raise ValueError(f"batch {global_batch} unshardable on {names}")
    return MeshInfo(mesh=mesh, dp=dp, tp="model", fsdp_over=fsdp)


# ---------------------------------------------------------------------------
# Step builders (lower-only; no allocation).
# ---------------------------------------------------------------------------

SERVING_TP_ONLY_LIMIT = 3e9   # per-chip param bytes under TP-only sharding


def _serving_param_specs(cfg, param_shapes, mi, fsdp_specs):
    """Inference param sharding: TP-only when the per-chip footprint
    allows (kills the per-step FSDP weight all-gathers — §Perf iteration
    B1); FSDP otherwise (grok-314B).  REPRO_SERVING_FSDP=1 forces the
    FSDP baseline for before/after measurements."""
    if os.environ.get("REPRO_SERVING_FSDP") == "1":
        return fsdp_specs
    per_chip = sum(
        x.size * jnp.dtype(x.dtype).itemsize
        for x in jax.tree.leaves(param_shapes)) / max(mi.tp_size, 1)
    if per_chip > SERVING_TP_ONLY_LIMIT:
        return fsdp_specs
    mi_tp = MeshInfo(mesh=mi.mesh, dp=(), tp=mi.tp)
    return rules.param_pspecs(cfg, param_shapes, mi_tp)


def build_cell(arch: str, shape: str, mesh, *, scan_layers=False):
    from ..train.step import build_train_step

    cfg, microbatches = prod_config(arch, shape, scan_layers=scan_layers)
    sh = SHAPES[shape]
    mi = mesh_info_for(mesh, sh.global_batch)
    # §Perf C4: the global microbatch must not drop below the dp shard
    # count, or GSPMD pads every chip to a whole row (silent 2x flops).
    microbatches = max(1, min(microbatches,
                              sh.global_batch // max(mi.dp_size, 1)))
    specs = input_specs(arch, shape)
    model = build_model(cfg)
    cache_len = sh.seq_len
    ov = OVERRIDES.get(arch, {})
    ctx = rules.make_ctx(cfg, mi, cache_len=cache_len,
                         seq_shard_attn=(sh.kind != "decode"))
    if ov.get("seq_parallel") and sh.kind != "decode":
        dp_ax = tuple(mi.dp) or None
        ctx.act_specs["act"] = P(dp_ax, mi.tp, None)
        ctx.act_specs["act_heads"] = P(dp_ax, mi.tp, None, None)
        ctx.act_specs["act_ff"] = P(dp_ax, mi.tp, None)
        ctx.act_specs["logits"] = P(dp_ax, mi.tp, None)

    param_shapes = jax.eval_shape(
        functools.partial(init_params, cfg), jax.random.PRNGKey(0))
    if ov.get("replicate_params"):
        p_specs = jax.tree.map(lambda _: P(), param_shapes)
    else:
        p_specs = rules.param_pspecs(cfg, param_shapes, mi)
    b_specs = rules.batch_pspecs(specs, mi)
    named = lambda tree: jax.tree.map(lambda s: NamedSharding(mesh, s), tree)

    if sh.kind == "train":
        opt_cfg = OptConfig(
            state_int8=OVERRIDES.get(arch, {}).get("opt_int8", False))
        state_shapes = {
            "params": param_shapes,
            "opt": jax.eval_shape(
                functools.partial(adamw_init, opt_cfg), param_shapes),
        }
        # Hierarchical ZeRO (§Perf C3): optimizer state shards over
        # (pod, data) — it is never gathered, so the extra pod dimension
        # costs one cross-DCI grad reduce-scatter + param all-gather per
        # step instead of doubling resident state.
        mi_opt = dataclasses.replace(mi, fsdp_over=tuple(mi.dp))
        o_specs = rules.param_pspecs(cfg, state_shapes["opt"], mi_opt)
        # opt m/v mirror params; scalar step replicated
        o_specs["step"] = P()
        state_specs = {"params": p_specs, "opt": o_specs}
        step = build_train_step(
            model, opt_cfg, microbatches=microbatches,
            accum_dtype=OVERRIDES.get(arch, {}).get("accum_dtype",
                                                    "float32"))

        def fn(state, batch):
            with use_sharding(ctx):
                return step(state, batch)

        jfn = jax.jit(fn,
                      in_shardings=(named(state_specs), named(b_specs)),
                      out_shardings=(named(state_specs), None),
                      donate_argnums=(0,))
        args = (state_shapes, specs)
    elif sh.kind == "prefill":
        p_specs = _serving_param_specs(cfg, param_shapes, mi, p_specs)

        def fn(params, batch):
            with use_sharding(ctx):
                return model.prefill(params, batch, cache_len)

        cache_shapes = jax.eval_shape(
            lambda: init_cache(cfg, sh.global_batch, cache_len,
                               mem_len=sh.seq_len
                               if cfg.family == "encdec" else 0))
        c_specs = rules.cache_pspecs(cfg, cache_shapes, mi,
                                     cache_len=cache_len)
        jfn = jax.jit(fn,
                      in_shardings=(named(p_specs), named(b_specs)),
                      out_shardings=(None, named(c_specs)))
        args = (param_shapes, specs)
    else:  # decode
        p_specs = _serving_param_specs(cfg, param_shapes, mi, p_specs)
        mem_len = sh.seq_len if cfg.family == "encdec" else 0
        cache_shapes = jax.eval_shape(
            lambda: init_cache(cfg, sh.global_batch, cache_len,
                               mem_len=mem_len))
        c_specs = rules.cache_pspecs(cfg, cache_shapes, mi,
                                     cache_len=cache_len)

        def fn(params, batch, caches):
            with use_sharding(ctx):
                return model.decode_step(params, batch, caches)

        jfn = jax.jit(fn,
                      in_shardings=(named(p_specs), named(b_specs),
                                    named(c_specs)),
                      out_shardings=(None, named(c_specs)),
                      donate_argnums=(2,))
        args = (param_shapes, specs, cache_shapes)
    return jfn, args, cfg, mi, microbatches


# ---------------------------------------------------------------------------
# Analyses
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1}

_COLL_RE = re.compile(
    r"(\w[\w\d.\-]*)\s*=\s*(\([^)]*\)|\S+?)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{?\{([\d,]+)\}")
_GROUPS_V2_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_stats(hlo_text: str, n_chips: int) -> dict:
    """Per-op-kind wire-bytes-per-chip (ring algorithm estimates).

    result-shape bytes R, group size g:
      all-gather:        R is gathered (full) -> wire/chip = R*(g-1)/g
      all-reduce:        R == operand         -> wire/chip = 2R*(g-1)/g
      reduce-scatter:    R is the shard       -> wire/chip = R*(g-1)
      all-to-all:        R == operand         -> wire/chip = R*(g-1)/g
      collective-permute:R == operand         -> wire/chip = R
    """
    out: dict[str, dict] = {}
    per_chip_total = 0.0
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        _, type_str, kind, _ = m.groups()
        R = _shape_bytes(type_str)
        g = n_chips
        mg = _GROUPS_RE.search(line)
        if mg:
            g = len(mg.group(1).split(","))
        else:
            mg2 = _GROUPS_V2_RE.search(line)
            if mg2:
                g = int(mg2.group(2))
        g = max(g, 1)
        if kind == "all-gather":
            wire = R * (g - 1) / g
        elif kind == "all-reduce":
            wire = 2 * R * (g - 1) / g
        elif kind == "reduce-scatter":
            wire = R * (g - 1)
        elif kind == "all-to-all":
            wire = R * (g - 1) / g
        else:
            wire = R
        d = out.setdefault(kind, {"count": 0, "wire_bytes_per_chip": 0.0})
        d["count"] += 1
        d["wire_bytes_per_chip"] += wire
        per_chip_total += wire
    return {"ops": out, "wire_bytes_per_chip": per_chip_total}


def analyze(compiled, n_chips: int) -> dict:
    from .hlo_cost import analyze_hlo, xla_cost_analysis

    ca = xla_cost_analysis(compiled)
    ma = compiled.memory_analysis()
    mem = {}
    for f in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        v = getattr(ma, f, None)
        if v is not None:
            mem[f] = int(v)
    txt = compiled.as_text()
    wa = analyze_hlo(txt, n_chips)        # while-aware exact accounting
    return {
        "flops_total": float(wa["flops"]),
        "bytes_accessed_total": float(wa["bytes"]),
        "convert_bytes_total": float(wa.get("convert_bytes", 0.0)),
        "xla_flops_body_once": float(ca.get("flops", -1)),
        "xla_bytes_body_once": float(ca.get("bytes accessed", -1)),
        "memory_analysis": mem,
        "collectives": {"ops": wa["collectives"],
                        "wire_bytes_per_chip": wa["wire_bytes_per_chip"],
                        "cross_pod_bytes_per_chip":
                            wa.get("cross_pod_bytes_per_chip", 0.0)},
        "n_collective_lines": sum(d["count"]
                                  for d in wa["collectives"].values()),
        "top_collectives": [
            {"path": p[-60:], "kind": k, "wire_bytes": round(w, 1),
             "shape": sh}
            for (p, k, w, sh) in wa["schedule"][:12]],
    }


def _mesh_for(mesh_kind: str):
    """Production mesh, or a reduced test mesh via REPRO_TEST_MESH=RxC."""
    tm = os.environ.get("REPRO_TEST_MESH")
    if tm:
        dims = tuple(int(x) for x in tm.split("x"))
        axes = (("pod", "data", "model") if len(dims) == 3
                else ("data", "model"))
        return make_auto_mesh(dims, axes)
    return make_production_mesh(multi_pod=(mesh_kind == "multi"))


def run_cell(arch: str, shape: str, mesh_kind: str, *, out_dir=ARTIFACT_DIR,
             force=False, scan_layers=True) -> dict:
    os.makedirs(out_dir, exist_ok=True)
    tag = f"{arch}__{shape}__{mesh_kind}"
    path = os.path.join(out_dir, tag + ".json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)
    mesh = _mesh_for(mesh_kind)
    n_chips = mesh.size
    t0 = time.time()
    rec = {"arch": arch, "shape": shape, "mesh": mesh_kind,
           "n_chips": n_chips, "ok": False}
    try:
        jfn, args, cfg, mi, mb = build_cell(arch, shape, mesh,
                                            scan_layers=scan_layers)
        lowered = jfn.lower(*args)
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
        rec.update(analyze(compiled, n_chips))
        rec.update(ok=True, lower_s=round(t1 - t0, 1),
                   compile_s=round(t2 - t1, 1), microbatches=mb,
                   dp=list(mi.dp), tp=list(mi.tp) if isinstance(mi.tp, tuple)
                   else [mi.tp], scan_layers=scan_layers)
        n_params = sum(x.size for x in jax.tree.leaves(jax.eval_shape(
            functools.partial(init_params, cfg), jax.random.PRNGKey(0))))
        rec["n_params"] = int(n_params)
    except Exception as e:  # noqa: BLE001 — record the failure, keep going
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--unrolled", action="store_true",
                    help="unroll layers (slow compile; cross-checks the "
                         "while-aware cost analysis)")
    ap.add_argument("--out", default=ARTIFACT_DIR)
    args = ap.parse_args()
    meshes = {"single": ["single"], "multi": ["multi"],
              "both": ["single", "multi"]}[args.mesh]
    cells = all_cells() if args.all else [(args.arch, args.shape)]
    for arch, shape in cells:
        for mk in meshes:
            rec = run_cell(arch, shape, mk, out_dir=args.out,
                           force=args.force,
                           scan_layers=not args.unrolled)
            status = "OK " if rec.get("ok") else "FAIL"
            mem = rec.get("memory_analysis", {})
            per_dev = (mem.get("argument_size_in_bytes", 0)
                       + mem.get("temp_size_in_bytes", 0)) / 1e9
            print(f"[{status}] {arch:22s} {shape:12s} {mk:6s} "
                  f"flops={rec.get('flops_total', 0):.3e} "
                  f"mem/dev={per_dev:.2f}GB "
                  f"coll={rec.get('n_collective_lines', '-')}"
                  + ("" if rec.get("ok")
                     else "  " + rec.get("error", "")[:120]),
                  flush=True)


if __name__ == "__main__":
    main()
