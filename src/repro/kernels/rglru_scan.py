"""RG-LRU diagonal linear-recurrence Pallas kernel (recurrentgemma).

h_t = a_t * h_{t-1} + sqrt(1 - a_t^2) * x_t   (element-wise over channels).

Same VMEM-resident-state pattern as the selective scan, but the state is a
single (bd,) lane vector, making this purely bandwidth-bound: one HBM pass
over a, x and h.  Channel-blocked grid; sequence walked inside the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu



def _rglru_kernel(a_ref, x_ref, h0_ref, y_ref, hf_ref, h_scr, *, S: int):
    # Blocks: a/x/y (1, bd, S); h0/hf (1, bd); scratch (1, bd) fp32.
    h_scr[...] = h0_ref[...].astype(jnp.float32)

    def step(t, _):
        at = a_ref[0, :, t].astype(jnp.float32)
        xt = x_ref[0, :, t].astype(jnp.float32)
        bt = jnp.sqrt(jnp.maximum(1.0 - at * at, 0.0)) * xt
        h = at * h_scr[0] + bt
        h_scr[0] = h
        y_ref[0, :, t] = h.astype(y_ref.dtype)
        return 0

    jax.lax.fori_loop(0, S, step, 0)
    hf_ref[...] = h_scr[...].astype(hf_ref.dtype)


def rglru_scan_pallas(x: jnp.ndarray, a: jnp.ndarray,
                      h0: jnp.ndarray | None = None, *,
                      bd: int = 256, interpret: bool = True
                      ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """x, a: [B, S, D] -> (h [B, S, D], h_final [B, D]).

    Matches ``ref.rglru_ref``.
    """
    B, S, Di = x.shape
    if h0 is None:
        h0 = jnp.zeros((B, Di), dtype=jnp.float32)
    bd_ = min(bd, Di)
    Dp = -(-Di // bd_) * bd_
    xt = jnp.swapaxes(x, 1, 2)                      # (B, D, S)
    at = jnp.swapaxes(a, 1, 2)
    if Dp != Di:
        xt = jnp.pad(xt, ((0, 0), (0, Dp - Di), (0, 0)))
        at = jnp.pad(at, ((0, 0), (0, Dp - Di), (0, 0)))
        h0 = jnp.pad(h0, ((0, 0), (0, Dp - Di)))
    kern = functools.partial(_rglru_kernel, S=S)
    y, hf = pl.pallas_call(
        kern,
        grid=(B, Dp // bd_),
        in_specs=[
            pl.BlockSpec((1, bd_, S), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, bd_, S), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, bd_), lambda b, i: (b, i)),
        ],
        out_specs=[pl.BlockSpec((1, bd_, S), lambda b, i: (b, i, 0)),
                   pl.BlockSpec((1, bd_), lambda b, i: (b, i))],
        out_shape=[jax.ShapeDtypeStruct((B, Dp, S), x.dtype),
                   jax.ShapeDtypeStruct((B, Dp), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((1, bd_), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(at, xt, h0)
    return jnp.swapaxes(y, 1, 2)[:, :, :Di], hf[:, :Di]
