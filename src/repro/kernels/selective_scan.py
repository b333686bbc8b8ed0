"""Mamba-1 selective-scan Pallas kernel (falcon-mamba's hot op).

The CUDA reference keeps the per-channel SSM state in registers/SMEM while
streaming the sequence.  The TPU adaptation (DESIGN.md §3): grid over
(batch, channel-blocks); the (bd, N) state lives in VMEM scratch; the kernel
walks the sequence with a fori_loop, reading (bd,) input slices and writing
(bd,) outputs per step — HBM traffic is one pass over x/dt/B/C/y, the
roofline minimum for this memory-bound op.  The recurrence itself is VPU
element-wise work (no MXU mapping for a diagonal SSM).

Layout: channel-minor (B, S, D) inputs are transposed to (B, D, S) by the
wrapper so each time step reads a contiguous lane vector.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu



def _sscan_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, h0_ref,
                  y_ref, hf_ref, h_scr, *, S: int):
    # Blocks: x/dt/y (1, bd, S); a (bd, N); b/c (1, S, N); d (1, bd);
    # h0/hf (1, bd, N); scratch h (bd, N) fp32.
    A = a_ref[...].astype(jnp.float32)              # (bd, N)
    Dskip = d_ref[0].astype(jnp.float32)            # (bd,)
    h_scr[...] = h0_ref[0].astype(jnp.float32)

    def step(t, _):
        xt = x_ref[0, :, t].astype(jnp.float32)     # (bd,)
        dtt = dt_ref[0, :, t].astype(jnp.float32)   # (bd,)
        Bt = b_ref[0, t, :].astype(jnp.float32)     # (N,)
        Ct = c_ref[0, t, :].astype(jnp.float32)     # (N,)
        dA = jnp.exp(dtt[:, None] * A)              # (bd, N)
        h = dA * h_scr[...] + (dtt * xt)[:, None] * Bt[None, :]
        h_scr[...] = h
        y = jnp.sum(h * Ct[None, :], axis=1) + Dskip * xt
        y_ref[0, :, t] = y.astype(y_ref.dtype)
        return 0

    jax.lax.fori_loop(0, S, step, 0)
    hf_ref[0] = h_scr[...].astype(hf_ref.dtype)


def selective_scan_pallas(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
                          B: jnp.ndarray, C: jnp.ndarray, D: jnp.ndarray,
                          h0: jnp.ndarray | None = None, *,
                          bd: int = 128, interpret: bool = True
                          ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """x, dt: [Bt, S, Di]; A: [Di, N]; B, C: [Bt, S, N]; D: [Di].

    Returns (y [Bt, S, Di], h_final [Bt, Di, N]).  Matches
    ``ref.selective_scan_ref``.
    """
    Bt, S, Di = x.shape
    N = A.shape[-1]
    if h0 is None:
        h0 = jnp.zeros((Bt, Di, N), dtype=jnp.float32)
    bd_ = min(bd, Di)
    Dp = -(-Di // bd_) * bd_
    xt = jnp.swapaxes(x, 1, 2)                      # (Bt, Di, S)
    dtt = jnp.swapaxes(dt, 1, 2)
    if Dp != Di:
        padc = ((0, 0), (0, Dp - Di), (0, 0))
        xt, dtt = jnp.pad(xt, padc), jnp.pad(dtt, padc)
        A = jnp.pad(A, ((0, Dp - Di), (0, 0)))
        D = jnp.pad(D, (0, Dp - Di))
        h0 = jnp.pad(h0, ((0, 0), (0, Dp - Di), (0, 0)))
    kern = functools.partial(_sscan_kernel, S=S)
    y, hf = pl.pallas_call(
        kern,
        grid=(Bt, Dp // bd_),
        in_specs=[
            pl.BlockSpec((1, bd_, S), lambda b, i: (b, i, 0)),   # x
            pl.BlockSpec((1, bd_, S), lambda b, i: (b, i, 0)),   # dt
            pl.BlockSpec((bd_, N), lambda b, i: (i, 0)),         # A
            pl.BlockSpec((1, S, N), lambda b, i: (b, 0, 0)),     # B
            pl.BlockSpec((1, S, N), lambda b, i: (b, 0, 0)),     # C
            pl.BlockSpec((1, bd_), lambda b, i: (b, i)),         # D (skip)
            pl.BlockSpec((1, bd_, N), lambda b, i: (b, i, 0)),   # h0
        ],
        out_specs=[pl.BlockSpec((1, bd_, S), lambda b, i: (b, i, 0)),
                   pl.BlockSpec((1, bd_, N), lambda b, i: (b, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((Bt, Dp, S), x.dtype),
                   jax.ShapeDtypeStruct((Bt, Dp, N), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bd_, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(xt, dtt, A, jnp.asarray(B), jnp.asarray(C),
      jnp.broadcast_to(D[None], (Bt, Dp)), h0)
    y = jnp.swapaxes(y, 1, 2)[:, :, :Di]
    return y, hf[:, :Di]
