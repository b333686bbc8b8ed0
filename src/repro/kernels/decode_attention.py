"""Flash-decode Pallas kernel: single-token GQA attention over a KV cache.

The decode step's attention is memory-bound: it streams the whole KV cache
(B, S, Hkv, d) from HBM once per token.  The kernel tiles the cache along S
and keeps the online-softmax running state for the g = Hq/Hkv query rows of
one KV head in VMEM, so HBM traffic is exactly one cache read — the roofline
minimum.  Valid-length masking supports ragged batches; sliding-window
masking supports recurrentgemma local attention at 500k contexts (only the
last `window` positions are ever resident).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


NEG_INF = -1.0e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *,
                   scale: float, window: int | None,
                   softcap: float | None, bs: int, g: int):
    isb = pl.program_id(2)
    ns = pl.num_programs(2)

    @pl.when(isb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[0, 0]
    kpos = isb * bs + jax.lax.broadcasted_iota(jnp.int32, (g, bs), 1)
    run = (isb * bs) < length
    if window is not None:
        run &= (isb * bs + bs - 1) > (length - 1 - window)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)        # (g, d)
        k = k_ref[0, 0].astype(jnp.float32)        # (bs, d)
        v = v_ref[0, 0].astype(jnp.float32)        # (bs, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        mask = kpos < length
        if window is not None:
            mask &= kpos > (length - 1 - window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(isb == ns - 1)
    def _finalize():
        l = l_scr[...]
        o_ref[0, 0] = (acc_scr[...] / jnp.where(l == 0.0, 1.0, l)
                       ).astype(o_ref.dtype)


def decode_attention_pallas(q: jnp.ndarray, k_cache: jnp.ndarray,
                            v_cache: jnp.ndarray, lengths: jnp.ndarray, *,
                            scale: float | None = None,
                            window: int | None = None,
                            softcap: float | None = None,
                            bs: int = 256,
                            interpret: bool = True) -> jnp.ndarray:
    """q: [B, Hq, d]; caches: [B, S, Hkv, d]; lengths: [B] -> [B, Hq, d]."""
    B, Hq, d = q.shape
    _, S, Hkv, _ = k_cache.shape
    assert Hq % Hkv == 0
    g = Hq // Hkv
    scale = (d ** -0.5) if scale is None else scale
    qt = q.reshape(B, Hkv, g, d)
    kt = jnp.swapaxes(k_cache, 1, 2)               # [B, Hkv, S, d]
    vt = jnp.swapaxes(v_cache, 1, 2)
    bs_ = min(bs, max(8, S))
    Sp = -(-S // bs_) * bs_
    if Sp != S:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))
    lens = lengths.astype(jnp.int32).reshape(B, 1)
    kern = functools.partial(_decode_kernel, scale=scale, window=window,
                             softcap=softcap, bs=bs_, g=g)
    out = pl.pallas_call(
        kern,
        grid=(B, Hkv, Sp // bs_),
        in_specs=[
            pl.BlockSpec((1, 1), lambda b, h, s: (b, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, g, d), lambda b, h, s: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bs_, d), lambda b, h, s: (b, h, s, 0)),
            pl.BlockSpec((1, 1, bs_, d), lambda b, h, s: (b, h, s, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), lambda b, h, s: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, g, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((g, 1), jnp.float32),
                        pltpu.VMEM((g, 1), jnp.float32),
                        pltpu.VMEM((g, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lens, qt, kt, vt)
    return out.reshape(B, Hq, d)
