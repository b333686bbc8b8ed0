"""Block-wise online-softmax (flash) attention Pallas kernel.

Used by the LM substrate for training and prefill.  Supports:
* causal masking (decoder LMs) and bidirectional (encoder),
* GQA: Hq query heads share Hq/Hkv KV heads (the kv BlockSpec index-map
  folds the group),
* sliding-window masking (recurrentgemma local attention),
* logit soft-capping (grok-style tanh cap),
* query/key position offset (Sq != Sk chunked prefill).

TPU mapping: the (bq, d) @ (bk, d)^T logits block hits the MXU; the online
max/sum rescale is VPU work; running (m, l, acc) live in VMEM scratch across
the sequential kv grid dimension.  Block sizes default to MXU-aligned
(128, 128).  CPU runs use interpret=True.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


NEG_INF = -1.0e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: int | None,
                  softcap: float | None, sq: int, sk: int,
                  bq: int, bk: int, pos_offset: int):
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) \
        + pos_offset
    kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    # Block-level skip: entirely-masked tiles do no work.
    q_lo = iq * bq + pos_offset
    q_hi = q_lo + bq - 1
    k_lo = ik * bk
    run = k_lo < sk                               # padded kv tail
    if causal:
        run &= k_lo <= q_hi
    if window is not None:
        run &= (ik * bk + bk - 1) > (q_lo - window)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)        # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)        # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)        # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        mask = kpos < sk
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]                        # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_scr[...]
        o_ref[0, 0] = (acc_scr[...] / jnp.where(l == 0.0, 1.0, l)
                       ).astype(o_ref.dtype)


def flash_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                           causal: bool = True, window: int | None = None,
                           scale: float | None = None,
                           softcap: float | None = None,
                           bq: int = 128, bk: int = 128,
                           interpret: bool = True) -> jnp.ndarray:
    """q: [B, Sq, Hq, d]; k, v: [B, Sk, Hkv, d] -> [B, Sq, Hq, d].

    Queries are end-aligned with keys (query i sits at position Sk-Sq+i).
    """
    B, Sq, Hq, d = q.shape
    _, Sk, Hkv, _ = k.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    g = Hq // Hkv
    scale = (d ** -0.5) if scale is None else scale
    # Head-major layout for blocking.
    qt = jnp.swapaxes(q, 1, 2)                     # [B, Hq, Sq, d]
    kt = jnp.swapaxes(k, 1, 2)                     # [B, Hkv, Sk, d]
    vt = jnp.swapaxes(v, 1, 2)
    bq_ = min(bq, max(8, Sq))
    bk_ = min(bk, max(8, Sk))
    Sqp, Skp = -(-Sq // bq_) * bq_, -(-Sk // bk_) * bk_
    if Sqp != Sq:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, Sqp - Sq), (0, 0)))
    if Skp != Sk:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, Skp - Sk), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, Skp - Sk), (0, 0)))
    kern = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, sq=Sq, sk=Sk, bq=bq_, bk=bk_,
        pos_offset=Sk - Sq)
    out = pl.pallas_call(
        kern,
        grid=(B, Hq, Sqp // bq_, Skp // bk_),
        in_specs=[
            pl.BlockSpec((1, 1, bq_, d), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk_, d),
                         lambda b, h, iq, ik, g=g: (b, h // g, ik, 0)),
            pl.BlockSpec((1, 1, bk_, d),
                         lambda b, h, iq, ik, g=g: (b, h // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq_, d),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sqp, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq_, 1), jnp.float32),
                        pltpu.VMEM((bq_, 1), jnp.float32),
                        pltpu.VMEM((bq_, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt)
    return jnp.swapaxes(out[:, :, :Sq], 1, 2)
