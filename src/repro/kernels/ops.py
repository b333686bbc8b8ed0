"""Public kernel API: jit'd wrappers that pick Pallas-on-TPU / interpret-on-
CPU / pure-jnp reference, uniformly switchable via ``impl``.

impl semantics:
  'auto'   — Pallas compiled on TPU; pure-jnp reference elsewhere (interpret
             mode is a correctness tool, far too slow for production CPU use).
  'pallas' — force the Pallas kernel (interpret=True off-TPU, for tests).
  'ref'    — force the pure-jnp oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref
from .decode_attention import decode_attention_pallas
from .flash_attention import flash_attention_pallas
from .minplus import (apsp_tiled_pallas, fw_counts_pallas,
                      fw_counts_tiled_pallas, minplus_tiled_pallas)
from .rglru_scan import rglru_scan_pallas
from .selective_scan import selective_scan_pallas


def on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def _resolve(impl: str) -> str:
    if impl == "auto":
        return "pallas" if on_tpu() else "ref"
    return impl


def _interp() -> bool:
    return not on_tpu()


# -- min-plus / APSP ---------------------------------------------------------

def fw_counts(W: jnp.ndarray, impl: str = "auto"
              ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Floyd-Warshall distances + path counts; [.., V, V] -> (D, N)."""
    if _resolve(impl) == "pallas":
        return fw_counts_pallas(W, interpret=_interp())
    return ref.fw_counts_ref(W)


def minplus(A: jnp.ndarray, B: jnp.ndarray, impl: str = "auto",
            **tiles) -> jnp.ndarray:
    if _resolve(impl) == "pallas":
        return minplus_tiled_pallas(A, B, interpret=_interp(), **tiles)
    return ref.minplus_ref(A, B)


def apsp(W: jnp.ndarray, impl: str = "auto", **tiles) -> jnp.ndarray:
    if _resolve(impl) == "pallas":
        return apsp_tiled_pallas(W, interpret=_interp(), **tiles)
    return ref.apsp_ref(W)


# -- attention ----------------------------------------------------------------

def flash_attention(q, k, v, *, causal=True, window=None, scale=None,
                    softcap=None, pos_offset=None, impl: str = "auto",
                    **blocks):
    if _resolve(impl) == "pallas" and pos_offset is None:
        return flash_attention_pallas(
            q, k, v, causal=causal, window=window, scale=scale,
            softcap=softcap, interpret=_interp(), **blocks)
    return ref.attention_ref(q, k, v, causal=causal, window=window,
                             scale=scale, softcap=softcap,
                             pos_offset=pos_offset)


def decode_attention(q, k_cache, v_cache, lengths, *, scale=None, window=None,
                     softcap=None, impl: str = "auto", **blocks):
    if _resolve(impl) == "pallas":
        return decode_attention_pallas(
            q, k_cache, v_cache, lengths, scale=scale, window=window,
            softcap=softcap, interpret=_interp(), **blocks)
    return ref.decode_attention_ref(q, k_cache, v_cache, lengths, scale=scale,
                                    window=window, softcap=softcap)


# -- recurrences ---------------------------------------------------------------

def selective_scan(x, dt, A, B, C, D, h0=None, impl: str = "auto", **kw):
    if _resolve(impl) == "pallas":
        return selective_scan_pallas(x, dt, A, B, C, D, h0,
                                     interpret=_interp(), **kw)
    return ref.selective_scan_ref(x, dt, A, B, C, D, h0)


def rglru_scan(x, a, h0=None, impl: str = "auto", **kw):
    if _resolve(impl) == "pallas":
        return rglru_scan_pallas(x, a, h0, interpret=_interp(), **kw)
    return ref.rglru_ref(x, a, h0)


# Scorer adapter: `repro.core.proxies.make_scorer(fw_impl=...)` expects a
# W -> (D, N) callable; this binds the Pallas FW kernel into the PlaceIT
# evaluation path (the paper's hot spot, DESIGN.md §3).
def fw_impl_pallas(W):
    return fw_counts_pallas(W, interpret=_interp())


fw_impl_ref = ref.fw_counts_ref

# Dispatch knee of ``fw_impl_tiled``.  The VMEM-resident kernel holds its
# W, D and N blocks double-buffered, 6 x Vp^2 x 4 B.  Compiled for a v5e
# without a chip: under the default 16 MiB scoped-VMEM limit it compiles
# up to padded V 768 (13.5 MiB) and is refused from 896 on; with the
# ``vmem_limit_bytes`` it now requests (``minplus._vmem_limit``) it also
# compiles at 896-2048.  Which kernel is faster above 768 has not been
# measured on the chip, so the knee stays where the default limit put
# it.  The blocked-tile FW keeps O(bt^2) per grid program regardless of V
# and compiles at padded V 1536 (homog256).
FW_TILED_AUTO_V = 768


def fw_counts_tiled(W: jnp.ndarray, *, bt: int = 128
                    ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Blocked-tile FW + path counts (bit-for-bit == fw_counts_ref)."""
    return fw_counts_tiled_pallas(W, bt=bt, interpret=_interp())


def fw_impl_tiled(W):
    """Size-dispatched FW scorer impl: VMEM-resident kernel while the
    padded V fits (< FW_TILED_AUTO_V), blocked-tile kernel beyond.  Both
    are bit-for-bit equal to ``fw_counts_ref``, so the dispatch point is
    invisible in results."""
    V = W.shape[-1]
    if max(128, -(-V // 128) * 128) <= FW_TILED_AUTO_V:
        return fw_counts_pallas(W, interpret=_interp())
    return fw_counts_tiled_pallas(W, interpret=_interp())
