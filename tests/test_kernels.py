"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode.

Every kernel is exercised over a grid of shapes and dtypes and must
``assert_allclose`` against its ``ref.py`` oracle.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose

from repro.kernels import ops, ref

R = np.random.default_rng(7)


def randn(*shape, dtype=np.float32):
    return R.standard_normal(shape).astype(dtype)


# ---------------------------------------------------------------------------
# min-plus / FW
# ---------------------------------------------------------------------------

def random_graph(V, n_edges, seed=0, batch=1):
    rng = np.random.default_rng(seed)
    W = np.full((batch, V, V), 1e9, np.float32)
    for b in range(batch):
        np.fill_diagonal(W[b], 0)
        for _ in range(n_edges):
            i, j = rng.integers(V, size=2)
            if i != j:
                w = float(rng.integers(1, 9))
                W[b, i, j] = min(W[b, i, j], w)
                W[b, j, i] = min(W[b, j, i], w)
    return W


# V 120, 136 and 432 (homog64) are not multiples of the strip height or of
# 128, so the kernel's trimmed pivot loop and row strips stop inside the
# padding; the last two are sparse enough that some pairs stay at 1e9.
@pytest.mark.parametrize("V,edges,batch", [(8, 12, 1), (40, 120, 2),
                                           (130, 400, 1), (120, 400, 1),
                                           (136, 180, 2), (432, 560, 1)])
def test_fw_counts_kernel(V, edges, batch):
    W = jnp.array(random_graph(V, edges, seed=V, batch=batch))
    D1, N1 = ops.fw_counts(W, impl="pallas")
    D2, N2 = ref.fw_counts_ref(W)
    for got, want in ((D1, D2), (N1, N2)):
        np.testing.assert_array_equal(np.asarray(got).view(np.uint32),
                                      np.asarray(want).view(np.uint32))


@pytest.mark.parametrize("m,k,n,tiles", [(64, 64, 64, dict(bm=32, bn=32, bk=32)),
                                         (100, 70, 130, dict(bm=32, bn=128, bk=32)),
                                         (128, 128, 128, dict())])
def test_minplus_tiled(m, k, n, tiles):
    A = jnp.array(R.random((m, k), np.float32) * 10)
    B = jnp.array(R.random((k, n), np.float32) * 10)
    o1 = ops.minplus(A, B, impl="pallas", **tiles)
    o2 = ref.minplus_ref(A, B)
    assert_allclose(np.array(o1), np.array(o2), rtol=1e-6)


def test_apsp_tiled_matches_fw():
    W = jnp.array(random_graph(48, 150, seed=3)[0])
    D1 = ops.apsp(W, impl="pallas", bm=32, bn=32, bk=32)
    D2, _ = ref.fw_counts_ref(W)
    assert_allclose(np.minimum(np.array(D1), 1e9),
                    np.minimum(np.array(D2), 1e9), rtol=1e-6)


@pytest.mark.parametrize("V", [2, 3])
def test_apsp_tiny_v(V):
    # Repeated-squaring edge cases: the iteration count is host math
    # (ceil(log2(max(V-1, 2)))); V=2 and V=3 must still converge.
    W = np.full((V, V), 1e9, np.float32)
    np.fill_diagonal(W, 0.0)
    W[0, V - 1] = W[V - 1, 0] = 5.0
    if V == 3:
        W[0, 1] = W[1, 0] = 2.0
        W[1, 2] = W[2, 1] = 2.0        # 0->2 via 1 (cost 4) beats direct 5
    W = jnp.asarray(W)
    D1 = ops.apsp(W, impl="pallas", bm=8, bn=8, bk=8)
    D2 = ref.apsp_ref(W)
    assert_allclose(np.minimum(np.array(D1), 1e9),
                    np.minimum(np.array(D2), 1e9), rtol=0)
    if V == 3:
        assert float(D1[0, 2]) == 4.0


# Blocked-tile FW with path counts (PR 7): must be bit-for-bit equal to
# the sequential reference — including multi-block tilings where the
# pivot block, panels and outer tiles all exercise distinct kernels.
@pytest.mark.parametrize("V,edges,batch,bt", [
    (8, 12, 1, 4),          # tiny tile, nb=2
    (13, 30, 2, 4),         # V not a tile multiple, nb=4
    (40, 120, 2, 16),       # nb=3 with padding
    (130, 400, 1, 64),      # nb=3, realistic size
    (130, 400, 2, 128),     # nb=2, production tile size
    (5, 0, 1, 4),           # fully disconnected (all-INF off-diagonal)
])
def test_fw_counts_tiled_bitforbit(V, edges, batch, bt):
    from repro.kernels.minplus import fw_counts_tiled_pallas
    W = jnp.array(random_graph(V, edges, seed=V + edges, batch=batch))
    D1, N1 = fw_counts_tiled_pallas(W, bt=bt)
    D2, N2 = ref.fw_counts_ref(W)
    assert_allclose(np.array(D1), np.array(D2), rtol=0)
    assert_allclose(np.array(N1), np.array(N2), rtol=0)


def test_fw_tiled_auto_dispatch():
    # fw_impl_tiled routes small V to the VMEM-resident kernel and large V
    # to the blocked-tile kernel; both must agree with the reference, so
    # the dispatch point is invisible in results.
    from repro.kernels.ops import FW_TILED_AUTO_V, fw_impl_tiled
    W = jnp.array(random_graph(24, 60, seed=1)[0])
    D1, N1 = fw_impl_tiled(W)
    D2, N2 = ref.fw_counts_ref(W)
    assert_allclose(np.array(D1), np.array(D2), rtol=0)
    assert_allclose(np.array(N1), np.array(N2), rtol=0)
    assert max(128, -(-24 // 128) * 128) <= FW_TILED_AUTO_V  # vmem path hit


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

ATTN_CASES = [
    dict(B=1, Sq=16, Sk=16, Hq=4, Hkv=4, d=16, causal=True),
    dict(B=2, Sq=24, Sk=24, Hq=4, Hkv=2, d=32, causal=True),
    dict(B=2, Sq=24, Sk=24, Hq=6, Hkv=2, d=16, causal=False),
    dict(B=1, Sq=8, Sk=32, Hq=4, Hkv=1, d=16, causal=True),   # chunk
    dict(B=1, Sq=32, Sk=32, Hq=2, Hkv=2, d=16, causal=True, window=7),
    dict(B=1, Sq=16, Sk=16, Hq=4, Hkv=4, d=16, causal=True, softcap=8.0),
]


@pytest.mark.parametrize("case", ATTN_CASES)
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_flash_attention(case, dtype):
    case = dict(case)
    B, Sq, Sk = case.pop("B"), case.pop("Sq"), case.pop("Sk")
    Hq, Hkv, d = case.pop("Hq"), case.pop("Hkv"), case.pop("d")
    q = jnp.array(randn(B, Sq, Hq, d)).astype(dtype)
    k = jnp.array(randn(B, Sk, Hkv, d)).astype(dtype)
    v = jnp.array(randn(B, Sk, Hkv, d)).astype(dtype)
    o1 = ops.flash_attention(q, k, v, impl="pallas", bq=8, bk=8, **case)
    o2 = ref.attention_ref(q, k, v, **case)
    tol = 2e-5 if dtype == np.float32 else 2e-2
    assert_allclose(np.array(o1, np.float32), np.array(o2, np.float32),
                    rtol=tol, atol=tol)


@pytest.mark.parametrize("S,Hq,Hkv,d,window", [
    (33, 4, 2, 16, None), (64, 8, 8, 32, None), (40, 4, 1, 16, 9)])
def test_decode_attention(S, Hq, Hkv, d, window):
    B = 3
    q = jnp.array(randn(B, Hq, d))
    kc = jnp.array(randn(B, S, Hkv, d))
    vc = jnp.array(randn(B, S, Hkv, d))
    lens = jnp.array([S, S // 2, 1], jnp.int32)
    o1 = ops.decode_attention(q, kc, vc, lens, impl="pallas", bs=8,
                              window=window)
    o2 = ref.decode_attention_ref(q, kc, vc, lens, window=window)
    assert_allclose(np.array(o1), np.array(o2), rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
# recurrences
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("Bt,S,Di,N", [(1, 8, 16, 4), (2, 12, 20, 8),
                                       (2, 7, 130, 4)])
def test_selective_scan(Bt, S, Di, N):
    x = jnp.array(randn(Bt, S, Di))
    dt = jnp.array(0.1 + R.random((Bt, S, Di)).astype(np.float32))
    A = jnp.array(-R.random((Di, N)).astype(np.float32))
    Bm = jnp.array(randn(Bt, S, N))
    Cm = jnp.array(randn(Bt, S, N))
    Dm = jnp.array(randn(Di))
    y1, h1 = ops.selective_scan(x, dt, A, Bm, Cm, Dm, impl="pallas", bd=8)
    y2, h2 = ref.selective_scan_ref(x, dt, A, Bm, Cm, Dm)
    assert_allclose(np.array(y1), np.array(y2), rtol=3e-5, atol=3e-5)
    assert_allclose(np.array(h1), np.array(h2), rtol=3e-5, atol=3e-5)


def test_selective_scan_carries_state():
    """Splitting a sequence across two kernel calls == one call."""
    Bt, S, Di, N = 1, 16, 8, 4
    x = jnp.array(randn(Bt, S, Di))
    dt = jnp.array(0.1 + R.random((Bt, S, Di)).astype(np.float32))
    A = jnp.array(-R.random((Di, N)).astype(np.float32))
    Bm, Cm = jnp.array(randn(Bt, S, N)), jnp.array(randn(Bt, S, N))
    Dm = jnp.array(randn(Di))
    y_full, h_full = ref.selective_scan_ref(x, dt, A, Bm, Cm, Dm)
    h = None
    ys = []
    for s0 in (0, 8):
        sl = slice(s0, s0 + 8)
        y, h = ops.selective_scan(x[:, sl], dt[:, sl], A, Bm[:, sl],
                                  Cm[:, sl], Dm, h, impl="pallas", bd=8)
        ys.append(np.array(y))
    assert_allclose(np.concatenate(ys, 1), np.array(y_full), rtol=3e-5,
                    atol=3e-5)
    assert_allclose(np.array(h), np.array(h_full), rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("B,S,D", [(1, 8, 16), (2, 20, 40), (2, 5, 130)])
def test_rglru_scan(B, S, D):
    x = jnp.array(randn(B, S, D))
    a = jnp.array((0.05 + 0.9 * R.random((B, S, D))).astype(np.float32))
    y1, h1 = ops.rglru_scan(x, a, impl="pallas", bd=8)
    y2, h2 = ref.rglru_ref(x, a)
    assert_allclose(np.array(y1), np.array(y2), rtol=3e-5, atol=3e-5)
    assert_allclose(np.array(h1), np.array(h2), rtol=3e-5, atol=3e-5)
