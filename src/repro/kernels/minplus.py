"""Pallas TPU kernels for min-plus / APSP — PlaceIT's scoring hot spot.

PlaceIT evaluates thousands of placements; every evaluation runs an
all-pairs-shortest-path with path *counting* over the PHY-level latency
graph (V = #PHYs + 2*#chiplets, a few hundred nodes).  On TPU the XLA
`fori_loop` formulation round-trips the (V, V) distance and count matrices
through HBM on every one of the V rank-1 relaxation steps.  Both kernels
below keep the working set VMEM-resident instead:

* ``fw_counts_pallas`` — batched whole-matrix Floyd-Warshall **with path
  counts**: one grid program per placement, (V, V) D and N matrices live in
  VMEM for the entire V-step relaxation.  This is the kernel the scorer
  uses (exact same math as ``ref.fw_counts_ref``).  V is padded to a
  multiple of 128 (lane width) with isolated nodes, but the pivots and
  the row strips stop at the true V: a pad pivot's candidates are at
  least 1e9 + 1e9, above every real entry (at most 1e9), and never tie
  below ``INF_CUT``, so it could change nothing that is returned.

* ``minplus_tiled_pallas`` — blocked tropical matmul (distances only) for
  graphs too large for a VMEM-resident FW; the classic (i, j, k) tiling
  with an accumulate-min inner loop.  Used for beyond-paper-scale APSP via
  repeated squaring.

* ``fw_counts_tiled_pallas`` — blocked-tile Floyd-Warshall **with path
  counts** for the 100+-chiplet regime (HexaMesh scale), where 3 x (V, V)
  float32 no longer fits VMEM.  The classic three-phase blocked FW
  (diagonal block -> row/col panels -> outer tiles), batched over
  placements; each grid program's (D, N) working set is one (bt, bt)
  tile.  Bit-for-bit equal to ``ref.fw_counts_ref`` — see the per-pivot
  snapshot scheme below.

Hardware note (DESIGN.md §3): (min, +) has no MXU mapping — these are VPU
kernels; tiles are (8k, 128)-aligned.  Off-TPU all kernels default to
interpret mode (``interpret=None`` auto-selects from the JAX backend) so
the CPU tests can check their results; interpret mode cannot show what
Mosaic refuses, which ``tests/test_tpu_compile.py`` checks by compiling
for a described v5e.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

INF_CUT = 1.0e8
_COUNT_CLIP = 1.0e30


def _default_interpret() -> bool:
    """Interpret off-TPU (the CPU tests), compile on TPU; pass an explicit
    bool to override.  Runs meant for the chip check the platform first
    (``chip_smoke.py``) instead of relying on this."""
    return jax.default_backend() != "tpu"


def _resolve_interpret(interpret) -> bool:
    return _default_interpret() if interpret is None else bool(interpret)


# ---------------------------------------------------------------------------
# Shared relaxation and in-kernel row/column reads.
#
# Mosaic lowers a dynamic row read or write of a VMEM ref on the sublane
# axis (``ref[0, pl.ds(k, 1), :]``), but refuses a dynamic single-lane
# column slice and ``lax.dynamic_slice`` on values altogether.  A column
# is therefore read as a masked lane reduction that selects one element
# per row — exact, since min(x, +inf, ..., +inf) == x — and written as a
# masked whole-tile store.
# ---------------------------------------------------------------------------

def _fw_step(Td, Tn, a_d, a_n, b_d, b_n, mask):
    """One rank-1 pivot update on a tile — the exact ref.fw_counts_ref
    expressions (operand order preserved for bitwise equality).  ``mask``
    is the ``notk`` mask restricted to the tile (or None when the tile
    provably excludes row/col k)."""
    cand = a_d + b_d
    ncand = jnp.minimum(a_n * b_n, _COUNT_CLIP)
    lt = cand < Td
    eq = (cand == Td) & (cand < INF_CUT)
    if mask is not None:
        lt = lt & mask
        eq = eq & mask
    Td = jnp.where(lt, cand, Td)
    Tn = jnp.where(lt, ncand, Tn + jnp.where(eq, ncand, 0.0))
    Tn = jnp.minimum(Tn, _COUNT_CLIP)
    return Td, Tn


def _init_counts(W, eye):
    """N0: 1 for finite off-diagonal edges, identity diagonal (== ref)."""
    return jnp.where((W < INF_CUT) & ~eye, 1.0, 0.0) + eye.astype(W.dtype)


def _iota(shape, axis, offset=0):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis) + offset


def _col(X, k, col):
    """Column k of X as [rows, 1]; ``col`` is X's lane-index iota."""
    return jnp.min(jnp.where(col == k, X, jnp.inf), axis=1, keepdims=True)


def _row(ref, k):
    """Row k of a [1, rows, cols] block ref as [1, cols]."""
    return ref[0, pl.ds(k, 1), :]


def _set_row(ref, k, v):
    ref[0, pl.ds(k, 1), :] = v


def _set_col(ref, k, v, col):
    ref[0] = jnp.where(col == k, v, ref[0])


# ---------------------------------------------------------------------------
# Batched VMEM-resident Floyd-Warshall with path counts.
# ---------------------------------------------------------------------------

_STRIP_ELEMS = 16 * 1024        # ~16 f32 vregs per strip operand


def _strip_rows(V: int) -> int:
    """Row-strip height of the VMEM kernel: a power of two in [8, V], so
    it divides the 128-multiple V, keeping one strip near 16 vregs."""
    rs = 8
    while rs * 2 <= V and rs * 2 * V <= _STRIP_ELEMS:
        rs *= 2
    return rs


def _fw_counts_kernel(w_ref, d_ref, n_ref, *, V: int, Vp: int, rs: int):
    """D and N live in the VMEM output blocks, [Vp, Vp] with V real
    vertices.  Pivots run over k < V only, and each sweeps just the
    ceil(V / rs) row strips of (rs, Vp) that hold real rows; lanes stay
    Vp wide.  Row k and column k are masked from pivot k's update, so
    every strip reads their time-k values whatever the sweep order.

    Exact: a pivot k < V updates entry (i, j) from D[i, k] and D[k, j]
    alone, so the real block goes through the reference's updates on the
    unpadded graph.  The pivots left out are the isolated pad nodes,
    whose candidates are at least 1e9 + 1e9 against real entries of at
    most 1e9, and so never win and never tie below ``INF_CUT``; rows past
    V are sliced off by the caller."""
    col = _iota((rs, Vp), 1)

    def strips(fn, n):
        def body(s, carry):
            r0 = pl.multiple_of(s * rs, rs)
            fn(pl.ds(r0, rs), _iota((rs, Vp), 0, r0))
            return carry
        jax.lax.fori_loop(0, n, body, 0)

    def init(rows, row):
        W = w_ref[0, rows, :]
        d_ref[0, rows, :] = W
        n_ref[0, rows, :] = _init_counts(W, row == col)

    strips(init, Vp // rs)

    def pivot(k, carry):
        b_d, b_n = _row(d_ref, k), _row(n_ref, k)

        def relax(rows, row):
            Td, Tn = d_ref[0, rows, :], n_ref[0, rows, :]
            Td, Tn = _fw_step(Td, Tn, _col(Td, k, col), _col(Tn, k, col),
                              b_d, b_n, (row != k) & (col != k))
            d_ref[0, rows, :] = Td
            n_ref[0, rows, :] = Tn

        strips(relax, -(-V // rs))
        return carry

    jax.lax.fori_loop(0, V, pivot, 0)


def _vmem_limit(V: int) -> int:
    """Scoped-VMEM request of the VMEM kernel: W, D and N blocks, each
    double-buffered by the grid pipeline, plus 4 MiB for strip temporaries
    and Mosaic's internal scratch."""
    return 6 * V * V * 4 + (4 << 20)


def _pad_isolated(W: jnp.ndarray, Vp: int) -> jnp.ndarray:
    """Pad [B, V, V] up to [B, Vp, Vp] with isolated nodes (diag 0, else
    INF); padded rows/cols never interact with real nodes, so the result
    restricted to real indices is bit-for-bit the unpadded computation."""
    B, V0, _ = W.shape
    if Vp == V0:
        return W
    pad = jnp.full((B, Vp, Vp), 1e9, dtype=W.dtype)
    pad = pad.at[:, :V0, :V0].set(W)
    idx = jnp.arange(V0, Vp)
    return pad.at[:, idx, idx].set(0.0)


def fw_counts_pallas(W: jnp.ndarray, *, interpret: bool | None = None
                     ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Batched FW + counts.  W: [B, V, V] (or [V, V]) float32.

    Pads V up to a multiple of 128 with isolated nodes (diag 0, else INF)
    for the lane axis, and passes the true V to the kernel, which runs
    only the V real pivots over the row strips that hold real rows (see
    ``_fw_counts_kernel`` for why that is exact).  At V % 128 == 0 the
    kernel is the untrimmed program.
    """
    interpret = _resolve_interpret(interpret)
    squeeze = W.ndim == 2
    if squeeze:
        W = W[None]
    B, V0, _ = W.shape
    Vp = max(128, -(-V0 // 128) * 128)
    W = _pad_isolated(W, Vp)
    kern = functools.partial(_fw_counts_kernel, V=V0, Vp=Vp,
                             rs=_strip_rows(Vp))
    block = pl.BlockSpec((1, Vp, Vp), lambda b: (b, 0, 0))
    D, N = pl.pallas_call(
        kern,
        grid=(B,),
        in_specs=[block],
        out_specs=[block, block],
        out_shape=[jax.ShapeDtypeStruct((B, Vp, Vp), W.dtype)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_vmem_limit(Vp)),
        interpret=interpret,
        name="fw_counts_vmem",
    )(W)
    D, N = D[:, :V0, :V0], N[:, :V0, :V0]
    if squeeze:
        D, N = D[0], N[0]
    return D, N


# ---------------------------------------------------------------------------
# Blocked-tile Floyd-Warshall WITH path counts (the 100+-chiplet regime).
#
# Naive blocked FW (fully relax the pivot block and panels, then one
# min-plus GEMM over the outer tiles) is correct for distances but WRONG
# for path counts: replaying a whole pivot block against an outer tile
# with end-of-block panel values double-counts paths that tie through
# several pivots.  The scheme below is exact — bit-for-bit equal to the
# sequential ``fw_counts_ref`` — because every tile replays the reference's
# per-pivot rank-1 updates with the reference's operands:
#
# * Pivots k inside a block are processed strictly in order.  At pivot k,
#   row k and column k are themselves masked from the update (the ``notk``
#   mask), so their time-k values equal their state after pivots < k.
# * Phase 1 (diagonal block) records, for each local pivot k, *snapshots*
#   of row k and column k at time k.  Phase 2 (row/col panels) consumes
#   the diagonal snapshots and records full panel snapshots at time k.
#   Phase 3 (outer tiles) replays the per-pivot updates from the column-
#   and row-panel snapshots.  Each (cell, pivot) update therefore sees
#   exactly the operands the sequential algorithm saw, in the same order,
#   evaluated by the same jnp expressions — float32 equality is bitwise,
#   not approximate (no re-association anywhere).
# * Phase 3 must *skip* the pivot row/col tiles (min is idempotent for D,
#   but N's tie-accumulation is not) — they were already updated exactly
#   once by phases 1/2.
#
# D and N live in HBM between the per-pivot-block pallas_calls (a host
# Python loop unrolled at trace time); each grid program touches only
# (bt, bt) tiles, so VMEM stays O(bt^2) regardless of V.  Inside a
# program the tile being relaxed lives in its output block, so rows of it
# are read with ``_row`` like the snapshots.
# ---------------------------------------------------------------------------

def _fw_diag_kernel(d_ref, n_ref, do_ref, no_ref, rd_ref, rn_ref,
                    cd_ref, cn_ref, *, bt: int):
    """Phase 1: relax the (bt, bt) pivot block over its own bt pivots,
    emitting per-pivot row snapshots (rd/rn, row k at time k) and column
    snapshots (cd/cn, column k at time k)."""
    row, col = _iota((bt, bt), 0), _iota((bt, bt), 1)
    do_ref[0], no_ref[0] = d_ref[0], n_ref[0]
    cd_ref[0] = jnp.zeros((bt, bt), cd_ref.dtype)
    cn_ref[0] = jnp.zeros((bt, bt), cn_ref.dtype)

    def body(k, carry):
        D, N = do_ref[0], no_ref[0]
        b_d, b_n = _row(do_ref, k), _row(no_ref, k)    # row k @ time k
        a_d, a_n = _col(D, k, col), _col(N, k, col)    # col k @ time k
        _set_row(rd_ref, k, b_d)
        _set_row(rn_ref, k, b_n)
        _set_col(cd_ref, k, a_d, col)
        _set_col(cn_ref, k, a_n, col)
        D, N = _fw_step(D, N, a_d, a_n, b_d, b_n, (row != k) & (col != k))
        do_ref[0], no_ref[0] = D, N
        return carry

    jax.lax.fori_loop(0, bt, body, 0)


def _fw_panel_kernel(d_ref, n_ref, sd_ref, sn_ref, dd_ref, dn_ref,
                     ds2_ref, ds3_ref, od_ref, on_ref, pd_ref, pn_ref,
                     *, bt: int, kk: int, is_row: bool):
    """Phase 2: relax one (bt, bt) panel tile over the block's bt pivots,
    consuming the diagonal snapshots (sd/sn) and emitting this panel's own
    per-pivot snapshots (pd/pn).  The tile at the pivot block itself
    (j == kk) copies phase 1's results instead of re-updating (N's
    tie-accumulation is not idempotent).

    Row panels (is_row): tile rows are the pivot rows; the pivot's "a"
    operand D[i, k] is the diagonal *column* snapshot, the "b" operand
    D[k, j] is the tile's own row k (masked at pivot k, so current ==
    time-k).  Col panels are the transpose."""
    j = pl.program_id(1)

    @pl.when(j == kk)
    def _copy_diag():
        od_ref[0], on_ref[0] = dd_ref[0], dn_ref[0]
        pd_ref[0], pn_ref[0] = ds2_ref[0], ds3_ref[0]

    @pl.when(j != kk)
    def _relax():
        col = _iota((bt, bt), 1)
        iot = _iota((bt, bt), 0) if is_row else col
        od_ref[0], on_ref[0] = d_ref[0], n_ref[0]
        if not is_row:
            pd_ref[0] = jnp.zeros((bt, bt), pd_ref.dtype)
            pn_ref[0] = jnp.zeros((bt, bt), pn_ref.dtype)

        def body(k, carry):
            D, N = od_ref[0], on_ref[0]
            if is_row:
                b_d, b_n = _row(od_ref, k), _row(on_ref, k)
                _set_row(pd_ref, k, b_d)
                _set_row(pn_ref, k, b_n)
                a_d, a_n = _col(sd_ref[0], k, col), _col(sn_ref[0], k, col)
            else:
                a_d, a_n = _col(D, k, col), _col(N, k, col)
                _set_col(pd_ref, k, a_d, col)
                _set_col(pn_ref, k, a_n, col)
                b_d, b_n = _row(sd_ref, k), _row(sn_ref, k)
            D, N = _fw_step(D, N, a_d, a_n, b_d, b_n, iot != k)
            od_ref[0], on_ref[0] = D, N
            return carry

        jax.lax.fori_loop(0, bt, body, 0)


def _fw_outer_kernel(d_ref, n_ref, cd_ref, cn_ref, rd_ref, rn_ref,
                     od_ref, on_ref, *, bt: int, kk: int):
    """Phase 3: replay the block's bt pivots on one outer (bt, bt) tile
    from the col-panel (cd/cn) and row-panel (rd/rn) snapshots.  Pivot
    row/col tiles pass through unchanged — they were already updated by
    phases 1/2 (re-applying would double-count N ties)."""
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when((i == kk) | (j == kk))
    def _copy():
        od_ref[0], on_ref[0] = d_ref[0], n_ref[0]

    @pl.when((i != kk) & (j != kk))
    def _relax():
        col = _iota((bt, bt), 1)

        def body(k, carry):
            D, N = carry
            a_d, a_n = _col(cd_ref[0], k, col), _col(cn_ref[0], k, col)
            b_d, b_n = _row(rd_ref, k), _row(rn_ref, k)
            return _fw_step(D, N, a_d, a_n, b_d, b_n, None)

        D, N = jax.lax.fori_loop(0, bt, body, (d_ref[0], n_ref[0]))
        od_ref[0], on_ref[0] = D, N


def fw_counts_tiled_pallas(W: jnp.ndarray, *, bt: int = 128,
                           interpret: bool | None = None
                           ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Blocked three-phase FW + path counts; bit-for-bit == fw_counts_ref.

    W: [B, V, V] (or [V, V]) float32 with 0 diagonal.  V is padded to a
    multiple of ``bt`` with isolated nodes.  Per-grid-program working set
    is O(bt^2) — use this when the VMEM-resident kernel's blocks exceed
    VMEM (see ``ops.FW_TILED_AUTO_V`` for the dispatch knee).
    """
    interpret = _resolve_interpret(interpret)
    squeeze = W.ndim == 2
    if squeeze:
        W = W[None]
    B, V0, _ = W.shape
    Vt = max(bt, -(-V0 // bt) * bt)
    nb = Vt // bt
    W = _pad_isolated(W, Vt)
    D, N = W, _init_counts(W, jnp.eye(Vt, dtype=bool))

    spec = pl.BlockSpec((1, bt, bt), lambda b: (b, 0, 0))
    shp = jax.ShapeDtypeStruct((B, bt, bt), W.dtype)

    def params(n_grid):
        return pltpu.CompilerParams(
            dimension_semantics=("parallel",) * n_grid)

    for kk in range(nb):
        k0 = kk * bt
        # -- phase 1: pivot block + per-pivot row/col snapshots ------------
        dD = jax.lax.dynamic_slice(D, (0, k0, k0), (B, bt, bt))
        dN = jax.lax.dynamic_slice(N, (0, k0, k0), (B, bt, bt))
        dD2, dN2, rdD, rdN, cdD, cdN = pl.pallas_call(
            functools.partial(_fw_diag_kernel, bt=bt),
            grid=(B,),
            in_specs=[spec, spec],
            out_specs=[spec] * 6,
            out_shape=[shp] * 6,
            compiler_params=params(1),
            interpret=interpret,
            name="fw_tiled_diag",
        )(dD, dN)

        # -- phase 2: row + col panels, emitting panel snapshots -----------
        tile_j = pl.BlockSpec((1, bt, bt), lambda b, j: (b, 0, j))
        tile_i = pl.BlockSpec((1, bt, bt), lambda b, j: (b, j, 0))
        fixed = pl.BlockSpec((1, bt, bt), lambda b, j: (b, 0, 0))
        row_shp = jax.ShapeDtypeStruct((B, bt, Vt), W.dtype)
        col_shp = jax.ShapeDtypeStruct((B, Vt, bt), W.dtype)
        rowD = jax.lax.dynamic_slice(D, (0, k0, 0), (B, bt, Vt))
        rowN = jax.lax.dynamic_slice(N, (0, k0, 0), (B, bt, Vt))
        rowD2, rowN2, rsD, rsN = pl.pallas_call(
            functools.partial(_fw_panel_kernel, bt=bt, kk=kk, is_row=True),
            grid=(B, nb),
            in_specs=[tile_j, tile_j] + [fixed] * 6,
            out_specs=[tile_j] * 4,
            out_shape=[row_shp] * 4,
            compiler_params=params(2),
            interpret=interpret,
            name="fw_tiled_row_panel",
        )(rowD, rowN, cdD, cdN, dD2, dN2, rdD, rdN)
        colD = jax.lax.dynamic_slice(D, (0, 0, k0), (B, Vt, bt))
        colN = jax.lax.dynamic_slice(N, (0, 0, k0), (B, Vt, bt))
        colD2, colN2, csD, csN = pl.pallas_call(
            functools.partial(_fw_panel_kernel, bt=bt, kk=kk, is_row=False),
            grid=(B, nb),
            in_specs=[tile_i, tile_i] + [fixed] * 6,
            out_specs=[tile_i] * 4,
            out_shape=[col_shp] * 4,
            compiler_params=params(2),
            interpret=interpret,
            name="fw_tiled_col_panel",
        )(colD, colN, rdD, rdN, dD2, dN2, cdD, cdN)
        D = jax.lax.dynamic_update_slice(D, rowD2, (0, k0, 0))
        N = jax.lax.dynamic_update_slice(N, rowN2, (0, k0, 0))
        D = jax.lax.dynamic_update_slice(D, colD2, (0, 0, k0))
        N = jax.lax.dynamic_update_slice(N, colN2, (0, 0, k0))

        # -- phase 3: outer tiles from the panel snapshots -----------------
        full = pl.BlockSpec((1, bt, bt), lambda b, i, j: (b, i, j))
        cpan = pl.BlockSpec((1, bt, bt), lambda b, i, j: (b, i, 0))
        rpan = pl.BlockSpec((1, bt, bt), lambda b, i, j: (b, 0, j))
        D, N = pl.pallas_call(
            functools.partial(_fw_outer_kernel, bt=bt, kk=kk),
            grid=(B, nb, nb),
            in_specs=[full, full, cpan, cpan, rpan, rpan],
            out_specs=[full, full],
            out_shape=[jax.ShapeDtypeStruct((B, Vt, Vt), W.dtype)] * 2,
            compiler_params=params(3),
            interpret=interpret,
            name="fw_tiled_outer",
        )(D, N, csD, csN, rsD, rsN)

    D, N = D[:, :V0, :V0], N[:, :V0, :V0]
    if squeeze:
        D, N = D[0], N[0]
    return D, N


# ---------------------------------------------------------------------------
# Tiled min-plus matmul (distances only) for large V.
# ---------------------------------------------------------------------------

def _minplus_kernel(a_ref, b_ref, o_ref, *, bk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref, 1e9)

    a = a_ref[...]                                  # (bm, bk)
    b = b_ref[...]                                  # (bk, bn)

    def body(t, acc):
        # Rank-1 (min, +) update: keeps the working set at (bm, bn).
        return jnp.minimum(acc, a[:, t][:, None] + b[t, :][None, :])

    o_ref[...] = jax.lax.fori_loop(0, bk, body, o_ref[...])


def minplus_tiled_pallas(A: jnp.ndarray, B: jnp.ndarray, *,
                         bm: int = 128, bn: int = 128, bk: int = 128,
                         interpret: bool | None = None) -> jnp.ndarray:
    """Tropical matmul out[i,j] = min_k A[i,k] + B[k,j], tiled for VMEM.

    A: [M, K], B: [K, N]; M, N, K padded to tile multiples with +INF
    (identity of min) — padding never wins the min.
    """
    interpret = _resolve_interpret(interpret)
    M, K = A.shape
    K2, N = B.shape
    assert K == K2
    Mp, Kp, Np = (-(-M // bm) * bm, -(-K // bk) * bk, -(-N // bn) * bn)
    Ap = jnp.full((Mp, Kp), 1e9, A.dtype).at[:M, :K].set(A)
    Bp = jnp.full((Kp, Np), 1e9, B.dtype).at[:K, :N].set(B)
    out = pl.pallas_call(
        functools.partial(_minplus_kernel, bk=bk),
        grid=(Mp // bm, Np // bn, Kp // bk),
        in_specs=[pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
                  pl.BlockSpec((bk, bn), lambda i, j, k: (k, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), A.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(Ap, Bp)
    return out[:M, :N]


def apsp_tiled_pallas(W: jnp.ndarray, *, interpret: bool | None = None,
                      **tile_kw) -> jnp.ndarray:
    """APSP by repeated tiled min-plus squaring (distances only).

    ceil(log2(V-1)) squarings suffice: after t rounds D covers all paths
    of <= 2^t hops, and a shortest path has at most V-1 hops.  V is a
    Python int here, so the count is host math, not a traced op.
    """
    V = W.shape[-1]
    D = W
    n_iter = max(1, math.ceil(math.log2(max(V - 1, 2))))
    for _ in range(n_iter):
        D = jnp.minimum(D, minplus_tiled_pallas(D, D, interpret=interpret,
                                                **tile_kw))
    return D
