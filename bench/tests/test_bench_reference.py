"""The benchmark's float64 reference against the program, on the CPU at
small sizes: Floyd-Warshall with counts against the kernel oracle, the
virtual-node folding against a full solve, graphs, flags and metrics of
random placements against the program's host builders and scorer, the
component rule of connectivity against the paths, and the normalizers
of a draw against the program's."""
import json
import os

import numpy as np
import pytest

from bench import reference as R

HERE = os.path.dirname(os.path.abspath(__file__))


def _random_graph(V, density, seed, symmetric=True, split=False):
    rng = np.random.default_rng(seed)
    W = np.where(rng.random((V, V)) < density,
                 rng.choice([10.0, 25.0], (V, V)), R.INF)
    if symmetric:
        W = np.minimum(W, W.T)
    if split:
        h = V // 2
        W[:h, h:] = R.INF
        W[h:, :h] = R.INF
    np.fill_diagonal(W, 0.0)
    return W


@pytest.mark.parametrize("V,density,symmetric,split", [
    (5, 0.6, True, False), (13, 0.3, True, False), (24, 0.15, False, False),
    (24, 0.3, True, True), (40, 0.1, False, True)])
def test_fw_counts_matches_kernel_oracle(V, density, symmetric, split):
    import jax.numpy as jnp
    from repro.kernels.ref import fw_counts_ref
    W = _random_graph(V, density, V, symmetric, split)
    D, N = R.fw_counts(W)
    d, n = fw_counts_ref(jnp.asarray(W, jnp.float32))
    np.testing.assert_array_equal(D, np.asarray(d, np.float64))
    np.testing.assert_array_equal(N, np.asarray(n, np.float64))
    if split:
        assert (D >= R.INF_CUT).any()


def _config(name):
    with open(os.path.join(HERE, name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["homog32_small"])
def test_virtual_nodes_fold_like_a_full_solve(name):
    from repro.core.api import make_rep
    from repro.core.chiplets import resolve_arch
    cfg = _config(name)
    arch = R.Arch(cfg)
    rep = make_rep(resolve_arch(cfg["arch"], "baseline"), cfg["arch"],
                   cfg["mutation_mode"])
    g = R.graph_of(arch, rep.random(np.random.default_rng(1)))
    P = R.paths_of([g])[0]
    D, N = R.fw_counts(g.full_W())
    src = arch.Vp + np.arange(arch.N)
    dst = arch.Vp + arch.N + np.arange(arch.N)
    phy = np.arange(arch.Vp)
    np.testing.assert_array_equal(P.Ds, D[np.ix_(src, phy)])
    np.testing.assert_array_equal(P.Ns, N[np.ix_(src, phy)])
    np.testing.assert_array_equal(P.Dt, D[np.ix_(phy, dst)])
    np.testing.assert_array_equal(P.Nt, N[np.ix_(phy, dst)])
    np.testing.assert_array_equal(P.Dst, D[np.ix_(src, dst)])
    np.testing.assert_array_equal(P.Nst, N[np.ix_(src, dst)])


@pytest.mark.parametrize("name", ["homog32_small"])
def test_reference_agrees_with_program(name):
    from repro.core.api import make_evaluator, make_rep
    from repro.core.chiplets import resolve_arch
    from repro.core.topology import stack_graphs
    cfg = _config(name)
    arch = R.Arch(cfg)
    parch = resolve_arch(cfg["arch"], "baseline")
    rep = make_rep(parch, cfg["arch"], cfg["mutation_mode"])
    rng = np.random.default_rng(5)
    ev = make_evaluator(rep, parch, rng=rng, norm_samples=4, chunk=4)
    sols = [rep.random(rng) for _ in range(4)]
    graphs = [rep.score_graph(s) for s in sols]
    out = ev.score_batch(stack_graphs(graphs))
    refs = [R.graph_of(arch, s) for s in sols]
    for i, (g, ref, p) in enumerate(zip(graphs, refs, R.paths_of(refs))):
        np.testing.assert_array_equal(g.W, ref.full_W().astype(np.float32))
        m = R.metrics(arch, ref, p)
        conn = m["connected_paths"]
        assert conn == g.connected
        if not conn:
            continue
        for k in ("lat_c2c", "lat_c2m", "thr_c2m", "thr_m2i", "area"):
            assert R.rel_gap(float(out[k][i]), m[k]) < 1e-5, k


def _draws(rep, seed, n):
    rng = np.random.default_rng(seed)
    return [rep.random(rng) for _ in range(n)]


@pytest.mark.parametrize("seed", [11, 2 ** 31 + 7])
def test_connected_rule_agrees_with_paths_and_program(seed):
    from repro.core.api import make_rep
    from repro.core.chiplets import resolve_arch
    cfg = _config("homog32_small")
    arch = R.Arch(cfg)
    rep = make_rep(resolve_arch(cfg["arch"], "baseline"), cfg["arch"],
                   cfg["mutation_mode"])
    draws = _draws(rep, seed, 60)
    graphs = [R.graph_of(arch, s) for s in draws]
    flags = [R.connected(arch, [tuple(e) for e in g.edges[:len(g.edges)
                                                         // 2]])
             for g in graphs]
    paths = [R.metrics(arch, g, p)["connected_paths"]
             for g, p in zip(graphs, R.paths_of(graphs))]
    assert flags == paths
    assert flags == [rep.score_graph(s).connected for s in draws]
    assert any(flags) and not all(flags)


@pytest.mark.parametrize("policy", ["mean", "median"])
def test_normalizers_agree_with_program(policy):
    from repro.core.api import make_evaluator, make_rep
    from repro.core.chiplets import resolve_arch
    from repro.core.objective import Objective
    cfg = _config("homog32_small")
    arch = R.Arch(cfg)
    parch = resolve_arch(cfg["arch"], "baseline")
    rep = make_rep(parch, cfg["arch"], cfg["mutation_mode"])
    ev = make_evaluator(rep, parch, rng=np.random.default_rng(9),
                        norm_samples=6, chunk=4,
                        objective=Objective(normalizer=policy))
    draws = _draws(rep, 9, 500)
    ref = R.normalizers(arch, draws, 6, policy)
    n = ev.norm
    prog = {f"lat_{t}": n.lat[t] for t in R.TRAFFIC}
    prog |= {f"inv_thr_{t}": n.inv_thr[t] for t in R.TRAFFIC}
    prog["area"] = n.area
    for k, v in ref.items():
        assert R.rel_gap(prog[k], v) < 1e-5, k
    assert R.normalizers(arch, draws[:3], 6, policy) is None


def test_pooled_reference_is_the_serial_reference():
    """Floyd-Warshall and the metrics on a pool of threads give every
    placement's metrics, and the normalizers reduced from them, bit for
    bit as one thread does."""
    from repro.core.api import make_rep
    from repro.core.chiplets import resolve_arch
    cfg = _config("homog32_small")
    arch = R.Arch(cfg)
    rep = make_rep(resolve_arch(cfg["arch"], "baseline"), cfg["arch"],
                   cfg["mutation_mode"])
    draws = _draws(rep, 2 ** 31 + 3, 200)
    graphs = [R.graph_of(arch, s) for s in draws[:12]]
    serial = R.metrics_of(arch, graphs, threads=1)
    assert R.metrics_of(arch, graphs, threads=5) == serial
    assert serial == [R.metrics(arch, g, p)
                      for g, p in zip(graphs, R.paths_of(graphs))]
    for policy in ("mean", "median"):
        one = R.normalizers(arch, draws, 8, policy, threads=1)
        assert one is not None
        assert R.normalizers(arch, draws, 8, policy, threads=4) == one


@pytest.mark.parametrize("block", [7, 64])
def test_link_load_in_blocks_is_the_whole_sum(block, monkeypatch):
    """Each link's load summed over its own (s, t) pairs, a block of
    links at a time, is bit for bit the einsum over every link at once."""
    from repro.core.api import make_rep
    from repro.core.chiplets import resolve_arch
    monkeypatch.setattr(R, "EDGE_BLOCK", block)
    cfg = _config("homog32_small")
    arch = R.Arch(cfg)
    rep = make_rep(resolve_arch(cfg["arch"], "baseline"), cfg["arch"],
                   cfg["mutation_mode"])
    graphs = [R.graph_of(arch, s) for s in _draws(rep, 2 ** 31 + 9, 6)]
    for g, P in zip(graphs, R.paths_of(graphs)):
        assert len(g.edges) > block
        for ks, kd in R.ENDPOINTS.values():
            srcs = np.flatnonzero(arch.kinds == ks)
            dsts = np.flatnonzero(arch.kinds == kd)
            dem = np.random.default_rng(len(srcs)).random(
                (len(srcs), len(dsts)))
            whole = np.einsum("st,set->e", dem, R._on_path_use(
                P, g, srcs, dsts, slice(None)))
            got = R._link_load(P, g, srcs, dsts, dem)
            assert got.view(np.uint64).tolist() == \
                whole.view(np.uint64).tolist()
