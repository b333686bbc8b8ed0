"""Plain reference for the benchmark's `correct` check: placement -> graph
-> float64 Floyd-Warshall with path counts -> proxy metrics -> cost
normalizers and objective cost.

It follows the paper's semantics (PlaceIT, arXiv 2502.01449, §IV-§VI) as
the configuration and traffic files state them, and imports nothing of
the program under test: only numpy.  Its inputs are placements as the
search produced them, the placements of the run's normalizer draw, the
configuration file (chiplet shapes, PHYs, latencies, grid) and the
traffic file (objective terms and weights).  It covers the homogeneous
grid family, the one the benchmark's configurations use.

Graph (node layout of the scored graph, V = Vp + 2N):
  [0, Vp)           PHYs, numbered by chiplet instance then local PHY;
  [Vp, Vp + N)      one virtual source per chiplet (edges to its PHYs, 0);
  [Vp + N, V)       one virtual sink per chiplet (edges from its PHYs, 0).
A D2D link joins two PHYs at 2*l_phy + l_link cycles; the PHYs of a
relay-capable chiplet are joined pairwise at l_relay.

Shortest paths never pass through a virtual node (sources have no
in-edges, sinks no out-edges), so the reference runs Floyd-Warshall over
the PHYs alone and extends distances and counts to the virtual nodes by
grouping: the distance from a chiplet's source is the least over its
PHYs, and the count sums the counts of the PHYs that attain it.
"""
from __future__ import annotations

import concurrent.futures
import math
import os

import numpy as np

INF = 1.0e9          # weight of a missing edge
INF_CUT = 1.0e8      # distances at or above this are unreachable
EPS = 1.0e-6         # floor of normalizers and throughputs in the cost
KIND_NAMES = ("compute", "memory", "io")
TRAFFIC = ("c2c", "c2m", "c2i", "m2i")
ENDPOINTS = {"c2c": (0, 0), "c2m": (0, 1), "c2i": (0, 2), "m2i": (1, 2)}


# ---------------------------------------------------------------------------
# Architecture, from the configuration file.
# ---------------------------------------------------------------------------

class Arch:
    """Chiplet instances (compute, then memory, then io), their PHYs and
    the latency parameters, as the configuration file states them."""

    def __init__(self, cfg: dict):
        if cfg["family"] != "homog":
            raise ValueError(f"the reference covers the homog family, not "
                             f"{cfg['family']!r}")
        self.shape = {}
        kinds = []
        for k, name in enumerate(KIND_NAMES):
            c = cfg["chiplets"][name]
            self.shape[k] = (float(c["w"]), float(c["h"]),
                             tuple((float(x), float(y)) for x, y in c["phys"]),
                             bool(c["relay"]))
            kinds += [k] * int(c["count"])
        self.kinds = np.array(kinds, np.int64)
        self.N = len(kinds)
        nphys = np.array([len(self.shape[k][2]) for k in kinds])
        self.phy_base = np.concatenate([[0], np.cumsum(nphys)])
        self.Vp = int(self.phy_base[-1])
        self.V = self.Vp + 2 * self.N
        self.owner = np.repeat(np.arange(self.N), nphys)
        self.relay = np.array([self.shape[k][3] for k in kinds])
        lat = cfg["latency"]
        self.d2d = 2.0 * float(lat["l_phy"]) + float(lat["l_link"])
        self.l_relay = float(lat["l_relay"])
        # PHY indices of each chiplet, padded with -1 to the widest.
        width = int(nphys.max())
        self.groups = np.full((self.N, width), -1, np.int64)
        for c in range(self.N):
            n = nphys[c]
            self.groups[c, :n] = self.phy_base[c] + np.arange(n)
        # Ordered PHY pairs inside each relay chiplet.
        pairs = [(a, b) for c in np.flatnonzero(self.relay)
                 for a in self.groups[c] for b in self.groups[c]
                 if a >= 0 and b >= 0 and a != b]
        self.relay_pairs = np.array(pairs, np.int64).reshape(-1, 2)
        # Each PHY's first PHY in its relay chiplet (itself elsewhere).
        self.relay_root = np.arange(self.Vp)
        for c in np.flatnonzero(self.relay):
            g = self.groups[c][self.groups[c] >= 0]
            self.relay_root[g] = g[0]
        # PHY offsets of each kind in each of its four turns [4, P, 2]:
        # single-PHY chiplets turn by their rotation, four-PHY ones do not.
        self.turned = {}
        for k, (w, h, phys, _) in self.shape.items():
            self.turned[k] = np.array(
                [rotated(w, h, phys, r if len(phys) == 1 else 0)[2]
                 for r in range(4)])
        # Instances of each kind, in order.
        self.of_kind = {k: np.flatnonzero(self.kinds == k) for k in range(3)}


def rotated(w: float, h: float, phys, rot: int):
    """A chiplet turned rot * 90 degrees counter-clockwise, re-anchored at
    the origin: (x, y) -> (h - y, x) per quarter turn."""
    for _ in range(int(rot) % 4):
        phys = tuple((h - y, x) for x, y in phys)
        w, h = h, w
    return w, h, phys


# ---------------------------------------------------------------------------
# Placement -> PHY positions and links.
# ---------------------------------------------------------------------------

def homog_geometry(arch: Arch, types, rot):
    """Grid placement (types [R, C] with -1 for empty, rot [R, C]) -> PHY
    positions [Vp, 2] in mm and the package area.  The j-th cell of a
    kind in row-major order holds that kind's j-th instance."""
    types = np.asarray(types)
    rot = np.asarray(rot)
    R, C = types.shape
    w, h = arch.shape[0][0], arch.shape[0][1]
    pos = np.zeros((arch.Vp, 2))
    for k in range(3):
        r, c = np.nonzero(types == k)
        inst = arch.of_kind[k][:len(r)]
        off = arch.turned[k][rot[r, c] % 4]                     # [n, P, 2]
        idx = arch.phy_base[inst][:, None] + np.arange(off.shape[1])
        pos[idx] = np.stack([c * w, r * h], -1)[:, None, :] + off
    return pos, w * h * R * C


def homog_links(arch: Arch, pos):
    """Two chiplets on adjacent cells are linked where their PHYs face
    each other, i.e. where two PHYs of different chiplets coincide.
    Returns the (p, q) pairs, p < q, in order."""
    key = np.round(pos * 1e6).astype(np.int64)
    order = np.lexsort((key[:, 1], key[:, 0]))
    ks = key[order]
    same = np.all(ks[1:] == ks[:-1], axis=1)
    p, q = order[:-1][same], order[1:][same]
    keep = arch.owner[p] != arch.owner[q]
    lo, hi = np.minimum(p, q)[keep], np.maximum(p, q)[keep]
    o = np.lexsort((hi, lo))
    return list(zip(lo[o].tolist(), hi[o].tolist()))


def connected(arch: Arch, links) -> bool:
    """Every chiplet's source reaches every chiplet's sink.  Links and
    relay joins are symmetric, so chiplet c reaches chiplet d exactly
    where one component of the PHY graph holds a PHY of each."""
    parent = arch.relay_root.tolist()

    def find(a):
        while parent[a] != a:
            parent[a] = parent[parent[a]]
            a = parent[a]
        return a

    for a, b in links:
        ra, rb = find(a), find(b)
        if ra != rb:
            parent[ra] = rb
    comp = np.array(parent)
    while not (comp[comp] == comp).all():
        comp = comp[comp]
    member = np.zeros((arch.N, arch.Vp), np.float32)
    member[arch.owner, comp] = 1.0
    return bool((member @ member.T > 0).all())


# ---------------------------------------------------------------------------
# Graph.
# ---------------------------------------------------------------------------

class Graph:
    """One placement's PHY graph: weights Wp [Vp, Vp], the directed link
    list and the area."""

    def __init__(self, arch: Arch, links, area):
        Wp = np.full((arch.Vp, arch.Vp), INF)
        np.fill_diagonal(Wp, 0.0)
        Wp[arch.relay_pairs[:, 0], arch.relay_pairs[:, 1]] = arch.l_relay
        for a, b in links:
            Wp[a, b] = Wp[b, a] = arch.d2d
        self.arch = arch
        self.Wp = Wp
        self.edges = np.array([(a, b) for a, b in links]
                              + [(b, a) for a, b in links],
                              np.int64).reshape(-1, 2)
        self.area = float(area)

    def full_W(self) -> np.ndarray:
        """The [V, V] weight matrix with the virtual source and sink nodes."""
        a = self.arch
        W = np.full((a.V, a.V), INF)
        W[:a.Vp, :a.Vp] = self.Wp
        np.fill_diagonal(W, 0.0)
        W[a.Vp + a.owner, np.arange(a.Vp)] = 0.0
        W[np.arange(a.Vp), a.Vp + a.N + a.owner] = 0.0
        return W


def graph_of(arch: Arch, sol) -> Graph:
    """The scored graph of one placement as the search represents it."""
    pos, area = homog_geometry(arch, *sol)
    return Graph(arch, homog_links(arch, pos), area)


# ---------------------------------------------------------------------------
# Floyd-Warshall with shortest-path counts.
# ---------------------------------------------------------------------------

def fw_counts(W, dtype=np.float64):
    """All-pairs distances and shortest-path counts of W [..., V, V]
    (0 on the diagonal, >= INF_CUT where no edge).  Pivot k improves
    (i, j) through k, or adds N[i, k] * N[k, j] paths where it ties;
    row and column k take no part, so that no path counts itself."""
    D = np.array(W, dtype=dtype)
    V = D.shape[-1]
    eye = np.eye(V, dtype=bool)
    N = ((D < INF_CUT) & ~eye).astype(dtype) + eye.astype(dtype)
    cand = np.empty_like(D)
    ncand = np.empty_like(D)
    lt = np.empty(D.shape, bool)
    eq = np.empty(D.shape, bool)
    reach = np.empty(D.shape, bool)
    for k in range(V):
        np.add(D[..., :, k:k + 1], D[..., k:k + 1, :], out=cand)
        np.multiply(N[..., :, k:k + 1], N[..., k:k + 1, :], out=ncand)
        np.less(cand, D, out=lt)
        np.equal(cand, D, out=eq)
        np.less(cand, INF_CUT, out=reach)
        eq &= reach
        for m in (lt, eq):
            m[..., k, :] = False
            m[..., :, k] = False
        np.copyto(D, cand, where=lt)
        np.copyto(N, ncand, where=lt)
        np.add(N, ncand, out=N, where=eq)
    return D, N


def _group_min(D, N, groups, axis):
    """Distances and counts from (axis=-2) or to (axis=-1) each chiplet's
    virtual node: the least over its PHYs, counts of the PHYs that attain
    it summed; unreachable entries count 0."""
    g = np.where(groups >= 0, groups, 0)
    pad = groups < 0
    if axis == -2:                              # [..., C, P, V]
        Dg, Ng, pad, red = D[..., g, :], N[..., g, :], pad[:, :, None], -2
    else:                                       # [..., V, C, P]
        Dg, Ng, red = D[..., :, g], N[..., :, g], -1
    Dg = np.where(pad, INF, Dg)
    dmin = Dg.min(axis=red)
    best = np.expand_dims(dmin, red)
    hit = (Dg == best) & (best < INF_CUT) & ~pad
    return dmin, np.where(hit, Ng, 0.0).sum(axis=red)


class Paths:
    """Distances and counts of one placement's graph, with the virtual
    nodes folded in: PHY to PHY (D, N), source of chiplet c to PHY
    (Ds, Ns [N, Vp]), PHY to sink of chiplet d (Dt, Nt [Vp, N]) and
    source to sink (Dst, Nst [N, N])."""

    def __init__(self, arch: Arch, D, N):
        self.D, self.N = D, N
        self.Ds, self.Ns = _group_min(D, N, arch.groups, -2)
        self.Dt, self.Nt = _group_min(D, N, arch.groups, -1)
        self.Dst, self.Nst = _group_min(self.Dt, self.Nt, arch.groups, -2)


def pool_size() -> int:
    """Threads of the reference's pool: the CPUs this process may run on."""
    try:
        return len(os.sched_getaffinity(0))
    except AttributeError:       # no affinity call on this platform
        return os.cpu_count() or 1


def paths_of(graphs, dtype=np.float64, pool=None, threads: int = 1):
    """Paths of each graph.  With a ``pool`` of ``threads`` threads the
    graphs are split into as many interleaved stacks, one a thread (numpy
    releases the interpreter lock in its loops)."""
    arch = graphs[0].arch

    def run(gs):
        D, N = fw_counts(np.stack([g.Wp for g in gs]), dtype)
        D = D.astype(np.float64)
        N = N.astype(np.float64)
        return [Paths(arch, D[i], N[i]) for i in range(len(gs))]

    if pool is None or threads <= 1 or len(graphs) == 1:
        return run(graphs)
    parts = [graphs[i::threads] for i in range(threads) if graphs[i::threads]]
    done = list(pool.map(run, parts))
    out = [None] * len(graphs)
    for i, part in enumerate(done):
        out[i::len(parts)] = part
    return out


# ---------------------------------------------------------------------------
# Metrics.
# ---------------------------------------------------------------------------

EDGE_BLOCK = 64      # links whose loads one step of ``_link_load`` sums


def _on_path_use(P: Paths, g: Graph, srcs, dsts, edges):
    """[S, E, T] share of s -> t shortest-path traffic that crosses each
    directed link of ``edges`` (ECMP over all shortest paths)."""
    eu, ev = g.edges[edges, 0], g.edges[edges, 1]
    w = g.Wp[eu, ev]
    Dsd = P.Dst[np.ix_(srcs, dsts)]
    Dsu, Nsu = P.Ds[srcs][:, eu], P.Ns[srcs][:, eu]
    Dvd, Nvd = P.Dt[ev][:, dsts], P.Nt[ev][:, dsts]
    Nsd = np.maximum(P.Nst[np.ix_(srcs, dsts)], 1.0)
    on = ((np.abs(Dsu[:, :, None] + w[None, :, None] + Dvd[None]
                  - Dsd[:, None, :]) < 0.5)
          & (Dsd[:, None, :] < INF_CUT))
    return np.where(on, Nsu[:, :, None] * Nvd[None] / Nsd[:, None, :], 0.0)


def _link_load(P: Paths, g: Graph, srcs, dsts, dem):
    """[E] load of each directed link under demand ``dem`` [S, T].  Each
    link's load is its own sum over (s, t), so the links are taken
    ``EDGE_BLOCK`` at a time: the [S, E, T] shares of a 256-chiplet arch
    would hold 0.3 GB an array."""
    return np.concatenate([
        np.einsum("st,set->e", dem,
                  _on_path_use(P, g, srcs, dsts, slice(a, a + EDGE_BLOCK)))
        for a in range(0, len(g.edges), EDGE_BLOCK)] or [np.zeros(0)])


def metrics(arch: Arch, g: Graph, P: Paths) -> dict:
    """§IV-A proxies per traffic class (mean shortest-path latency over
    chiplet pairs; saturation throughput 1 / max link load of uniform
    traffic routed over all shortest paths), the area, and the
    connectivity of every source to every sink."""
    out = {"area": g.area,
           "connected_paths": bool((P.Dst < INF_CUT).all())}
    for t, (ks, kd) in ENDPOINTS.items():
        srcs = np.flatnonzero(arch.kinds == ks)
        dsts = np.flatnonzero(arch.kinds == kd)
        ok = np.ones((len(srcs), len(dsts)), bool)
        if ks == kd:
            ok &= srcs[:, None] != dsts[None, :]
        Dsd = P.Dst[np.ix_(srcs, dsts)]
        out[f"lat_{t}"] = float(np.where(ok, Dsd, 0.0).sum()
                                / max(ok.sum(), 1))
        dem = ok / np.maximum(ok.sum(axis=1, keepdims=True), 1)
        load = _link_load(P, g, srcs, dsts, dem)
        top = load.max() if len(load) else 0.0
        out[f"thr_{t}"] = float(min(1.0, 1.0 / top)) if top > 0 else 1.0
    return out


def metrics_of(arch: Arch, graphs, dtype=np.float64,
               threads: int | None = None) -> list[dict]:
    """``metrics`` of each graph, in order.  Floyd-Warshall and then the
    metrics run on one pool of ``threads`` threads (``pool_size()`` by
    default); each placement's numbers are computed alone, so they are
    bit for bit what ``threads=1`` gives."""
    if not graphs:
        return []
    threads = pool_size() if threads is None else threads
    if threads <= 1:
        return [metrics(arch, g, p)
                for g, p in zip(graphs, paths_of(graphs, dtype))]
    with concurrent.futures.ThreadPoolExecutor(threads) as pool:
        paths = paths_of(graphs, dtype, pool, threads)
        return list(pool.map(lambda gp: metrics(arch, *gp),
                             zip(graphs, paths)))


def normalizers(arch: Arch, draws, n: int, policy: str,
                dtype=np.float64, threads: int | None = None
                ) -> dict | None:
    """§IV-B cost normalizers of a normalizer draw: ``draws`` are the
    placements drawn one after another, and the first ``n`` of them that
    are connected are scored and reduced by the objective's policy (the
    mean, the median, or ones).  None where fewer than ``n`` connect."""
    keys = ([f"lat_{t}" for t in TRAFFIC] + [f"inv_thr_{t}" for t in TRAFFIC]
            + ["area"])
    if policy == "ones":
        return dict.fromkeys(keys, 1.0)
    kept = []
    for sol in draws:
        pos, area = homog_geometry(arch, *sol)
        links = homog_links(arch, pos)
        if connected(arch, links):
            kept.append(Graph(arch, links, area))
            if len(kept) == n:
                break
    if len(kept) < n:
        return None
    ms = metrics_of(arch, kept, dtype, threads)
    stat = {"mean": np.mean, "median": np.median}[policy]
    out = {}
    for t in TRAFFIC:
        out[f"lat_{t}"] = float(stat([m[f"lat_{t}"] for m in ms]))
        out[f"inv_thr_{t}"] = float(stat([1.0 / max(m[f"thr_{t}"], EPS)
                                          for m in ms]))
    out["area"] = float(stat([m["area"] for m in ms]))
    return out


# ---------------------------------------------------------------------------
# Objective.
# ---------------------------------------------------------------------------

# The metrics each objective term reads.
TERM_METRICS = {
    "lat": tuple(f"lat_{t}" for t in TRAFFIC),
    "inv-thr": tuple(f"thr_{t}" for t in TRAFFIC),
    "area": ("area",),
}


def cost(m: dict, objective: dict, norms: dict) -> float:
    """§IV-B cost: a weighted sum of terms.  ``objective`` is the traffic
    file's objective (per-class latency and throughput weights, area
    weight, terms with weights); ``norms`` holds the normalizers
    (lat_<t>, inv_thr_<t>, area)."""
    wl, wt = objective["mix_lat"], objective["mix_thr"]
    terms = {
        "lat": lambda: sum(wl[i] * m[f"lat_{t}"] / max(norms[f"lat_{t}"], EPS)
                           for i, t in enumerate(TRAFFIC)),
        "inv-thr": lambda: sum(
            wt[i] * (1.0 / max(m[f"thr_{t}"], EPS))
            / max(norms[f"inv_thr_{t}"], EPS) for i, t in enumerate(TRAFFIC)),
        "area": lambda: (objective["w_area"] * m["area"]
                         / max(norms["area"], EPS)),
    }
    return float(sum(term["weight"] * terms[term["name"]]()
                     for term in objective["terms"]))


def rel_gap(a: float, b: float) -> float:
    """|a - b| relative to |b| (b the reference); inf where a is not
    finite."""
    if not math.isfinite(a):
        return math.inf
    return abs(a - b) / max(abs(b), 1e-12)
