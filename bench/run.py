#!/usr/bin/env python3
"""PlaceIT benchmark: one cell of ``BENCHMARK.json`` on the chip.

    python3 bench/run.py --workload homog64.ga.synth --seed 7 --seconds 30 \\
        --trace 0

A cell names a configuration (``bench/configs/<config>.json``: the arch,
its chiplets and the search's hyper-parameters) and a traffic mix
(``bench/traffic/<mix>.json``: the objective and the search's warm
generations).  A traffic file may also carry ``"search": {"optimizer":
<name>, <params>}``: its keys override the configuration's
``optimizer`` and parameter keys, and the parameters are built as the
``params_cls`` the optimizer is registered with (``BRParams(batch=...)``,
``GAParams(population=..., ...)``), from the keys that class has.

Set-up builds the placement representation and the Evaluator as
``run_sweep`` does, the host normalizer draw included, warms every shape
the window uses, and turns on JAX's persistent compilation cache in
``.jax_cache/`` of the checkout.  Then one search starts: the registered
optimizer's step generator under the paper's wall budget of 3600 s,
driven as ``run_sweep`` drives it.  Its first generations are set-up
too; the window opens at the start of the next generation and closes at
the first generation start after ``--seconds``, where the optimizer's
own wall-budget check would stop it.  Only optimizers with a generation
boundary the window can see run (``BOUNDARIES``): ``ga-batched``, whose
generation starts as it samples its children, and ``br-batched``, whose
generation is one batch of random placements.  Any other is refused
with ``SetupError`` before set-up.

A cell on one chip scores each request with ``optimize._score_request``
on the default device.  A cell on more chips takes the route of a
sharded ``run_sweep`` group: each request goes through
``optimize.score_stacked`` with the population-sharded scorer
(``sharding.population.shard_scorer``) over a mesh of the first
``chips`` devices; warm-up builds that program at every resample size,
and the result reports the fullest device's peak memory, with each
device's beside it.

With ``--trace 0`` the result's metrics are the cell's end-to-end
metrics; with ``--trace 1`` the window runs under the profiler and the
metrics are the cell's per-layer metrics, each read by
``bench/metrics/<name>.py``.  Either way the outputs of the window are
checked against the float64 reference (``bench/reference.py``) once it
has closed, on a thread pool as wide as the CPUs the process may use.
The last line of stdout is one JSON object; the numbers compared, each
with its limit, are the last lines of stderr.  A run that finds no TPU,
fewer chips than the cell asks for, or an optimizer the window cannot
bound, exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up starts with the process

import argparse  # noqa: E402
import collections  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

from bench import reduce, reference  # noqa: E402

CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")

# Limits of the numbers that decide `correct` (see PERF.md for the
# readings each was set from).  The graph and the connectivity flags are
# compared exactly; normalizer, metric and cost gaps are relative to the
# float64 reference.
LIMITS = {
    "graph_mismatch": 0,
    "connected_mismatch": 0,
    "norm_gap": 1e-3,
    "metric_gap": 1e-3,
    "cost_gap": 1e-3,
}
SEARCH_BUDGET_S = 3600.0  # the paper's wall budget of one search
SAMPLE_ROWS = 32        # placements of the window's last rounds checked,
SAMPLE_CONNECTED = 24   # at most this many of them flagged connected
KEEP_ROUNDS = 12        # scoring rounds the recorder keeps,
KEEP_BYTES = 2 << 30    # and no more of their weights W than this (device)

# The pipeline sampler that one generation of each optimizer enters once,
# at its start: the window's boundary.  ga-batched also draws its first
# population through sample_random_steps, so that is not its boundary.
BOUNDARIES = {"ga-batched": "sample_children_steps",
              "br-batched": "sample_random_steps"}


class SetupError(Exception):
    pass


def search_of(config: dict, traffic: dict):
    """The cell's optimizer and its typed parameters.  The traffic file's
    ``search`` keys override the configuration's ``optimizer`` and
    parameter keys; the parameters are the optimizer's registered
    ``params_cls``, built from the keys it has (its defaults for the
    rest).  An optimizer the window has no boundary for is refused."""
    import dataclasses
    from repro.core import api  # noqa: F401  (registers the optimizers)
    from repro.core.registries import OPTIMIZERS

    keys = {**config, **traffic.get("search", {})}
    name = keys["optimizer"]
    if name not in BOUNDARIES:
        raise SetupError(f"optimizer {name!r} has no window boundary; the "
                         f"benchmark runs {sorted(BOUNDARIES)}")
    cls = OPTIMIZERS.get(name).params_cls
    return name, cls(**{f.name: keys[f.name]
                        for f in dataclasses.fields(cls) if f.name in keys})


# ---------------------------------------------------------------------------
# Manifest.
# ---------------------------------------------------------------------------

def load_cell(name: str, root: str = ROOT) -> dict:
    """The cell ``name`` of BENCHMARK.json with its configuration file,
    traffic file and metric specs resolved."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SetupError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    cfg = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    with open(os.path.join(root, cfg["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "bench", "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)

    def mine(m):
        return name in m.get("workloads", [name])

    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": [m for m in manifest["end_to_end"] if mine(m)],
            "per_layer": [m for m in manifest["per_layer"] if mine(m)]}


def metric_reader(name: str, root: str = ROOT):
    """``read(run)`` of ``bench/metrics/<name>.py``."""
    path = os.path.join(root, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def device_kind_peaks(kind: str, root: str = ROOT) -> dict:
    with open(os.path.join(root, "bench", "peaks.json")) as f:
        peaks = json.load(f)
    if kind not in peaks["devices"]:
        raise SetupError(f"device kind {kind!r} is not in bench/peaks.json")
    return peaks["devices"][kind]


# ---------------------------------------------------------------------------
# What the window produced.
# ---------------------------------------------------------------------------

class Recorder:
    """Keeps the last rounds of the search: each stage call's placements
    (and the stage's own connectivity flags, where it gives them) and the
    scoring call that follows it.  It keeps ``KEEP_ROUNDS`` rounds, fewer
    where their weights W, which stay on the device, would pass
    ``KEEP_BYTES``.  Both wrappers open a profiler span, so a trace shows
    the produce and score stages on the host."""

    def __init__(self):
        self.stages = collections.deque()
        self.scores = collections.deque()

    def clear(self):
        self.stages.clear()
        self.scores.clear()

    def stage(self, fn):
        import jax

        def wrapped(*args):
            with jax.profiler.TraceAnnotation("bench.produce"):
                t, r, batch = fn(*args)
            self.stages.append((t, r, batch.get("connected")))
            while len(self.stages) > KEEP_ROUNDS:
                self.stages.popleft()
            return t, r, batch
        return wrapped

    def score(self, fn):
        import jax

        def wrapped(batch, *args, **kw):
            with jax.profiler.TraceAnnotation("bench.score"):
                out = fn(batch, *args, **kw)
            self.scores.append((batch["W"], out))
            while len(self.scores) > KEEP_ROUNDS or (
                    len(self.scores) > 1 and
                    sum(W.nbytes for W, _ in self.scores) > KEEP_BYTES):
                self.scores.popleft()
            return out
        return wrapped

    def rounds(self):
        """(t, r, stage_connected, W, outputs) of the last rounds."""
        n = min(len(self.stages), len(self.scores))
        st, sc = list(self.stages)[-n:], list(self.scores)[-n:]
        return [s + c for s, c in zip(st, sc)]


class WindowClosed(Exception):
    """Raised into the search where the window closes."""


class Window:
    """The measured slice of one long search.  ``generations(fn)`` wraps
    the pipeline sampler that the optimizer enters once at the start of
    each generation (``BOUNDARIES``).  The window opens as generation
    ``warm + 1`` starts and closes at the first generation start after
    ``seconds``, where the optimizer's own wall-budget check would end the
    search: it then raises ``WindowClosed`` out of the search.  With
    ``trace`` the profiler runs for exactly the window."""

    def __init__(self, ev, rec: Recorder, warm: int, seconds: float,
                 trace: bool):
        self.ev, self.rec = ev, rec
        self.warm, self.seconds, self.trace = warm, seconds, trace
        self.started = 0         # generations the search has started
        self.t0 = self.t1 = None
        self.kept = 0            # children of the window's generations
        self.marks = []          # (time, kept) at each window generation
        self.span = None

    def generations(self, fn):
        def wrapped(*args, **kw):
            self._boundary()
            out = yield from fn(*args, **kw)
            if self.t0 is not None:
                self.kept += len(out[3])
            return out
        return wrapped

    def _boundary(self):
        import jax
        now = time.perf_counter()
        self.started += 1
        if self.t0 is None:
            if self.started > self.warm:
                self.rec.clear()
                if self.trace:
                    shutil.rmtree(TRACE_DIR, ignore_errors=True)
                    opts = jax.profiler.ProfileOptions()
                    opts.python_tracer_level = 0
                    jax.profiler.start_trace(TRACE_DIR,
                                             profiler_options=opts)
                self.span = jax.profiler.TraceAnnotation(reduce.WINDOW)
                self.span.__enter__()
                self.counts0 = (self.ev.n_generated, self.ev.n_score_calls)
                self.t0 = time.perf_counter()
                self.marks.append((self.t0, 0))
            return
        self.marks.append((now, self.kept))
        if now - self.t0 > self.seconds:
            self.t1 = now
            self.counts1 = (self.ev.n_generated, self.ev.n_score_calls)
            self.span.__exit__(None, None, None)
            if self.trace:
                jax.profiler.stop_trace()
            raise WindowClosed

    def thirds(self) -> list[float]:
        """Placements kept per second in each third of the window."""
        out = []
        for k in range(3):
            a = self.t0 + k * (self.t1 - self.t0) / 3
            b = self.t0 + (k + 1) * (self.t1 - self.t0) / 3
            ka = max((n for t, n in self.marks if t <= a), default=0)
            kb = max((n for t, n in self.marks if t <= b), default=0)
            out.append((kb - ka) / (b - a))
        return out


class CompileLog:
    """Times of JAX's backend-compile events (a compile or a persistent
    cache read: every program built)."""

    def __init__(self):
        self.times = []

    def __call__(self, event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.times.append(time.perf_counter())

    def between(self, a: float, b: float) -> int:
        return sum(1 for t in self.times if a <= t <= b)


# ---------------------------------------------------------------------------
# Set-up.
# ---------------------------------------------------------------------------

def build(config: dict, traffic: dict):
    """Representation and Evaluator, with its host normalizer draw, built
    as ``run_sweep`` builds them for this config from the config's search
    seed, and the search's parameters (``search_of``).  Every placement
    the draw takes is recorded, so that the reference can work out the
    normalizers from the same data."""
    from repro.core import api
    from repro.core.chiplets import resolve_arch
    from repro.core.objective import Objective, TermSpec, TrafficMix

    ref_arch = reference.Arch(config)
    arch = resolve_arch(config["arch"], config["chiplet_config"])
    if list(arch.kinds()) != ref_arch.kinds.tolist() or \
            sum(c.n_phys() for c in arch.chiplets) != ref_arch.Vp:
        raise SetupError(f"{config['arch']}: the program's arch differs "
                         f"from {config['name']}.json")
    rep = api.make_rep(arch, config["arch"], config["mutation_mode"])
    o = traffic["objective"]
    objective = Objective(
        mix=TrafficMix(lat=tuple(o["mix_lat"]), thr=tuple(o["mix_thr"])),
        w_area=o["w_area"], normalizer=o["normalizer"],
        terms=tuple(TermSpec(t["name"], t["weight"]) for t in o["terms"]))
    draws = []
    random = rep.random

    def recorded(rng):
        sol = random(rng)
        draws.append(tuple(np.asarray(x).copy() for x in sol))
        return sol

    rep.random = recorded
    try:
        ev = api.make_evaluator(
            rep, arch, rng=np.random.default_rng(config["search_seed"]),
            norm_samples=config["norm_samples"], chunk=config["chunk"],
            backend=config["backend"], objective=objective)
    finally:
        del rep.random
    _, params = search_of(config, traffic)
    return ref_arch, ev, params, draws


def resample_sizes(n: int) -> list[int]:
    """Batch sizes a mask-and-resample round over ``n`` slots can have:
    the first round's n, then the next power of two of the invalid count,
    at least min(8, n) and at most n."""
    return sorted({n} | {min(max(1 << (k - 1).bit_length(), min(8, n)), n)
                         for k in range(1, n + 1)})


def warm_up(ev, optimizer: str, params, config: dict, score) -> None:
    """Build every program the resample rounds run, on a random stream of
    its own: the produce stages and the scorer (``score``, the route the
    search's requests take) at every resample batch size, and the slot
    repairs at every count.  ga-batched: ``_gen`` at the population and
    ``_child`` at the children; br-batched: ``_gen`` at the batch, and the
    read of the best row.  The search's own warm generations build the
    rest before the window opens."""
    import jax
    import jax.numpy as jnp
    from repro.core import optimize

    pipe = ev.pipeline()
    rng = np.random.default_rng([config["search_seed"], 1])

    def key():
        return jax.random.PRNGKey(int(rng.integers(2 ** 31 - 1)))

    if optimizer == "ga-batched":
        P = params.population
        C = P - params.elitism
        t0, r0, _ = pipe._gen(key(), P)
        parents = [x[jnp.asarray(rng.integers(P, size=C))]
                   for x in (t0, r0, t0, r0)]
        stages = ((P, lambda s: pipe._gen(key(), s)),
                  (C, lambda s: pipe._child(
                      key(), *[x[jnp.asarray(np.arange(s) % C)]
                               for x in parents], params.p_mutation)))
    else:
        stages = ((params.batch, lambda s: pipe._gen(key(), s)),)
    for n, make in stages:
        full = make(n)
        for s in resample_sizes(n):
            t, r, batch = full if s == n else make(s)
            score(batch)
            for L in range(1, s + 1):
                idx = jnp.asarray(np.arange(L))
                for a, b in zip(full[:2], (t, r)):
                    a.at[idx].set(b[idx]).block_until_ready()
        if optimizer == "br-batched":
            optimize._sol_at(*full[:2], n - 1)


# ---------------------------------------------------------------------------
# The check of the window's outputs.
# ---------------------------------------------------------------------------

def normalizers(ev) -> dict:
    """The program's normalizers, under the reference's keys."""
    n = ev.norm
    out = {f"lat_{t}": float(n.lat[t]) for t in reference.TRAFFIC}
    out |= {f"inv_thr_{t}": float(n.inv_thr[t]) for t in reference.TRAFFIC}
    out["area"] = float(n.area)
    return out


def sample(rec: Recorder, seed: int) -> list[dict]:
    """A seeded sample of the placements the window's last rounds scored
    (as many as possible of them flagged connected, the rest not), each
    with what the program said of it: its weights W, connectivity flag,
    metrics and cost."""
    rounds = rec.rounds()
    flags = [np.asarray(out["connected"] if sconn is None else sconn, bool)
             for _, _, sconn, _, out in rounds]
    rows = {True: [], False: []}
    for i, f in enumerate(flags):
        for j in range(len(f)):
            rows[bool(f[j])].append((i, j))
    rng = np.random.default_rng([seed, 3])
    n_conn = min(SAMPLE_CONNECTED, len(rows[True]))
    pick = []
    for flag, n in ((True, n_conn),
                    (False, min(SAMPLE_ROWS - n_conn, len(rows[False])))):
        for k in sorted(rng.choice(len(rows[flag]), size=n, replace=False)):
            pick.append(rows[flag][k])
    items = []
    for i, j in pick:
        t, r, _, W, out = rounds[i]
        items.append({"sol": (np.asarray(t[j]), np.asarray(r[j])),
                      "W": np.asarray(W[j]),
                      "connected": bool(flags[i][j]),
                      "metrics": {key: float(np.asarray(v)[j])
                                  for key, v in out.items()}})
    return items


def reference_of(ref_arch, items, dtype=np.float64) -> list[dict]:
    """The reference's graph, metrics and connectivity of each item."""
    graphs = [reference.graph_of(ref_arch, it["sol"]) for it in items]
    out = []
    for g, m in zip(graphs, reference.metrics_of(ref_arch, graphs, dtype)):
        ok = m.pop("connected_paths")
        out.append({"graph": g, "metrics": m, "connected": ok})
    return out


def compare(items, refs, objective: dict, norms: dict, ref_norms):
    """The numbers that decide `correct`: graphs that differ from the
    reference's, connectivity flags that differ, the widest relative gap
    of a normalizer (``ref_norms`` is None where the reference found too
    few connected placements in the draw), of a metric the objective
    reads and of a cost on the placements the reference calls connected;
    the program's cost is held against the reference's cost under the
    reference's normalizers.  Also returns how many connected placements
    got no finite cost, and the metric whose gap is widest."""
    numbers = {"graph_mismatch": 0, "connected_mismatch": 0,
               "norm_gap": math.inf, "metric_gap": 0.0, "cost_gap": 0.0}
    if ref_norms is not None:
        numbers["norm_gap"] = max(reference.rel_gap(norms[k], v)
                                  for k, v in ref_norms.items())
    failed, widest = 0, None
    keys = [k for t in objective["terms"]
            for k in reference.TERM_METRICS[t["name"]]]
    for it, ref in zip(items, refs):
        if not np.array_equal(it["W"],
                              ref["graph"].full_W().astype(np.float32)):
            numbers["graph_mismatch"] += 1
        if it["connected"] != ref["connected"]:
            numbers["connected_mismatch"] += 1
        if not ref["connected"]:
            continue
        prog = it["metrics"]
        cost = prog.get("cost", math.nan)
        failed += not math.isfinite(cost)
        for key in keys:
            gap = reference.rel_gap(prog.get(key, math.nan),
                                    ref["metrics"][key])
            if widest is None or gap > numbers["metric_gap"]:
                numbers["metric_gap"], widest = gap, key
        ref_cost = (math.nan if ref_norms is None else
                    reference.cost(ref["metrics"], objective, ref_norms))
        numbers["cost_gap"] = max(numbers["cost_gap"],
                                  reference.rel_gap(cost, ref_cost))
    return numbers, failed, widest


# ---------------------------------------------------------------------------
# One run.
# ---------------------------------------------------------------------------

def scorer_route(ev, chips: int):
    """``score(request) -> (costs, metrics)`` for the search's requests.
    One chip: ``optimize._score_request`` on the default device.  More:
    the route ``run_sweep(shard=True)`` gives a stacked group, one
    request stacked alone and scored by the population-sharded scorer
    over the first ``chips`` devices, still through ``ev.score_batch``."""
    from repro.core import optimize
    if chips == 1:
        return lambda req: optimize._score_request(ev, req)
    import jax
    from repro.sharding.population import population_mesh, shard_scorer
    score_fn = shard_scorer(ev.scorer, population_mesh(jax.devices()[:chips]))

    def score(req):
        (out,), _ = optimize.score_stacked(
            [(optimize._request_parts(req), ev)], score_fn=score_fn)
        return out
    return score


def run_cell(spec: dict, seed: int, seconds: float, trace: bool, *,
             t_start: float = T_START) -> dict:
    """Set up, warm up, run the search through its warm generations and
    the window, and check what the window produced."""
    import jax
    from repro.core import api

    config, traffic = spec["config"], spec["traffic"]
    optimizer, _ = search_of(config, traffic)
    chips = spec["cell"]["chips"]
    compiles = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    try:
        t_build = time.perf_counter()
        ref_arch, ev, params, draws = build(config, traffic)
        t_warm = time.perf_counter()
        rec = Recorder()
        pipe = ev.pipeline()
        pipe._gen, pipe._child = rec.stage(pipe._gen), rec.stage(pipe._child)
        ev.score_batch = rec.score(ev.score_batch)
        score = scorer_route(ev, chips)
        warm_up(ev, optimizer, params, config, score)
        t_search = time.perf_counter()

        win = Window(ev, rec, traffic["warm_generations"], seconds, trace)
        boundary = BOUNDARIES[optimizer]
        setattr(pipe, boundary, win.generations(getattr(pipe, boundary)))
        steps = api.stackable_steps(optimizer)
        search = steps(ev, np.random.default_rng(api.algo_seed(
            config["search_seed"], 0, optimizer)),
            api.Budget(seconds=SEARCH_BUDGET_S), params)
        try:
            req = next(search)
            while True:
                req = search.send(score(req))
        except WindowClosed:
            search.close()
        except StopIteration:
            raise RuntimeError("the search ended before the window closed")
    finally:
        jax.monitoring.unregister_event_duration_listener(compiles)
    t0, t1 = win.t0, win.t1
    devs = jax.devices()[:chips]
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devs]
    run = {"window_s": t1 - t0, "setup_s": t0 - t_start,
           "build_s": t_warm - t_build, "warmup_s": t_search - t_warm,
           "warm_search_s": t0 - t_search,
           "setup_programs": compiles.between(t_start, t0),
           "n_evaluated": win.kept,
           "n_generated": win.counts1[0] - win.counts0[0],
           "score_calls": win.counts1[1] - win.counts0[1],
           "generations": len(win.marks) - 1,
           "thirds": win.thirds(),
           "draws": len(draws),
           "compiles_in_window": compiles.between(t0, t1),
           "trace": reduce.Reduction.from_dir(TRACE_DIR) if trace else None}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": chips, "memory_peak_bytes": max(peaks),
              "memory_peak_bytes_each": peaks}
    norms = normalizers(ev)
    t_check = time.perf_counter()
    items = sample(rec, seed)
    refs = reference_of(ref_arch, items)
    objective = traffic["objective"]
    ref_norms = reference.normalizers(ref_arch, draws, config["norm_samples"],
                                      objective["normalizer"])
    numbers, failed, widest = compare(items, refs, objective, norms,
                                      ref_norms)
    run["check_s"] = time.perf_counter() - t_check
    return {"run": run, "device": device, "numbers": numbers,
            "widest": widest, "checked": len(items), "failed": failed,
            "rec": rec, "norms": norms, "ref_norms": ref_norms,
            "arch": ref_arch, "draws": draws}


def result_line(spec: dict, out: dict, trace: bool) -> dict:
    """The result object: metrics of the run's kind, the device, and the
    numbers compared with their limits last."""
    run = out["run"]
    metrics = {}
    if trace:
        for m in spec["per_layer"]:
            v = metric_reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = {"placements_per_s": run["n_evaluated"] / run["window_s"],
               "setup_s": run["setup_s"]}
        for m in spec["end_to_end"]:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    numbers = out["numbers"]
    line = {"correct": (run["n_evaluated"] > 0 and out["failed"] == 0
                        and all(numbers[k] <= LIMITS[k] for k in LIMITS)),
            "attempted": out["checked"], "failed": out["failed"],
            "metrics": metrics, "device": dict(out["device"])}
    tr = run["trace"]
    if trace and tr is not None:
        line["device"].update(busy_s=tr.busy_s, window_s=tr.window_s)
        line["breakdown"] = {"device_ops": tr.top_ops(10),
                             "idle_gaps": tr.idle_gaps(10)}
    line["checks"] = {k: {"value": numbers[k], "limit": LIMITS[k]}
                      for k in LIMITS}
    return line


def enable_cache() -> None:
    """JAX's persistent compilation cache in the checkout, holding every
    program however fast it compiled, so that only a cell's first run in
    a checkout compiles.

    Eviction stays off whatever the environment asks: with a size limit
    every write reads each entry's access-time file, and on a TPU host
    one missing file failed every later write, so no run found its
    programs in the cache."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache(ROOT)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_max_size", -1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    try:
        spec = load_cell(args.workload)
        import jax
        devs = jax.devices()
        chips = spec["cell"]["chips"]
        if devs[0].platform != "tpu":
            raise SetupError(f"no TPU: JAX platform {devs[0].platform!r}")
        if len(devs) < chips:
            raise SetupError(f"the cell needs {chips} chips, JAX sees "
                             f"{len(devs)}")
        device_kind_peaks(devs[0].device_kind)
        enable_cache()
        search_of(spec["config"], spec["traffic"])
    except SetupError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    out = run_cell(spec, args.seed, args.seconds, bool(args.trace))
    line = result_line(spec, out, bool(args.trace))
    run = out["run"]
    print(f"window {run['window_s']:.3f} s: {run['generations']} generations, "
          f"{run['n_evaluated']} placements kept of {run['n_generated']} "
          f"scored in {run['score_calls']} calls, kept per second by thirds "
          f"{[round(x, 3) for x in run['thirds']]}; set-up "
          f"{run['setup_s']:.3f} s (build and normalizer draw of "
          f"{run['draws']} placements {run['build_s']:.3f} s, warm-up "
          f"{run['warmup_s']:.3f} s, warm generations "
          f"{run['warm_search_s']:.3f} s, {run['setup_programs']} programs "
          f"built); check {run['check_s']:.3f} s; widest metric gap: "
          f"{out['widest']}", file=sys.stderr)
    for k, v in line["checks"].items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
