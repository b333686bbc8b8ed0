"""Scorer: device time of the compiled scorer per placement scored.

Trace: the device time of the XLA module events of the scorer program,
found by its jit name (``score`` in ``core/proxies.make_scorer``; on more
than one chip the population-sharded program of
``sharding/population.shard_scorer``, jitted from a lambda), per device,
over every placement the window produced and scored (the
``n_generated`` delta), in ms."""

JIT_NAME = r"^jit_(score|_lambda)\b"


def read(run):
    tr = run["trace"]
    if tr is None or not run["n_generated"]:
        return None
    secs = tr.module_s(JIT_NAME)
    if secs <= 0:
        return None
    return 1e3 * secs / run["n_generated"]
