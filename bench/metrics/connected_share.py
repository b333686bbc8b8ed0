"""Produce and graph build: connected placements over placements scored.
Counters: the children the window's generations kept (all connected)
over the ``Evaluator.n_generated`` delta of the window (every placement
produced and scored, resample rounds included), in %."""


def read(run):
    if not run["n_generated"]:
        return None
    return 100.0 * run["n_evaluated"] / run["n_generated"]
