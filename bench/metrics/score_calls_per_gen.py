"""Search driver: scoring calls (host round trips) per generation.
Counter: ``Evaluator.n_score_calls`` over the window, divided by the
generations the window ran.  One call per generation means no resample
round was needed."""


def read(run):
    if not run["generations"]:
        return None
    return run["score_calls"] / run["generations"]
