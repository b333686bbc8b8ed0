"""Scorer: device-idle ms per scoring call while the host copies the
scorer's outputs back.

Trace: the window's device-idle time under the program's
``placeit.score.fetch`` spans (``Evaluator.score_batch``, after the wait
for the device) over the scoring calls of the window."""

from bench import idle


def read(run):
    return idle.idle_ms_per(run, "placeit.score.fetch", "score_calls")
