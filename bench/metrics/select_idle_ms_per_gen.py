"""Search driver: device-idle ms per generation in the GA's selection.

Trace: the window's device-idle time under the program's
``placeit.select`` spans (ranking, the best update, tournament picks,
parent gathers, the elite concat) over the window's generations."""

from bench import idle


def read(run):
    return idle.idle_ms_per(run, "placeit.select", "generations")
