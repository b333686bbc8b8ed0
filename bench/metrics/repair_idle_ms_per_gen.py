"""Search driver: device-idle ms per generation in the slot repairs of
the resample loop.

Trace: the window's device-idle time under the program's
``placeit.repair`` spans (``DevicePipeline._until_connected_steps``
writing resampled rows into their slots) over the window's
generations."""

from bench import idle


def read(run):
    return idle.idle_ms_per(run, "placeit.repair", "generations")
