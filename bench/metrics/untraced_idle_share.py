"""Device: share of the traced window in which the device is idle and no
span of the program is open, in %.

Trace: the first device's idle time in the window less the part of it
under any ``placeit.*`` span, over the window.  None where the program
opens no span."""

from bench import idle


def read(run):
    tr = run["trace"]
    under = idle.idle_ms(tr)
    if under is None or tr.window_s <= 0:
        return None
    total = 1e-6 * sum(b - a for a, b in idle.idle_intervals(tr))
    return 100.0 * (total - under) / (1e3 * tr.window_s)
