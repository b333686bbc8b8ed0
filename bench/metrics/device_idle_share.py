"""Device: share of the traced window in which no operation ran, in %.

Trace: 1 - (union of the device's operation intervals) / (the window's
host span)."""


def read(run):
    tr = run["trace"]
    if tr is None or tr.window_s <= 0 or not tr.devices:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
