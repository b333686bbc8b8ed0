"""FW kernels: device time of the Floyd-Warshall kernels per placement.

Trace: the device time of the ``fw_counts_vmem`` / ``fw_tiled_*`` kernel
events over every placement the window produced and scored (the
``n_generated`` delta), in us.  The scorer pads a batch to a whole number
of chunks, so the kernels also run on the padding rows."""

from bench import reduce


def read(run):
    tr = run["trace"]
    if tr is None or not run["n_generated"]:
        return None
    secs = tr.op_s(reduce.FW_KERNELS.pattern)
    if secs <= 0:
        return None
    return 1e6 * secs / run["n_generated"]
