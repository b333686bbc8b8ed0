"""FW kernels: relaxations per second of device time in the VMEM-resident
Floyd-Warshall kernel.

Trace: rows x V^3 relaxations of each ``fw_counts_vmem`` event, read from
its output shape (padding rows and padded V included: the work the kernel
does), over the kernels' device time.  Each relaxation is
``reduce.FW_OPS_PER_RELAXATION`` elementwise operations on the vector
unit; no peak of that unit has a source yet, so this is a rate, not a
roofline share."""

from bench import reduce


def read(run):
    tr = run["trace"]
    if tr is None:
        return None
    secs = tr.op_s(reduce.FW_KERNELS.pattern)
    relax = tr.fw_relaxations()
    if secs <= 0 or not relax:
        return None
    return relax / secs
