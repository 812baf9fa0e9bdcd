"""Share of the traced window, in %, in which the device ran no operation
(averaged over the cell's chips)."""

from chipbench import tracereduce


def read(run):
    if run.trace is None or not run.trace.device_ops:
        return None
    return 100.0 * (1.0 - tracereduce.busy_ns(run.trace) / run.trace.window_ns)
