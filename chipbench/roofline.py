"""A kernel's share of its roofline, from the trace and the peaks table."""


def share(run, program: str, flops: float, nbytes: float):
    """100 x (least time of all calls) / (their measured device time), for
    the jitted program ``program``; None where it did not run."""
    found = run.module(program)
    if found is None:
        return None
    calls, seconds = found
    least = max(flops / run.peak("flops_per_s"),
                nbytes / run.peak("hbm_bytes_per_s"))
    return 100.0 * calls * least / seconds
