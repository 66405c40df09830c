"""Share (%) of the repair cell's traced window in which the dispatching
thread was blocked waiting for the next staged segment of the survivors
(the pread gather on the prefetch thread)."""

from rsbench.program_counters import wait_share


def read(run):
    return wait_share(run, "segment")
