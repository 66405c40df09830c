"""Share (%) of the encode cell's traced window in which the dispatching
thread was blocked on the full write-behind queue (the native chunk
copies and parity drains ahead of it)."""

from rsbench.program_counters import wait_share


def read(run):
    return wait_share(run, "writer")
