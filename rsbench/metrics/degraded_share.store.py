"""Share (%) of the reads that missed the object cache and were served
by the degraded read path (a scan, the survivor inverse and a rebuild)
rather than the fast one."""

from rsbench.store_counters import degraded_share


def read(run):
    return degraded_share(run)
