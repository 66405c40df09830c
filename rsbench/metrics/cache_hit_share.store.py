"""Share (%) of object GETs the daemon's object cache served."""

from rsbench.store_counters import cache_hit_share


def read(run):
    return cache_hit_share(run)
