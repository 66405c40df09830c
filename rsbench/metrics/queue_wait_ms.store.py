"""Mean milliseconds an object GET waited in the daemon's admission
queue before a worker dispatched it."""

from rsbench.store_counters import queue_wait_ms


def read(run):
    return queue_wait_ms(run, "object_get")
