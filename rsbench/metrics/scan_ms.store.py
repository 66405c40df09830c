"""Milliseconds of archive scan per degraded read: the full CRC32 check
of every surviving chunk of the stripe that each degraded GET starts
with (``api._scan_chunks``)."""

from rsbench.store_counters import scan_ms_per_degraded_read


def read(run):
    return scan_ms_per_degraded_read(run)
