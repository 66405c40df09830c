"""Seconds per repair in the archive health scan that precedes it: the
size and full CRC32 check of every surviving chunk."""

from rsbench.program_counters import scan_s


def read(run):
    return scan_s(run)
