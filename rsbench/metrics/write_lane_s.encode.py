"""Seconds per encode that the write-behind lane was busy: the k native
chunk copies, the parity D2H waits and writes, and their CRC32s."""

from rsbench.program_counters import write_lane_s


def read(run):
    return write_lane_s(run)
