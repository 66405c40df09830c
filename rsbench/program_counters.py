"""Per-layer readings of the file path from the program's own counters,
as they changed over the traced window:

* ``rs_pipeline_wait_seconds_total{wait}``: seconds the dispatching
  thread blocked, ``writer`` on the full write-behind queue, ``segment``
  on the next staged segment.  In the file cells no other thread waits
  on either, so the counter is that thread's blocked time.
* ``rs_io_write_seconds_total{lane}``: seconds the write-behind lane
  spent in drain tasks (D2H wait, writes, CRC).  The file cells' lane is
  one ordered worker, so the sum is the time that lane was busy.
* ``rs_scrub_scan_seconds_total``: seconds of archive health scans, the
  size and CRC check of every chunk before a repair.

A program without the counter gives nothing to read, and so does a trace
in which the device ran nothing: the contract refuses such a run on the
chip, and the CPU rehearsal, whose trace has no device op, reports only
the device's idle share.
"""

from __future__ import annotations

WAITS = "rs_pipeline_wait_seconds_total"
WRITE_LANE = "rs_io_write_seconds_total"
SCAN = "rs_scrub_scan_seconds_total"


def _seconds(run: dict, counter: str, prefix: str = "") -> float | None:
    """The counter's change over the window, summed over the series whose
    labels start with ``prefix``; None where there is nothing to read."""
    tr = run.get("trace")
    series = (run.get("counters") or {}).get(counter)
    if not tr or tr["window_s"] <= 0 or tr["busy_s"] <= 0 or series is None:
        return None
    return float(sum(v for labels, v in series.get("values", {}).items()
                     if labels.startswith(prefix)))


def wait_share(run: dict, wait: str) -> float | None:
    """Blocked seconds of kind ``wait`` over the window, in %."""
    s = _seconds(run, WAITS, f'{{wait="{wait}"}}')
    return None if s is None else 100.0 * s / run["trace"]["window_s"]


def per_op(run: dict, counter: str, prefix: str = "") -> float | None:
    """The counter's seconds over the window's operations."""
    s = _seconds(run, counter, prefix)
    ops = sum(n for _, n in run.get("gemms") or [])
    return None if s is None or ops <= 0 else s / ops


def write_lane_s(run: dict) -> float | None:
    return per_op(run, WRITE_LANE, "{lane=")


def scan_s(run: dict) -> float | None:
    return per_op(run, SCAN)
