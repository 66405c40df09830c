"""Readings of the repair planner from the program's own counters, as
they changed over the traced window:

* ``rs_repair_plans_total{plan}``: fragments rebuilt, by plan: ``local``
  where the rebuild read fewer than k survivors, ``global`` where it read
  k;
* ``rs_repair_survivors_read_total``: survivor chunk files the streamed
  rebuilds read;
* ``rs_repair_chunks_rebuilt_total``: chunk files repairs regenerated.

A program without the counter gives nothing to read, and so does a trace
in which the device ran nothing (``program_counters``' rule).
"""

from __future__ import annotations


def counter_values(run: dict, counter: str) -> dict | None:
    """{labels: change over the window} of ``counter``, or None."""
    tr = run.get("trace")
    series = (run.get("counters") or {}).get(counter)
    if not tr or tr["window_s"] <= 0 or tr["busy_s"] <= 0 or series is None:
        return None
    return series.get("values", {})
