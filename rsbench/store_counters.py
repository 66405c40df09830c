"""Per-layer readings of the served object path from the program's own
counters, as they changed over the traced window:

* ``rs_store_range_reads_total{path}``: object reads that missed the
  daemon's cache and went to the stripes, by the lane that served them
  (``fast``: the k native windows; ``degraded``: a scan, the survivor
  inverse and a rebuild; ``failed``);
* ``rs_obj_cache_hits_total`` / ``rs_obj_cache_misses_total``: the
  daemon's object-cache verdicts;
* ``rs_scrub_scan_seconds_total``: seconds of archive health scans, in
  the store cell the full CRC scan each degraded read starts with;
* ``rs_serve_queue_wait_seconds{op}``: each request's wait from
  admission to dispatch (its exact sum and count).

A program without the counter gives nothing to read, and so does a
trace in which the device ran nothing (the guard of
``program_counters.py``), or a window with nothing to divide by.
"""

from __future__ import annotations

RANGE_READS = "rs_store_range_reads_total"
CACHE_HITS = "rs_obj_cache_hits_total"
CACHE_MISSES = "rs_obj_cache_misses_total"
SCAN = "rs_scrub_scan_seconds_total"
QUEUE_WAIT = "rs_serve_queue_wait_seconds"


def _values(run: dict, counter: str) -> dict | None:
    """The counter's series over the window; None where there is nothing
    to read."""
    tr = run.get("trace")
    series = (run.get("counters") or {}).get(counter)
    if not tr or tr["window_s"] <= 0 or tr["busy_s"] <= 0 or series is None:
        return None
    return series.get("values", {})


def _total(run: dict, counter: str) -> float | None:
    vals = _values(run, counter)
    return None if vals is None else float(sum(vals.values()))


def _reads(run: dict, path: str) -> float | None:
    vals = _values(run, RANGE_READS)
    return None if vals is None else float(vals.get(f'{{path="{path}"}}', 0))


def _share(part: float | None, rest: float | None) -> float | None:
    if part is None or rest is None or part + rest <= 0:
        return None
    return 100.0 * part / (part + rest)


def degraded_share(run: dict) -> float | None:
    """Degraded reads over fast and degraded reads, in %."""
    return _share(_reads(run, "degraded"), _reads(run, "fast"))


def cache_hit_share(run: dict) -> float | None:
    """Object-cache hits over hits and misses, in %."""
    return _share(_total(run, CACHE_HITS), _total(run, CACHE_MISSES))


def scan_ms_per_degraded_read(run: dict) -> float | None:
    """Scan milliseconds over the window's degraded reads."""
    scan = _total(run, SCAN)
    reads = _reads(run, "degraded")
    if scan is None or not reads:
        return None
    return 1e3 * scan / reads


def queue_wait_ms(run: dict, op: str) -> float | None:
    """Mean admission-to-dispatch wait of ``op`` requests, in ms."""
    vals = _values(run, QUEUE_WAIT)
    got = (vals or {}).get(f'{{op="{op}"}}')
    if not got or got["count"] <= 0:
        return None
    return 1e3 * got["sum"] / got["count"]
