"""Phase timing / observability.

Capability parity with the reference's cudaEvent step timing + aggregate
"total computation" vs "total communication" report (encode.cu:111-163,
227-232, 254-277; cpu-rs.c:523-532) — reimagined for an async runtime:
device work is timed by bracketing ``block_until_ready`` fences around
phases, host IO by wall clock.  The report keeps the reference's
computation/communication split so numbers are comparable.

Phases integrate with the unified observability layer (``..obs``): when a
tracing session is active (``RS_TRACE``), every timed phase also lands as
a span on the ``phase`` lane of the exported Perfetto trace — the timer
stays the human-readable report, the trace the per-event timeline.  Under
the profiler sink (``RS_PROFILE``) each phase is an ``rs.<phase>`` span
of the ``jax.profiler`` capture, beside the device's ops.
"""

from __future__ import annotations

import threading
import time
from collections import defaultdict
from contextlib import contextmanager

from ..obs import tracing as _tracing


class PhaseTimer:
    """Accumulates named phase durations.

    Communication phases are identified by an explicit parenthesized tag
    suffix — ``"stage segment (io)"`` — checked against :data:`COMM_TAGS`
    exactly, never by substring (a phase merely *containing* "io", like
    "dispatch ratio" or "prioritize", must not silently count as
    communication).
    """

    # Comm-tag vocabulary: a phase named "... (<tag>)" with <tag> in this
    # set counts as communication; everything else is computation.
    COMM_TAGS = frozenset({"io", "transfer", "stage"})

    def __init__(self, enabled: bool = True):
        self.enabled = enabled
        self.acc: dict[str, float] = defaultdict(float)
        self.counts: dict[str, int] = defaultdict(int)
        self.best: dict[str, float] = {}  # per-phase minimum duration
        # Phases land from three threads at once (dispatch, prefetch
        # worker, write-behind drain); += on the dicts is read-modify-write.
        self._rec_lock = threading.Lock()
        self._t0 = time.perf_counter()

    @classmethod
    def is_comm(cls, name: str) -> bool:
        """Exact comm-tag classification (see class docstring)."""
        if not name.endswith(")") or "(" not in name:
            return False
        return name[name.rfind("(") + 1 : -1] in cls.COMM_TAGS

    def _record(self, name: str, dt: float) -> None:
        with self._rec_lock:
            self.acc[name] += dt
            self.counts[name] += 1
            prev = self.best.get(name)
            if prev is None or dt < prev:
                self.best[name] = dt

    @contextmanager
    def phase(self, name: str):
        if not self.enabled:
            # A disabled timer never accumulates, but an active RS_TRACE
            # session or profiler sink still gets the phase span — the
            # file APIs default to a disabled timer, and the trace must
            # not go blind there.
            if not _tracing.enabled():
                yield
                return
            with _tracing.span(
                name, lane="phase:" + threading.current_thread().name
            ):
                yield
            return
        t = time.perf_counter()
        try:
            # Lane per thread: the prefetch worker's IO phases overlap the
            # consumer's compute phases; same-lane X events must nest.
            with _tracing.span(
                name, lane="phase:" + threading.current_thread().name
            ):
                yield
        finally:
            self._record(name, time.perf_counter() - t)

    def add(self, name: str, seconds: float) -> None:
        """Record an externally measured duration (same accounting as a
        :meth:`phase` block).  Honours ``enabled`` — a disabled timer must
        never mutate its accumulators."""
        if not self.enabled:
            return
        self._record(name, seconds)

    @property
    def total(self) -> float:
        return time.perf_counter() - self._t0

    def phase_report(self) -> dict[str, float]:
        """Accumulated seconds per phase, snapshotted under the recording
        lock — the per-phase decomposition the run ledger (obs/runlog.py)
        embeds in each record."""
        with self._rec_lock:
            return {name: round(v, 6) for name, v in self.acc.items()}

    def summary(self, data_bytes: int | None = None) -> str:
        comm = sum(v for k, v in self.acc.items() if self.is_comm(k))
        comp = sum(v for k, v in self.acc.items() if not self.is_comm(k))
        lines = [
            f"  {name}: {1e3 * v:.3f} ms  (x{self.counts[name]})"
            for name, v in sorted(self.acc.items())
        ]
        lines.append(f"  total computation: {1e3 * comp:.3f} ms")
        lines.append(f"  total communication: {1e3 * comm:.3f} ms")
        lines.append(f"  total wall: {1e3 * self.total:.3f} ms")
        if data_bytes is not None and self.total > 0:
            lines.append(f"  throughput: {data_bytes / self.total / 1e9:.3f} GB/s")
        return "\n".join(lines)
