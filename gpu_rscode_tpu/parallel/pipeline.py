"""Host<->HBM streaming pipeline — the CUDA-stream analog.

The reference overlaps PCIe copies with kernels by splitting each device's
slice ``streamNum`` ways and issuing H2D -> kernel -> D2H depth-first per
stream (encode.cu:165-218).  On TPU the runtime is already asynchronous:
``device_put`` and jitted dispatch return futures, and compute overlaps
host work automatically.  What still needs managing is *backpressure* — how
many segments may be in flight before the host blocks on results — and
that is exactly what :class:`AsyncWindow` provides (its ``depth`` is the
``-s`` flag).  For mesh runs the sharded placement happens in
``codec._matmul`` via ``put_sharded``, inside the same window, so the H2D
of segment i+1 overlaps compute of segment i.
"""

from __future__ import annotations

import queue
import threading
import time
from collections.abc import Callable, Iterable
from contextlib import contextmanager
from typing import Any, Generic, TypeVar

from ..obs import metrics as _metrics, tracing as _tracing

T = TypeVar("T")

# Process-lifetime staging-ring occupancy watermark (see
# DeviceStagingRing._report_occupancy).  Held in a list so tests can
# reset it without rebinding the module attribute they imported.
_RING_PEAK = [0]


@contextmanager
def blocked(wait: str):
    """One blocking wait of a pipeline thread: a ``wait_<wait>`` span on
    the waiting thread and its seconds in
    ``rs_pipeline_wait_seconds_total{wait}``.  Callers enter it only after
    a non-blocking try failed, so a wait that does not block costs
    nothing."""
    t0 = time.perf_counter()
    try:
        with _tracing.span(
            "wait_" + wait, lane="wait:" + threading.current_thread().name
        ):
            yield
    finally:
        _metrics.counter(
            "rs_pipeline_wait_seconds_total",
            "seconds a pipeline thread blocked: on a full writer queue "
            "(writer) or an empty segment queue (segment)",
        ).labels(wait=wait).inc(time.perf_counter() - t0)


class AsyncWindow(Generic[T]):
    """Bounded window of in-flight async results.

    ``depth`` = the number of segments allowed in flight: after any ``push``
    returns, at most ``depth`` futures are pending (``-s 2`` overlaps one
    segment's host work with the previous segment's compute).  ``push(tag,
    future)`` enqueues; beyond ``depth`` pending the oldest is drained
    through ``consume(tag, future)`` (which should block on the future —
    e.g. ``np.asarray`` — and commit the result).  ``flush`` drains the rest
    in order.

    With an ``executor`` (:class:`.io_executor.DrainExecutor`) the drain
    becomes *write-behind*: instead of running ``consume`` on the dispatch
    thread, the oldest pending (tag, future) is handed to the executor's
    bounded writer queue and ``push`` returns immediately — the fifth
    pipeline stage (write ∥ dispatch).  Backpressure then comes from the
    executor's ``depth``; in-flight device futures are bounded by
    ``window depth + executor depth + workers``.  A worker exception
    re-raises at the next ``push``/``flush`` (via ``executor.submit``);
    note ``flush`` only *hands off* the remaining pending futures — the
    executor's own ``flush`` (its context exit) is the write barrier.
    """

    def __init__(
        self,
        depth: int,
        consume: Callable[[Any, T], None],
        executor=None,
    ):
        self.depth = max(1, depth)
        self.consume = consume
        self.executor = executor
        self._pending: list[tuple[Any, T]] = []

    def _report_depth(self) -> None:
        n = len(self._pending)
        _metrics.gauge(
            "rs_pipeline_inflight",
            "async segments in flight (AsyncWindow pending futures)",
        ).set(n)
        _tracing.counter("pipeline_inflight", inflight=n)

    def _drain_oldest(self) -> None:
        tag, future = self._pending.pop(0)
        if self.executor is not None:
            # Device futures know their size; the byte count feeds the
            # write_drain span args and per-lane accounting (docs/IO.md).
            nbytes = getattr(future, "nbytes", 0) or 0
            self.executor.submit(
                lambda: self.consume(tag, future), nbytes=int(nbytes)
            )
        else:
            self.consume(tag, future)

    def push(self, tag: Any, future: T) -> None:
        self._pending.append((tag, future))
        self._report_depth()
        while len(self._pending) > self.depth:
            self._drain_oldest()
            self._report_depth()

    def flush(self) -> None:
        while self._pending:
            self._drain_oldest()
            self._report_depth()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is None:
            self.flush()
        else:
            # Abort: drop the pending futures unconsumed — but leave the
            # inflight gauge/counter track reset to zero, not frozen at its
            # last nonzero sample (a stale gauge would read as a live
            # pipeline long after the window died).
            dropped = len(self._pending)
            self._pending.clear()
            if dropped:
                self._report_depth()
                _tracing.instant(
                    "pipeline_aborted", lane="dispatch", dropped=dropped
                )
        return False


class DeviceStagingRing:
    """Double-buffered device staging: the H2D transfer of segment i+1 is
    issued while segment i computes and segment i-1 drains.

    Completes the 3-stage H2D -> compute -> D2H pipeline of the reference's
    stream loop (encode.cu:165-218) on the device side: the
    :class:`SegmentPrefetcher` overlaps *read IO* with everything, the
    :class:`AsyncWindow` overlaps *D2H + write IO* with compute — but the
    H2D placement itself used to happen inside the dispatch call, so the
    transfer of segment i+1 only started after segment i's dispatch
    returned.  This ring pulls ``depth`` segments ahead of the consumer and
    calls ``stage(tag, host_seg)`` on each (typically
    ``codec.stage_segment`` — an async ``jax.device_put`` of the
    bucket-padded segment), so the DMA is in flight before the consumer
    asks for the data.

    ``source`` yields ``(tag, host_segment)`` (a SegmentPrefetcher is one);
    iteration yields ``(tag, staged)`` in source order.  ``stage`` runs on
    the consumer thread (device_put returns immediately; nothing here
    blocks), and its exceptions propagate at the consuming ``__next__``.
    ``depth=2`` is the double-buffer: one segment staged ahead of the one
    being handed out.
    """

    def __init__(self, source, stage, depth: int = 2):
        self._source = iter(source)
        self._stage = stage
        self._depth = max(1, depth)
        self._staged: list = []
        self._exhausted = False

    def _report_occupancy(self) -> None:
        n = len(self._staged)
        _metrics.gauge(
            "rs_staging_ring_occupancy",
            "segments staged on-device ahead of the consumer",
        ).set(n)
        # High-watermark across EVERY ring of the process (one ring per
        # file op — a per-ring peak would let a later small op overwrite
        # the fleet answer "did any ring ever fill" downward).  The
        # module global is gated on enabled() so a climb during a
        # disabled run cannot suppress the gauge of a later enabled one.
        if _metrics.enabled() and n > _RING_PEAK[0]:
            _RING_PEAK[0] = n
            _metrics.gauge(
                "rs_staging_ring_occupancy_peak",
                "process-wide high watermark of staged segments ahead "
                "of the consumer",
            ).set(n)
        _tracing.counter("staging_ring_occupancy", staged=n)

    def _fill(self) -> None:
        while not self._exhausted and len(self._staged) < self._depth:
            try:
                tag, host = next(self._source)
            except StopIteration:
                self._exhausted = True
                return
            self._staged.append((tag, self._stage(tag, host)))

    def __iter__(self):
        return self

    def __next__(self):
        self._fill()
        if not self._staged:
            raise StopIteration
        tag, staged = self._staged.pop(0)
        self._fill()  # issue the next H2D before handing this segment out
        # ONE sample per handed-out segment, after pop+refill: steady state
        # reads depth, the tail drain (source exhausted, ring emptying)
        # shows the occupancy actually falling to zero.
        self._report_occupancy()
        return tag, staged


class SegmentPrefetcher:
    """Stage segments on a worker thread into a bounded queue.

    Completes the three-way overlap of the reference's stream loop
    (encode.cu:165-218: H2D || kernel || D2H): JAX's async dispatch already
    overlaps device compute with the drain's D2H+write, but in a
    single-threaded loop the *read* of segment i+depth only starts after the
    drain of segment i returns — read IO and write IO serialize.  With the
    pread gather on its own thread, steady-state encode wall approaches
    max(read, compute, write) instead of read + max(compute, write).

    ``segments``: (off, cols) tags, staged in order.  ``produce(off, cols)``
    runs on the worker thread (it must be thread-safe against the consumer's
    work — the pread/memmap gathers are: distinct fds/offsets).  ``depth``
    bounds staged-but-unconsumed segments, so host memory holds at most
    ``depth + 1`` staged segments beyond the AsyncWindow's in-flight ones.

    Iterating yields ``((off, cols), staged)`` in order.  A ``produce``
    exception re-raises at the consuming ``__next__``.  Exiting the context
    early (consumer exception) cancels the worker promptly: the worker
    checks a stop flag before each stage and uses timeouts around queue
    puts.
    """

    _STOP = object()

    def __init__(
        self,
        segments: Iterable[tuple[int, int]],
        produce: Callable[[int, int], Any],
        depth: int = 2,
    ):
        self._segments = list(segments)
        self._produce = produce
        self._q: queue.Queue = queue.Queue(maxsize=max(1, depth))
        self._stop = threading.Event()
        self._started = False
        self._thread = threading.Thread(
            target=self._run, name="rs-segment-prefetch", daemon=True
        )

    def _run(self) -> None:
        try:
            for off, cols in self._segments:
                if self._stop.is_set():
                    return
                item = ((off, cols), self._produce(off, cols))
                while not self._stop.is_set():
                    try:
                        self._q.put(item, timeout=0.1)
                        break
                    except queue.Full:
                        continue
                else:
                    return
            self._put_forever((self._STOP, None))
        except BaseException as e:  # noqa: BLE001 — relayed to the consumer
            self._put_forever((self._STOP, e))

    def _put_forever(self, item) -> None:
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.1)
                return
            except queue.Full:
                continue

    def __iter__(self):
        return self

    def __next__(self):
        if not self._started:
            # Outside the context manager the worker thread never started,
            # so q.get() below would block forever — fail loudly instead.
            raise RuntimeError(
                "SegmentPrefetcher must be used as a context manager "
                "(worker thread not started; iterate inside 'with')"
            )
        try:
            tag, item = self._q.get_nowait()
        except queue.Empty:
            with blocked("segment"):
                tag, item = self._q.get()
        if tag is self._STOP:
            self._stop.set()  # idempotent; lets join() return fast
            if item is not None:
                raise item
            raise StopIteration
        return tag, item

    def __enter__(self):
        self._thread.start()
        self._started = True
        return self

    def __exit__(self, exc_type, exc, tb):
        self._stop.set()
        # Unblock a worker waiting on put() by draining whatever is queued.
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=30)
        return False
