"""Write-behind I/O executor — the missing fifth pipeline stage.

The reference's whole performance story is a 3-way stream overlap — H2D ∥
kernel ∥ D2H per CUDA stream (encode.cu:165-218).  PR 1 rebuilt two thirds
of it for the TPU host runtime (SegmentPrefetcher for reads,
DeviceStagingRing for H2D), but the drain stage stayed serialized: every
``AsyncWindow.consume`` ran ``np.asarray`` (device wait + D2H) and the
``pwrite``/``fp.write`` commit on the dispatch thread, so write I/O stole
wall time from dispatch.  This module completes the 5-stage overlap

    read ∥ H2D ∥ compute ∥ D2H ∥ write

with three pieces:

* :class:`DrainExecutor` — a bounded writer-worker queue the window hands
  its (tag, future) drains to.  ``depth`` bounds queued-but-unwritten
  drains (backpressure: a slow disk eventually blocks dispatch instead of
  growing an unbounded backlog of live device buffers); worker exceptions
  re-raise at the next ``submit``/``flush``; ``ordered=True`` commits
  strictly in submit order (the streaming shared-``fp`` decode path and
  every incremental-CRC drain need it) while ``ordered=False`` lets
  ``workers`` threads race pwrite-at-offset drains out of order.
  ``workers=0`` degrades to the old synchronous inline drain
  (``RS_IO_WRITERS=0``).
* :class:`FleetPipeline` — deferred per-archive commit for multi-file
  operations: each archive's finalize (close + rename promote + checksum
  rewrite) rides the shared writer lane *behind* that archive's writes, so
  archive j+1's reads/dispatches overlap archive j's write drain instead
  of waiting for it.  Registered cleanups run on abort, keeping the
  per-archive atomicity contract.
* :func:`run_rows` — a small shared reader pool that fans the per-chunk
  preads of a segment gather across threads (distinct fds/offsets are
  independently seekable, so this is safe); used by the ``native``
  fallbacks when no C++ toolchain (whose pool, rs_native.cpp ``run_rows``,
  this mirrors) is available.

Knobs: ``RS_IO_WRITERS`` (writer threads; 0 = synchronous drain; default
1), ``RS_IO_WRITE_DEPTH`` (queued drains before dispatch blocks; default
2 x writers), ``RS_IO_READERS`` (fallback read pool; default
min(4, cores)).  Observability (docs/OBSERVABILITY.md): the
``rs_io_*`` counters/gauges and per-lane ``write_drain`` spans recorded
here make the overlap visible in Perfetto; a ``submit`` that blocks on a
full queue records a ``wait_writer`` span and
``rs_pipeline_wait_seconds_total{wait="writer"}``.

Import cost: stdlib only (no jax, no numpy) — same contract as ``obs``.
"""

from __future__ import annotations

import os
import queue
import threading
import time
from collections.abc import Callable

from ..obs import metrics as _metrics, tracing as _tracing
from ..resilience import faults as _faults, retry as _retry
from .pipeline import blocked


def writer_count(default: int = 1) -> int:
    """``RS_IO_WRITERS``: write-behind worker threads (0 = drain inline on
    the dispatch thread, the pre-write-behind behavior)."""
    try:
        return max(0, int(os.environ.get("RS_IO_WRITERS", default)))
    except ValueError:
        return default


def writer_depth(workers: int) -> int:
    """``RS_IO_WRITE_DEPTH``: queued-but-unwritten drains allowed before
    ``submit`` blocks.  Each queued drain pins a live device future (its
    D2H has not run), so this bounds device memory as well as host backlog.
    """
    fallback = 2 * max(1, workers)
    try:
        return max(1, int(os.environ.get("RS_IO_WRITE_DEPTH", fallback)))
    except ValueError:
        return fallback


def reader_count() -> int:
    """``RS_IO_READERS``: threads for the fallback per-chunk pread fan-out
    (1 = serial).  The native C++ pool (RS_NATIVE_IO_THREADS) is separate —
    it applies when the toolchain-built library handles the gather."""
    try:
        return max(1, int(os.environ.get("RS_IO_READERS", 0) or
                          min(4, os.cpu_count() or 1)))
    except ValueError:
        return min(4, os.cpu_count() or 1)


class DrainExecutor:
    """Bounded background executor for the pipeline's drain stage.

    ``submit(fn, nbytes=...)`` enqueues one drain callable (typically a
    closed-over ``consume(tag, future)``) and returns immediately unless
    ``depth`` drains are already queued — the backpressure that keeps a
    slow writer from accumulating unbounded live device buffers.  Worker
    exceptions are latched and re-raised at the next ``submit`` or
    ``flush`` (the dispatch loop's next touch point); after an error the
    workers discard the remaining queue so ``flush`` cannot deadlock.

    ``ordered=True`` commits strictly in submit order on one worker
    (required by shared-``fp`` streaming writes and incremental CRC
    accumulation); ``ordered=False`` races ``workers`` threads over
    offset-addressed ``pwrite`` drains.  ``workers=0`` runs every submit
    synchronously on the caller — the ``RS_IO_WRITERS=0`` escape hatch and
    the degenerate case the A/B bench compares against.

    Context manager: a clean exit flushes (barrier + error re-raise); an
    exceptional exit cancels queued drains (never half-commits a stream
    that already failed) but still joins the workers, so caller ``finally``
    blocks may safely close the files the drains write to.
    """

    _STOP = object()

    def __init__(
        self,
        workers: int | None = None,
        depth: int | None = None,
        ordered: bool = False,
        name: str = "rs-io-writer",
    ):
        if workers is None:
            workers = writer_count()
        self.ordered = ordered
        self.workers = min(workers, 1) if ordered else workers
        self.depth = depth if depth is not None else writer_depth(self.workers)
        self._q: queue.Queue | None = (
            queue.Queue(maxsize=self.depth) if self.workers else None
        )
        self._error: BaseException | None = None
        self._lock = threading.Lock()
        self._cancelled = False
        self._threads = [
            threading.Thread(
                target=self._run, name=f"{name}-{i}", daemon=True
            )
            for i in range(self.workers)
        ]
        self._started = False

    # -- worker side ---------------------------------------------------------

    def _run(self) -> None:
        lane = threading.current_thread().name.replace("rs-io-", "")
        while True:
            item = self._q.get()
            try:
                if item is self._STOP:
                    return
                fn, nbytes = item
                if self._error is None and not self._cancelled:
                    self._run_task(fn, nbytes, lane)
            except BaseException as e:  # noqa: BLE001 — relayed to submit/flush
                with self._lock:
                    if self._error is None:
                        self._error = e
            finally:
                self._q.task_done()
                self._report_depth()

    def _run_task(self, fn: Callable[[], None], nbytes: int, lane: str) -> None:
        t0 = time.perf_counter()
        # Resilience write boundary (docs/RESILIENCE.md): the fault
        # plane's write hook fires per attempt (injected ioerror/torn/
        # delay), and transient failures — injected or real — retry the
        # whole drain under the default policy.  Drains are idempotent by
        # construction: offset-addressed pwrites and incremental-CRC
        # commits deferred until after the writes landed.  The lane's
        # attempted-byte accounting (torn faults) counts a task's bytes
        # once, not per retry.
        first = True

        def attempt() -> None:
            nonlocal first
            # Flag cleared BEFORE the hook: if the hook itself raises on
            # the first attempt, the retry must not re-count the bytes.
            nb = nbytes if first else 0
            first = False
            _faults.on_write(lane, nb)
            fn()

        with _tracing.span("write_drain", lane=lane, nbytes=nbytes):
            _retry.default_policy().call(attempt, op="write_drain")
        dt = time.perf_counter() - t0
        _metrics.counter(
            "rs_io_write_seconds_total",
            "wall seconds spent in drain (D2H wait + write) tasks",
        ).labels(lane=lane).inc(dt)
        _metrics.quantile(
            "rs_io_drain_wall_seconds",
            "writer-lane drain task wall seconds (streaming quantiles)",
        ).labels(lane=lane).observe(dt)

    def _report_depth(self) -> None:
        if self._q is not None:
            n = self._q.qsize()
            _metrics.gauge(
                "rs_io_writer_queue_depth",
                "drain tasks queued behind the write-behind workers",
            ).set(n)
            _tracing.counter("io_writer_queue_depth", queued=n)

    # -- caller side ---------------------------------------------------------

    def _check_error(self) -> None:
        with self._lock:
            err = self._error
        if err is not None:
            # A failed stream is dead: cancel the queue BEFORE re-raising,
            # so no drain still queued behind the failure (in a fleet, an
            # archive's finalize/promote) can run after the caller saw the
            # error.  The latched error keeps re-raising at every later
            # submit/flush.
            self._cancelled = True
            raise err

    def submit(self, fn: Callable[[], None], *, nbytes: int = 0) -> None:
        """Enqueue one drain; blocks when ``depth`` are already queued.
        Re-raises a pending worker exception instead of queueing more work
        behind a failed stream."""
        if self.workers == 0:
            self._run_task(fn, nbytes, "drain-sync")
            return
        if not self._started:
            raise RuntimeError(
                "DrainExecutor must be entered as a context manager before "
                "submit() (worker threads not started)"
            )
        self._check_error()
        try:
            self._q.put_nowait((fn, nbytes))
        except queue.Full:
            with blocked("writer"):
                self._q.put((fn, nbytes))
        self._report_depth()

    def submit_pwrite(self, fileno: int, data: bytes, offset: int) -> None:
        """Queue one offset-addressed ``os.pwrite`` drain — the
        random-access patch lane of the update/append subsystem
        (update/engine.py): an ``ordered=True`` executor commits patches
        strictly in submit order (the per-chunk ascending-offset
        invariant its incremental CRC accounting depends on), each drain
        crosses the fault plane's write boundary like every other lane,
        and a retried drain re-pwrites the same bytes at the same offset
        (idempotent by construction)."""
        nbytes = len(data)

        def task() -> None:
            done = os.pwrite(fileno, data, offset)
            if done != nbytes:
                raise OSError(
                    f"short pwrite ({done} of {nbytes} bytes at {offset})"
                )
            _metrics.counter(
                "rs_io_write_bytes_total",
                "bytes write by the staging-I/O layer",
            ).labels(call="patch_pwrite").inc(nbytes)

        self.submit(task, nbytes=nbytes)

    def flush(self) -> None:
        """Barrier: block until every submitted drain ran (or was discarded
        after an error), then re-raise the first worker exception."""
        if self._q is not None:
            self._q.join()
        self._check_error()

    def cancel(self) -> None:
        """Discard queued-but-unstarted drains (the in-progress one
        finishes).  Used on the exceptional exit path — a stream that
        already failed must not keep committing segments."""
        self._cancelled = True

    def _shutdown(self) -> None:
        if not self._started:
            return
        for _ in self._threads:
            self._q.put(self._STOP)
        for t in self._threads:
            t.join()
        self._started = False

    def __enter__(self) -> "DrainExecutor":
        for t in self._threads:
            t.start()
        self._started = bool(self._threads)
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is None:
            try:
                self.flush()
            finally:
                self._shutdown()
        else:
            self.cancel()
            self._shutdown()
        return False


class FleetPipeline:
    """Deferred per-archive commit over a shared :class:`DrainExecutor`.

    Multi-file operations (``repair_fleet``, ``encode_fleet``,
    ``decode_fleet``) stream archives back to back through one writer
    lane.  Each archive's commit — close output files, promote ``.rs_tmp``
    renames, rewrite checksum lines — must run only after *that archive's*
    writes landed, but the dispatch loop must not wait for it; ``defer``
    therefore submits the finalize onto the (ordered) writer lane, where
    FIFO guarantees it runs behind the archive's last write while the main
    thread is already reading/dispatching the next archive.

    Lifecycle per archive: ``register(cleanup)`` *before* streaming starts
    (so an abort at any point can close fds and unlink the archive's temp
    files), then ``commit(key, finalize)`` after the archive's last drain
    was submitted.  A successful finalize unregisters its cleanup; on any
    failure :meth:`abort` runs every still-registered cleanup, keeping the
    same nothing-half-committed contract as a failed single-archive
    operation.  Call ``abort`` only after the executor has fully shut down
    (workers joined), so no in-flight drain races a cleanup's
    closes/unlinks.
    """

    def __init__(self, executor: DrainExecutor):
        if executor.workers and not executor.ordered:
            raise ValueError(
                "FleetPipeline needs an ordered executor: an out-of-order "
                "lane could promote an archive before its writes landed"
            )
        self.executor = executor
        self._cleanups: dict[int, Callable[[], None]] = {}
        self._n = 0
        self._lock = threading.Lock()

    def register(self, cleanup: Callable[[], None]) -> int:
        """Register an archive's failure cleanup; returns the key for
        :meth:`commit`."""
        with self._lock:
            key = self._n
            self._n += 1
            self._cleanups[key] = cleanup
        return key

    def commit(self, key: int, finalize: Callable[[], None]) -> None:
        """Queue the archive's commit behind its writes on the ordered
        writer lane.  Only a *successful* finalize releases the registered
        cleanup — a failed one leaves it for :meth:`abort`."""

        def run() -> None:
            finalize()
            with self._lock:
                self._cleanups.pop(key, None)

        self.executor.submit(run)

    def abort(self) -> None:
        with self._lock:
            pending = list(self._cleanups.values())
            self._cleanups.clear()
        for cb in pending:
            try:
                cb()
            except OSError:
                pass  # best-effort temp cleanup must not bury the cause


# -- shared reader pool ------------------------------------------------------

_POOLS: dict[int, "object"] = {}
_POOL_LOCK = threading.Lock()


def _pool(n: int):
    from concurrent.futures import ThreadPoolExecutor

    with _POOL_LOCK:
        pool = _POOLS.get(n)
        if pool is None:
            pool = _POOLS[n] = ThreadPoolExecutor(
                max_workers=n, thread_name_prefix="rs-io-reader"
            )
        return pool


def run_rows(n: int, fn: Callable[[int], None]) -> None:
    """Run ``fn(i)`` for each row ``i`` in ``range(n)``, fanned across the
    shared reader pool (``RS_IO_READERS`` wide; serial when 1 or when the
    row count doesn't warrant threads).  Blocks until every row completed;
    the first row exception re-raises here.

    Deliberately NOT a fault/retry boundary of its own: every caller is a
    segment gather that api.py already wraps in the fault plane's
    per-survivor read hook plus the default retry policy (op=
    encode/decode/repair_stage).  A second layer here would double the
    effective injected-fault rate on toolchain-less builds only (this
    pool is the native gather's fallback), raise unattributable faults
    (no chunk index -> the degraded survivor swap can't engage) and burn
    (attempts+1)^2 nested retries — so the read lane's resilience
    boundary stays one level up, uniform across builds."""
    workers = min(reader_count(), n)
    if workers <= 1:
        for i in range(n):
            fn(i)
        return
    pool = _pool(workers)
    futures = [pool.submit(fn, i) for i in range(n)]
    err = None
    for f in futures:
        try:
            f.result()
        except BaseException as e:  # noqa: BLE001 — re-raised after the join
            if err is None:
                err = e
    if err is not None:
        raise err
