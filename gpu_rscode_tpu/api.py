"""File-level encode/decode — the L4 orchestration layer.

Capability parity with the reference's ``encode_file`` (encode.cu:300-473)
and ``decode_file`` (decode.cu:235-434), redesigned for a TPU host runtime:

* The file is striped into k contiguous ranges (``chunk_size =
  ceil(total/k)``, same layout as encode.cu:317,332-346) and processed in
  column *segments* so arbitrarily large files stream through bounded
  memory.  Segments are dispatched asynchronously: JAX's async dispatch
  overlaps the host striping/IO of segment i+1 with device compute of
  segment i — the TPU-native analog of the reference's CUDA-stream
  depth-first pipeline (encode.cu:165-218).  ``pipeline_depth`` caps how many
  segments may be in flight (the ``-s`` stream-count knob).
* Tail padding is explicit zeros (deterministic parity — fixes the
  reference's uninitialised-heap padding divergence, encode.cu:325-330).
* Natives are written straight from the source file; only parity rides the
  device (the reference writes natives from the original buffer too).
* Decode trusts the .METADATA matrix (decode.cu:272-282), inverts the
  survivor submatrix on host, and streams the recovery GEMM the same way.
"""

from __future__ import annotations

import functools
import inspect
import os
import threading
import time

import numpy as np

from collections import OrderedDict
from contextlib import contextmanager, nullcontext

from .codec import RSCodec
from .models.lrc import erasure_tolerance, local_plans
from .obs import attrib as _obs_attrib, health as _obs_health, \
    metrics as _obs_metrics, runlog as _obs_runlog, tracing as _obs_tracing
from .parallel.io_executor import DrainExecutor, FleetPipeline
from .parallel.pipeline import AsyncWindow, DeviceStagingRing, SegmentPrefetcher
from .resilience import faults as _faults, retry as _retry
from .utils.fileformat import (
    append_checksums,
    chunk_crc32,
    chunk_file_name,
    chunk_size_for,
    chunk_size_for_layout,
    crc32_of,
    metadata_file_name,
    parse_chunk_index,
    read_archive_meta,
    read_conf,
    rewrite_checksums,
    write_conf,
    write_metadata,
)
from .utils.timing import PhaseTimer


class UndecidedSubsetError(ValueError):
    """The decodable-subset search hit its candidate cap without finding an
    invertible k-subset.  Distinct from exhaustion: more combinations exist,
    so the archive is NOT proven unrecoverable (scan_file reports this as
    ``decodable: "unknown"`` rather than false)."""


class ChunkIntegrityError(ValueError):
    """A surviving chunk's bytes are unusable — CRC mismatch, truncated or
    vanished after the scan that selected it (the TOCTOU window), or
    unreadable after retries.

    ``bad_chunks`` maps chunk index -> file path, so callers can build a new
    conf from different survivors (the checksum extension turns silent
    corruption into a recoverable erasure); :func:`auto_decode_file` uses it
    to exclude the named chunks and reselect automatically.
    """

    def __init__(self, bad_chunks: dict[int, str],
                 reason: str = "chunk checksum mismatch (corrupt survivors)"):
        self.bad_chunks = dict(bad_chunks)
        names = ", ".join(f"{i}:{p}" for i, p in sorted(bad_chunks.items()))
        super().__init__(
            f"{reason}: {names}; "
            "pick different survivors in the conf file"
        )


# Default segment sizing: bound host+device working set to ~64 MiB of natives
# per in-flight segment (k rows x seg_cols bytes).
DEFAULT_SEGMENT_BYTES = 64 * 1024 * 1024


# -- deep-profiling hook (RS_PROFILE) ----------------------------------------
#
# jax.profiler capture used to be a CLI-only wrapper around encode/decode
# (cli.py's --profile-dir); lifting it here puts EVERY file-level entry
# point — scrub, repair, fleet, chaos recovery loops, library callers —
# under the same deep-profiling surface.  RS_PROFILE=<dir> (or the CLI
# flag, now an alias that latches profile_dir_override) wraps the
# OUTERMOST observed operation in jax.profiler.trace(dir); nested entry
# points (auto_decode -> decode, fleet -> repair) join the active capture
# instead of re-entering the profiler.

_PROFILE_LOCK = threading.Lock()
_PROFILE_ACTIVE = False
_PROFILE_DIR_OVERRIDE: str | None = None


def profile_dir_override(profile_dir: str | None) -> None:
    """Latch a capture directory for this process regardless of
    ``RS_PROFILE`` — the in-process equivalent of exporting the env var
    (the CLI's deprecated ``--profile-dir`` alias routes through this
    instead of wrapping the operation itself).  Pass None to clear."""
    global _PROFILE_DIR_OVERRIDE
    _PROFILE_DIR_OVERRIDE = profile_dir


@contextmanager
def _profile_session():
    """jax.profiler capture for one outermost file operation (no-op when
    RS_PROFILE is unset and no override is latched; nested operations
    record into the outer capture)."""
    profile_dir = _PROFILE_DIR_OVERRIDE or os.environ.get("RS_PROFILE")
    if not profile_dir:
        yield
        return
    global _PROFILE_ACTIVE
    with _PROFILE_LOCK:
        owner = not _PROFILE_ACTIVE
        if owner:
            _PROFILE_ACTIVE = True
    if not owner:
        yield
        return
    try:
        import jax

        # The program's spans go into the capture too (rs.<name>), on
        # the profiler's clock beside the device's ops.
        was = _obs_tracing.profiler_sink(True)
        try:
            with jax.profiler.trace(profile_dir):
                yield
        finally:
            _obs_tracing.profiler_sink(was)
    finally:
        with _PROFILE_LOCK:
            _PROFILE_ACTIVE = False


def _observed_file_op(op: str):
    """Wrap a file-level entry point with the unified observability surface
    (docs/OBSERVABILITY.md): every wrapped function accepts an extra
    keyword-only ``trace_path=`` argument that — like the ``RS_TRACE`` env
    var — activates a span-tracing session exported as Chrome-trace /
    Perfetto JSON on completion, records a top-level span, and counts the
    operation in ``rs_file_ops_total`` (RS_METRICS).  Sessions are
    reentrant, so nested entry points (auto_decode -> decode, fleet ->
    repair) record into ONE trace owned by the outermost call.

    With ``RS_RUNLOG`` set, every wrapped call — success OR failure —
    also appends one structured record to the persistent run ledger
    (obs/runlog.py): op, config {k,n,w,strategy}, input bytes, wall,
    the PhaseTimer phase decomposition and the outcome.  Nested entry
    points each get their own record (a fleet repair's per-archive
    zero-size fallthroughs are real operations too)."""

    def deco(fn):
        sig = inspect.signature(fn)

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            trace_path = kwargs.pop("trace_path", None)
            # Rearm the retry budget per file-level entry: it bounds the
            # retry storm of ONE operation; without this a long-lived
            # process would silently lose all transient-retry protection
            # once the cumulative budget drained (docs/RESILIENCE.md).
            _retry.reset_budget()
            t0 = time.perf_counter()
            # Entry snapshot of a caller-supplied timer: nested fleet ops
            # share one, and the record must carry THIS op's delta, not
            # the fleet's running totals.
            phases0 = (
                _obs_runlog.timer_phases(sig, args, kwargs)
                if _obs_runlog.enabled() else None
            )
            error: BaseException | None = None
            try:
                with _profile_session(), _obs_tracing.session(trace_path):
                    with _obs_tracing.span(op, lane="op"):
                        out = fn(*args, **kwargs)
            except BaseException as e:
                error = e
                raise
            finally:
                # Failure records matter MOST (the regression watch and
                # the error-rate trend both read them); recording itself
                # never raises into the operation.
                if _obs_runlog.enabled():
                    _obs_runlog.record_file_op(
                        op, sig, args, kwargs,
                        wall=time.perf_counter() - t0, error=error,
                        phases_before=phases0,
                    )
            _obs_metrics.counter(
                "rs_file_ops_total", "file-level operations completed"
            ).labels(op=op).inc()
            # Tail latency of the whole operation (p50/p99 next to the
            # mean the ledger already trends) — successes only; failures
            # are counted by outcome in the ledger, and mixing their
            # walls into the latency series would skew the percentiles.
            _obs_metrics.quantile(
                "rs_file_op_wall_seconds",
                "file-level operation wall seconds (streaming quantiles)",
            ).labels(op=op).observe(time.perf_counter() - t0)
            return out

        return wrapper

    return deco

# Fleet repair routes batched survivor inversions to the device on TPU
# backends per the measured k x batch grid
# (bench_captures/inverse_nopivot_tpu_20260801T001751Z.jsonl, real v5e):
# the device wins at (k=10, b=1024: 3.46x), (k=32, b>=256: 2.1-5.6x) and
# (k=64, b>=64: 1.10-1.25x — a thin but consistent margin across three
# batch sizes); it loses at every k=128 cell (0.54-0.90x) and at small
# batches for every k (a ~0.13-0.15 s flat dispatch floor, measured
# before PR 1 and not re-read on the chip since).  That capture
# also REFUTES the r4 hypothesis that the per-step pivot scan caused the
# k=128 loss: the scan-free no-pivot elimination times are identical to
# the pivoting ones on TPU (the lax.scan over k elimination steps itself
# is the cost), so depth stays host-routed.  CPU backends keep the
# ungated device dispatch (14-136x at every measured point,
# inverse_cpu_20260730T174508Z.jsonl).
def _device_invert_min_batch_tpu(k: int) -> int | None:
    """Group-size threshold for the batched device inverter on TPU.

    At the measured depths (k = 10/32/64/128) the value is the smallest
    batch where the device dispatch beat the per-archive host loop (None
    where the host won every cell); unmeasured intermediate depths take
    the STRICTER neighbouring threshold — e.g. k=20 requires 1024, not
    k=32's 256, because (k=10, b=256) measured a 0.81x LOSS."""
    if k > 64:
        return None
    if k == 64:
        return 64
    if k >= 32:
        return 256
    return 1024


def _segment_cols(chunk_size: int, native_num: int, segment_bytes: int) -> int:
    cols = max(1, segment_bytes // max(1, native_num))
    # Lane-align segments (TPU tiles are 128 wide) except when the chunk
    # itself is smaller.
    if cols < chunk_size:
        cols = max(128, cols - cols % 128)
    return min(cols, chunk_size)


def _staging_ring(prefetch, codec, seg_cols: int, sym: int, depth: int):
    """The H2D stage all three file loops (encode/decode/repair) share:
    bucket-pad each prefetched segment and issue its async device_put
    (``codec.stage_segment``), ``depth`` segments ahead of the consumer."""
    return DeviceStagingRing(
        prefetch,
        lambda tag, seg: codec.stage_segment(
            seg, cap=seg_cols // sym, sym=sym
        ),
        depth=depth,
    )


def warm_plan(
    native_num: int,
    parity_num: int,
    *,
    w: int = 8,
    generator: str = "vandermonde",
    strategy: str = "auto",
    segment_bytes: int = DEFAULT_SEGMENT_BYTES,
    file_bytes: int | None = None,
) -> dict:
    """Pre-compile the encode executable for one plan-cache shape bucket.

    The residency hook (docs/SERVE.md): a resident process — the serve
    daemon at startup (``rs serve --warm k,n``), a long-lived embedder —
    pays the AOT compile HERE instead of inside its first real request.
    Stages one zero segment of the bucket the workload will hit
    (``file_bytes`` sizes it like :func:`encode_file` would; default one
    full segment) and blocks until the dispatch lands in the shared plan
    cache, where every later :func:`encode_file`/:func:`encode_fleet`
    with the same ``(k, p, w, strategy)`` shape finds it warm.  Returns
    the resolved shape (strategy ``auto`` pinned to its backend choice).
    """
    if w not in (8, 16):
        raise ValueError(f"file-layer symbol width must be 8 or 16, got {w}")
    sym = w // 8
    codec = RSCodec(
        native_num, parity_num, w=w, generator=generator, strategy=strategy
    )
    chunk = (
        chunk_size_for(file_bytes, native_num, sym)
        if file_bytes else max(sym, segment_bytes)
    )
    seg_cols = _segment_cols(chunk, native_num, segment_bytes)
    seg = np.zeros((native_num, seg_cols), dtype=np.uint8)
    staged = codec.stage_segment(seg, cap=seg_cols // sym, sym=sym)
    np.asarray(codec.encode(staged))  # block: the compile is now cached
    return {
        "k": native_num, "p": parity_num, "w": w,
        "strategy": codec.strategy, "generator": generator,
        "cols": seg_cols,
    }


@contextmanager
def _fleet_lane():
    """The fleet scaffold every multi-file entry point shares: one ordered
    write-behind lane, archives committed behind their own writes, and —
    the ordering-sensitive part — ``abort`` (running the still-registered
    cleanups) only AFTER the executor context exited with its workers
    joined, so no in-flight drain races a cleanup's closes/unlinks."""
    pipe = FleetPipeline(DrainExecutor(ordered=True))
    try:
        with pipe.executor:
            yield pipe
    except BaseException:
        pipe.abort()
        raise


def _drain_ctx(fleet: FleetPipeline | None, *, ordered: bool = True):
    """Write-behind executor for one dispatch loop (the 5th pipeline stage:
    write ∥ dispatch — see parallel/io_executor.py and docs/IO.md).

    Inside a fleet operation the loop rides the fleet's shared ordered
    lane (not closed per file — archive j+1's dispatches overlap archive
    j's drain); standalone it owns a fresh ``DrainExecutor`` whose context
    exit is the write barrier, placed inside the caller's ``with`` block so
    every write lands before files are closed or promoted.  ``ordered``
    must stay True for drains with cross-segment state (incremental CRC,
    shared-``fp`` streaming writes); the offset-addressed ``os.pwrite``
    collectives pass False to let ``RS_IO_WRITERS`` workers race.
    """
    if fleet is not None:
        return nullcontext(fleet.executor)
    return DrainExecutor(ordered=ordered)


@contextmanager
def _dispatch_span(op: str, off: int, cols: int):
    """Per-segment dispatch span (one per dispatched segment, with its
    column range in args — the trace's unit of accountability).  Also
    feeds the dispatch tail-latency quantiles (`rs analyze` reads the
    p50/p99 split to tell dispatch-bound strategies from memory-bound
    ones) and samples device memory at the segment boundary — this is
    the ONE per-segment sampling site (all six dispatch loops, mesh
    included, pass through here)."""
    t0 = time.perf_counter()
    with _obs_tracing.span(
        "dispatch", lane="dispatch", op=op, off=int(off), cols=int(cols)
    ):
        yield
    _obs_metrics.quantile(
        "rs_dispatch_wall_seconds",
        "per-segment dispatch wall seconds (streaming quantiles)",
    ).labels(op=op).observe(time.perf_counter() - t0)
    _obs_attrib.sample_device_memory()


def _write_deinterleaved_block(
    out_fp, off: int, cols: int, blk: np.ndarray, sym: int, total_size: int
) -> None:
    """Interleaved-layout output write shared by decode_file and
    locate_decode_file (docs/UPDATE.md): the chunk-byte window
    [off, off+cols) of the k rows holds the CONTIGUOUS file range
    [off*k, (off+cols)*k) — one de-interleave and one write per segment
    instead of k scattered row writes, clamped to the real file size."""
    from .update.layout import deinterleave

    k = blk.shape[0]
    lo = off * k
    if lo >= total_size:
        return
    hi = min(lo + cols * k, total_size)
    out_fp.seek(lo)
    out_fp.write(deinterleave(blk, sym)[: hi - lo].tobytes())
    _obs_metrics.counter(
        "rs_io_write_bytes_total",
        "bytes write by the staging-I/O layer",
    ).labels(call="stream_write").inc(hi - lo)


def _segment_spans(chunk_size: int, seg_cols: int) -> list[tuple[int, int]]:
    """(off, cols) spans covering [0, chunk_size) in seg_cols steps."""
    spans = []
    off = 0
    while off < chunk_size:
        cols = min(seg_cols, chunk_size - off)
        spans.append((off, cols))
        off += cols
    return spans


def _check_gfwidth(w: int, meta_path: str) -> None:
    """Reject metadata symbol widths this build does not code for (every
    entry point that reads .METADATA validates before using ``w``)."""
    if w not in (8, 16):
        raise ValueError(
            f"unsupported gfwidth {w} in {meta_path!r} "
            "(this build handles w=8 and w=16 files)"
        )


def _mesh_processes(mesh) -> list[int]:
    """Sorted process indices a mesh's devices span ([] for mesh=None)."""
    if mesh is None:
        return []
    return sorted({d.process_index for d in mesh.devices.flat})


def _open_chunk(
    path: str, chunk: int, index: int | None = None, scope: str = "read"
) -> np.ndarray:
    """Read-only byte view of a chunk file, validated against the expected
    size.  Zero-size archives (chunk == 0, foreign reference encodes of an
    empty file) get an empty array — np.memmap refuses zero-byte files.

    This is a resilience boundary (docs/RESILIENCE.md): the fault plane's
    read hook fires here (``scope`` distinguishes decode reads from scrub
    CRC reads), transient open failures retry under the default policy,
    and — the TOCTOU fix — a chunk that passed the archive scan but shrank
    before this open raises :class:`ChunkIntegrityError` naming ``index``
    (when the caller supplies it) so :func:`auto_decode_file` can exclude
    it and reselect survivors instead of dying on a raw ValueError."""

    def attempt() -> np.ndarray:
        _faults.on_read(path, index=index, scope=scope)
        mm = (
            np.zeros(0, dtype=np.uint8)
            if chunk == 0
            else np.memmap(path, dtype=np.uint8, mode="r")
        )
        if mm.shape[0] < chunk:
            if index is not None:
                raise ChunkIntegrityError(
                    {index: path},
                    reason=f"chunk truncated after scan "
                    f"({mm.shape[0]} of {chunk} bytes)",
                )
            raise ValueError(
                f"chunk {path!r} is {mm.shape[0]} bytes, expected {chunk}"
            )
        return _faults.corrupt(path, index, mm, scope=scope)

    return _retry.default_policy().call(attempt, op="chunk_open")


class _ArchiveCommit:
    """The single-host encode paths' ``.rs_tmp`` crash-atomicity scaffold
    (row and interleaved share it): every output — n chunk files AND
    .METADATA — writes to a temp name and the whole set promotes only
    after every byte landed, chunks first and .METADATA last (its
    presence is the marker of a complete encode).  ``discard`` unlinks
    temps and retracts chunks a failing commit loop already promoted —
    unless they pre-existed (re-encode over an archive), whose previous
    bytes are unrecoverable by rename and whose partial new set still
    scans/repairs via the old .METADATA."""

    def __init__(self, file_name: str, n: int):
        self.file_name = file_name
        self.written: list[str] = [
            chunk_file_name(file_name, i) for i in range(n)
        ] + [metadata_file_name(file_name)]
        self.tmps = {name: name + ".rs_tmp" for name in self.written}
        self._preexisting = {
            name for name in self.written if os.path.exists(name)
        }
        self._committed: list[str] = []

    @property
    def meta_tmp(self) -> str:
        return self.tmps[metadata_file_name(self.file_name)]

    def promote(self) -> None:
        for name in self.written[:-1]:
            os.replace(self.tmps[name], name)
            self._committed.append(name)
        os.replace(self.meta_tmp, metadata_file_name(self.file_name))

    def discard(self) -> None:
        for tmp in self.tmps.values():
            if os.path.exists(tmp):
                os.unlink(tmp)
        for name in self._committed:
            if name not in self._preexisting and os.path.exists(name):
                os.unlink(name)


def _write_empty_atomic(out_path: str) -> str:
    """Atomically produce a zero-byte output file (the decode result of a
    totalSize=0 archive) under the same .rs_tmp commit protocol."""
    tmp_path = out_path + ".rs_tmp"
    with open(tmp_path, "wb"):
        pass
    os.replace(tmp_path, out_path)
    return out_path


def _broadcast_lead_verdict(scan_err, procs, what: str) -> None:
    """Lockstep lead-error propagation for collectives whose lead does
    work peers cannot see (archive scan, survivor selection, conf write).

    Broadcasts an ok/error flag from the lead; on error every process
    raises — the lead its original exception, peers a RuntimeError naming
    the lead — instead of the peers wedging at the next barrier until
    coordinator teardown.  Call on ALL processes, before the barrier that
    consumes the lead's work.  (Collectives that already broadcast lead
    state piggyback a sentinel on that array instead — e.g. the -1 health
    state in _repair_file_multiprocess, the CRC bad_mask in
    _decode_file_multiprocess — saving the extra collective.)
    """
    from jax.experimental import multihost_utils

    flag = np.array([1 if scan_err is not None else 0], dtype=np.int32)
    flag = np.asarray(
        multihost_utils.broadcast_one_to_all(flag, is_source=_is_lead(procs))
    )
    if flag[0]:
        if scan_err is not None:
            raise scan_err
        raise RuntimeError(
            f"{what} failed on the lead process (process {procs[0]}); "
            "see its log for the cause"
        )


def _is_lead(procs) -> bool:
    """Whether this process is the collective's lead (True single-process)."""
    if len(procs) <= 1:
        return True
    import jax

    return jax.process_index() == procs[0]


def _write_native_chunks(
    src: np.ndarray,
    file_name: str,
    tmps: dict[str, str],
    k: int,
    chunk: int,
    total_size: int,
    copy_step: int,
    crcs: dict[int, int] | None,
    timer: PhaseTimer,
) -> None:
    """Write the k native chunk temp files: straight copies of the k file
    ranges, tail zero-padded, in bounded slices (a 100 GB chunk never
    materialises in RAM), with optional incremental CRC32.  The
    multi-process lead's path; a single-process encode writes its natives
    from the gathered segments instead (:func:`_drain_natives`)."""

    for i in range(k):
        with timer.phase("write natives (io)"):
            lo, hi = i * chunk, min((i + 1) * chunk, total_size)
            crc = 0
            with open(tmps[chunk_file_name(file_name, i)], "wb") as fp:

                def put(buf: bytes) -> None:
                    nonlocal crc
                    fp.write(buf)
                    if crcs is not None:
                        with _obs_tracing.span("crc", lane="crc"):
                            crc = crc32_of(buf, crc)

                for s in range(lo, hi, copy_step):
                    put(src[s : min(s + copy_step, hi)].tobytes())
                pad = chunk - max(0, hi - lo)
                zeros = b"\x00" * min(pad, copy_step)
                for s in range(0, pad, copy_step):
                    put(zeros[: min(copy_step, pad - s)])
            if crcs is not None:
                crcs[i] = crc


@_observed_file_op("encode")
def encode_file(
    file_name: str,
    native_num: int,
    parity_num: int,
    *,
    generator: str = "vandermonde",
    strategy: str = "auto",
    segment_bytes: int = DEFAULT_SEGMENT_BYTES,
    pipeline_depth: int = 2,
    mesh=None,
    stripe_sharded: bool = False,
    checksums: bool = False,
    w: int = 8,
    layout: str = "row",
    timer: PhaseTimer | None = None,
    _fleet: FleetPipeline | None = None,
) -> list[str]:
    """Encode ``file_name`` into n = k + p chunk files plus .METADATA.

    Returns the list of files written.  ``pipeline_depth`` is the number of
    segments allowed in flight (maps the reference's ``-s`` flag).  With a
    ``mesh``, segments are sharded across devices (see parallel/sharded.py).
    ``checksums=True`` appends per-chunk CRC32 lines to .METADATA (format
    extension; decode verifies them automatically when present).  Off by
    default so the metadata stays byte-identical to the reference's.
    ``w``: symbol width — 8 (reference-compatible) or 16 (wide-symbol
    extension: chunks hold little-endian uint16 symbols, recorded in
    .METADATA as ``# gfwidth 16``; supports up to 65536 total chunks where
    GF(2^8) caps out at 256).

    Observability: like every file-level entry point, accepts a
    keyword-only ``trace_path=`` (or the ``RS_TRACE`` env var) that writes
    a per-segment Chrome-trace/Perfetto JSON timeline, and feeds the
    ``RS_METRICS`` registry — see docs/OBSERVABILITY.md.
    """
    timer = timer or PhaseTimer(enabled=False)
    if w not in (8, 16):
        raise ValueError(f"file-layer symbol width must be 8 or 16, got {w}")
    sym = w // 8
    k, p = native_num, parity_num
    codec = RSCodec(
        k, p, w=w, generator=generator, strategy=strategy,
        mesh=mesh, stripe_sharded=stripe_sharded,
    )
    total_size = os.path.getsize(file_name)
    if total_size == 0:
        raise ValueError(f"refusing to encode empty file {file_name!r}")
    if layout not in ("row", "interleaved"):
        raise ValueError(
            f"unknown chunk layout {layout!r} (want row or interleaved)"
        )
    if layout == "interleaved":
        # The append-mode layout (docs/UPDATE.md): file symbol s lives in
        # row s % k, column s // k, so `rs append` only ever touches the
        # tail column block.  Single-host: the mesh collectives assume
        # the reference's row-contiguous staging.
        if mesh is not None:
            raise ValueError(
                "interleaved layout encodes single-host; drop --devices"
            )
        return _encode_file_interleaved(
            file_name, codec, total_size, segment_bytes=segment_bytes,
            pipeline_depth=pipeline_depth, checksums=checksums,
            timer=timer, _fleet=_fleet,
        )
    chunk = chunk_size_for(total_size, k, sym)
    seg_cols = _segment_cols(chunk, k, segment_bytes)

    if len(_mesh_processes(mesh)) > 1:
        if _fleet is not None:
            raise ValueError(
                "fleet encode is single-host; multi-process encodes are "
                "collectives with their own barriers"
            )
        return _encode_file_multiprocess(
            file_name, codec, chunk, total_size, seg_cols,
            checksums=checksums, pipeline_depth=pipeline_depth, timer=timer,
        )

    src = np.memmap(file_name, dtype=np.uint8, mode="r")

    # Failure atomicity (same contract decode and repair already keep):
    # a mid-encode crash leaves no partial ``_<i>_`` files for scan_file
    # to misread as a damaged archive (_ArchiveCommit).
    commit = _ArchiveCommit(file_name, k + p)
    written, tmps = commit.written, commit.tmps
    crcs: dict[int, int] | None = {} if checksums else None

    def gather_segment(off: int, cols: int) -> np.ndarray:
        """(k, cols) segment of the striped view, zero-padded.  Uses the
        native pread gather when built (one syscall per row instead of
        Python slice copies); NumPy fallback reuses the open memmap.
        Runs on the prefetch worker thread (reads-only: safe).  A
        resilience read boundary: fault hook + transient-retry (the
        gather writes a fresh buffer, so re-running it is exact)."""
        from . import native

        def attempt() -> np.ndarray:
            _faults.on_read(file_name, scope="read")
            return native.stripe_read(
                file_name, chunk, k, off, cols, total_size, fallback_src=src
            )

        with timer.phase("stage segment (io)"):
            return _retry.default_policy().call(attempt, op="encode_stage")

    files: list = []  # the k native then p parity temp files

    def finalize() -> None:
        # The commit tail: runs only after every chunk write landed — on
        # the caller thread standalone, on the fleet's writer lane (behind
        # this file's drains) in batch mode.
        for fp in files:
            fp.close()
        with timer.phase("write metadata (io)"):
            write_metadata(
                commit.meta_tmp, total_size, p, k, codec.total_matrix, w=w
            )
            if crcs is not None:
                append_checksums(commit.meta_tmp, crcs)
        # Renames over a previous archive's chunks can wait on the disk's
        # writeback; the phase names that wait.
        with timer.phase("promote chunks (io)"):
            commit.promote()

    def cleanup() -> None:
        for fp in files:
            if not fp.closed:
                fp.close()
        commit.discard()

    # In a fleet, cleanup is registered up front and runs via the fleet's
    # abort (after its workers joined) — never inline, where it would race
    # this file's still-queued drains on the shared lane.
    key = _fleet.register(cleanup) if _fleet is not None else None
    try:
        with _drain_ctx(_fleet) as dex:
            for name in written[:-1]:
                files.append(open(tmps[name], "wb"))
            natives, parities = files[:k], files[k:]

            def drain(tag, payload) -> None:
                # Two tasks on the ordered lane, natives first: the k
                # native rows straight from the gathered host segment (so
                # the source is read once), then the parity once its D2H
                # lands, its wait hidden behind the native writes.
                (off, cols), (host, parity) = tag, payload
                dex.submit(
                    lambda: _drain_natives(off, host, natives, timer, crcs),
                    nbytes=host.nbytes,
                )
                dex.submit(
                    lambda: _drain_parity(
                        (off, cols, parity), parities, timer, crcs, k
                    ),
                    nbytes=getattr(parity, "nbytes", 0),
                )

            # Stream segments through the device, staging on a worker
            # thread (SegmentPrefetcher) so read IO overlaps the drains'
            # writes — the three-way overlap of the reference's stream
            # loop (encode.cu:165-218).
            with SegmentPrefetcher(
                _segment_spans(chunk, seg_cols), gather_segment,
                depth=pipeline_depth,
            ) as prefetch, AsyncWindow(pipeline_depth, drain) as window:
                # 5-stage pipeline: the prefetcher reads segment i+2, the
                # ring issues segment i+1's H2D (an async device_put of the
                # bucket-padded segment, see plan.py) while segment i
                # computes, and the write-behind executor drains segment
                # i-1's native rows, D2H and parity off the dispatch thread.
                # Ordered lane: the incremental CRCs (and the no-toolchain
                # seek/write fallback) need commits in column order.  The
                # ring's tag carries each host segment to its drain.
                staging = _staging_ring(
                    (((off, cols, seg), seg) for (off, cols), seg in prefetch),
                    codec, seg_cols, sym, pipeline_depth,
                )
                for (off, cols, host), seg in staging:
                    with timer.phase("encode dispatch"), _dispatch_span(
                        "encode", off, cols
                    ):
                        parity = codec.encode(seg)  # async
                    window.push((off, cols), (host, parity))
        if _fleet is not None:
            _fleet.commit(key, finalize)
        else:
            finalize()
    except BaseException:
        if _fleet is None:
            cleanup()
        raise
    return written


def _drain_natives(off, seg, native_files, timer, crcs=None) -> None:
    """Write a row-layout encode segment's k native rows from the gathered
    host segment ``seg`` at chunk offset ``off``, with their incremental
    CRC32 (see :func:`_drain_parity` for the commit rule)."""
    from . import native

    with timer.phase("write natives (io)"):
        native.scatter_write(native_files, seg, off, call="native_rows")
    if crcs is not None:
        with _obs_tracing.span("crc", lane="crc"):
            new_crcs = {
                i: crc32_of(seg[i], crcs.get(i, 0))
                for i in range(seg.shape[0])
            }
        crcs.update(new_crcs)


def _drain_parity(entry, parity_files, timer, crcs=None, k=0) -> None:
    from . import native

    off, cols, parity = entry
    with timer.phase("encode compute"):
        parity_np = np.asarray(parity)  # blocks on device + D2H
    if parity_np.dtype != np.uint8:
        parity_np = np.ascontiguousarray(parity_np).view(np.uint8)  # LE symbol bytes
    # Segments drain strictly in column order (AsyncWindow is FIFO), so
    # incremental CRC over each parity row is well-defined.  The CRC
    # advance is computed BEFORE the write but committed only AFTER it
    # lands: the writer lane may retry this whole drain on a transient
    # write error (docs/RESILIENCE.md), and a half-committed accumulator
    # would silently corrupt the checksum lines.
    new_crcs = None
    if crcs is not None:
        with _obs_tracing.span("crc", lane="crc"):
            new_crcs = {
                k + j: crc32_of(parity_np[j], crcs.get(k + j, 0))
                for j in range(parity_np.shape[0])
            }
    with timer.phase("write parity (io)"):
        native.scatter_write(parity_files, parity_np, off)
    if new_crcs is not None:
        crcs.update(new_crcs)


def _encode_file_interleaved(
    file_name: str,
    codec: RSCodec,
    total_size: int,
    *,
    segment_bytes: int,
    pipeline_depth: int,
    checksums: bool,
    timer: PhaseTimer,
    _fleet: FleetPipeline | None,
) -> list[str]:
    """Single-host encode under the interleaved chunk layout
    (docs/UPDATE.md): each segment is ONE contiguous read of the source
    file (bytes [off*k, (off+cols)*k)) interleaved into the (k, cols)
    stripe, natives and parity both scatter-written per column window.
    Keeps :func:`encode_file`'s contracts: .rs_tmp atomicity, CRC32
    extension lines, write-behind drain lane, fleet composition."""
    from . import native
    from .update.layout import interleave

    k, p, w = codec.native_num, codec.parity_num, codec.w
    sym = w // 8
    chunk = chunk_size_for_layout(total_size, k, sym, "interleaved")
    seg_cols = _segment_cols(chunk, k, segment_bytes)
    src = np.memmap(file_name, dtype=np.uint8, mode="r")

    commit = _ArchiveCommit(file_name, k + p)
    written, tmps = commit.written, commit.tmps
    crcs: dict[int, int] | None = {} if checksums else None
    files: list = []

    def gather(off: int, cols: int) -> np.ndarray:
        # One contiguous pread range per segment — the layout's staging
        # win (row-major staging needs k scattered range reads).  Same
        # resilience boundary as the row gather: fault hook + retry into
        # a fresh buffer.
        def attempt() -> np.ndarray:
            _faults.on_read(file_name, scope="read")
            lo = off * k
            hi = min(lo + cols * k, total_size)
            buf = np.zeros(cols * k, dtype=np.uint8)
            if lo < hi:
                buf[: hi - lo] = src[lo:hi]
            return interleave(buf, k, sym)

        with timer.phase("stage segment (io)"):
            return _retry.default_policy().call(attempt, op="encode_stage")

    def drain(tag, payload) -> None:
        off, cols = tag
        seg_host, parity = payload
        with timer.phase("encode compute"):
            parity_np = np.asarray(parity)
        if parity_np.dtype != np.uint8:
            parity_np = np.ascontiguousarray(parity_np).view(np.uint8)
        new_crcs = (
            {
                **{i: crc32_of(seg_host[i], crcs.get(i, 0))
                   for i in range(k)},
                **{k + j: crc32_of(parity_np[j], crcs.get(k + j, 0))
                   for j in range(p)},
            }
            if crcs is not None else None
        )
        with timer.phase("write natives (io)"):
            native.scatter_write(files[:k], seg_host, off)
        with timer.phase("write parity (io)"):
            native.scatter_write(files[k:], parity_np, off)
        if new_crcs is not None:
            crcs.update(new_crcs)

    def finalize() -> None:
        for fp in files:
            fp.close()
        with timer.phase("write metadata (io)"):
            write_metadata(
                commit.meta_tmp, total_size, p, k, codec.total_matrix, w=w,
                layout="interleaved",
            )
            if crcs is not None:
                append_checksums(commit.meta_tmp, crcs)
        # Renames over a previous archive's chunks can wait on the disk's
        # writeback; the phase names that wait.
        with timer.phase("promote chunks (io)"):
            commit.promote()

    def cleanup() -> None:
        for fp in files:
            if not fp.closed:
                fp.close()
        commit.discard()

    key = _fleet.register(cleanup) if _fleet is not None else None
    try:
        with _drain_ctx(_fleet) as dex:
            for name in written[:-1]:
                files.append(open(tmps[name], "wb"))
            with SegmentPrefetcher(
                _segment_spans(chunk, seg_cols), gather,
                depth=pipeline_depth,
            ) as prefetch, AsyncWindow(
                pipeline_depth, drain, executor=dex
            ) as window:
                for (off, cols), seg in prefetch:
                    with timer.phase("encode dispatch"), _dispatch_span(
                        "encode", off, cols
                    ):
                        staged = codec.stage_segment(
                            seg, cap=seg_cols // sym, sym=sym
                        )
                        parity = codec.encode(staged)  # async
                    window.push((off, cols), (seg, parity))
        if _fleet is not None:
            _fleet.commit(key, finalize)
        else:
            finalize()
    except BaseException:
        if _fleet is None:
            cleanup()
        raise
    return written


def _encode_file_multiprocess(
    file_name: str,
    codec: RSCodec,
    chunk: int,
    total_size: int,
    seg_cols: int,
    *,
    checksums: bool,
    pipeline_depth: int,
    timer: PhaseTimer,
) -> list[str]:
    """Multi-host file encode over a process-spanning mesh.

    The reference tops out at one machine (pthread-per-GPU, SURVEY §2);
    this is the genuinely-distributed extension: every participating host
    stages only ITS portion of each segment (the byte ranges its mesh
    devices own), the global array is assembled with
    ``make_array_from_process_local_data`` (put_sharded's multi-process
    branch), the sharded GEMM runs collectively, and each host writes only
    its addressable output shards into the shared-filesystem chunk files.
    Requirements: a shared filesystem; w=8 and the w=16 wide-symbol
    extension both work (device columns are whole symbols, so w=16 byte
    offsets are 2x the sharding's symbol spans).

    ``stripe_sharded`` composes with multi-process: the k axis shards
    across the mesh too (each host stages only its stripe rows — the
    wide-stripe DCN layout of BASELINE config 4), the psum rides the
    process boundary, and hosts on stripe row 0 write the (replicated)
    parity output.

    All processes must call encode_file with the same arguments (it is a
    collective).  The lead process (lowest process index in the mesh)
    writes natives and .METADATA and performs the atomic promotion; the
    cross-process barriers are ``sync_global_devices``.
    """
    import jax
    from jax.experimental import multihost_utils
    from jax.sharding import NamedSharding, PartitionSpec as P

    from . import native
    from .parallel.mesh import COLS
    from .parallel.sharded import put_sharded, sharded_gf_matmul

    mesh = codec.mesh
    k, p = codec.native_num, codec.parity_num
    sym = codec.w // 8
    stripe_sharded = codec.stripe_sharded

    lead = jax.process_index() == min(
        d.process_index for d in mesh.devices.flat
    )
    cols_size = mesh.shape[COLS]
    # Input sharding: wide-stripe mode also shards the k axis — each host
    # stages only the stripe rows its devices own (its share of the file),
    # the DCN-scale layout BASELINE config 4 describes.  The GEMM's output
    # is replicated along stripe (psum), so only stripe-row-0 hosts write
    # parity (identical replicas elsewhere — writing them would just
    # duplicate shared-FS IO).
    in_sharding, writes_parity = _stripe_io_roles(mesh, stripe_sharded)
    sharding = NamedSharding(mesh, P(None, COLS))

    written: list[str] = [
        chunk_file_name(file_name, i) for i in range(k + p)
    ] + [metadata_file_name(file_name)]
    tmps = {name: name + ".rs_tmp" for name in written}
    parity_names = [chunk_file_name(file_name, k + j) for j in range(p)]

    src = np.memmap(file_name, dtype=np.uint8, mode="r")
    copy_step = max(1, seg_cols * k)
    crcs: dict[int, int] | None = {} if checksums else None
    preexisting = {name for name in written if os.path.exists(name)}
    committed: list[str] = []

    try:
        if lead:
            _write_native_chunks(
                src, file_name, tmps, k, chunk, total_size, copy_step, crcs,
                timer,
            )
            # Pre-size parity temp files so every process can open r+b and
            # pwrite its shard ranges.
            for name in parity_names:
                with open(tmps[name], "wb") as fp:
                    fp.truncate(chunk)
        multihost_utils.sync_global_devices("rs_encode_files_created")

        def stage(off: int, cols: int):
            # Padded global width in SYMBOLS (equal per-device shards for
            # make_array_from_process_local_data); parity of the zero pad is
            # zero and is trimmed at write time.
            cols_s = cols // sym
            W = ((cols_s + cols_size - 1) // cols_size) * cols_size
            if not stripe_sharded:
                lo, hi = _local_col_span(sharding, k, W)
                with timer.phase("stage segment (io)"):
                    seg = native.stripe_read(
                        file_name, chunk, k, off + lo * sym, (hi - lo) * sym,
                        total_size, fallback_src=src,
                    )
                    return seg.view(np.uint16) if sym == 2 else seg
            # Wide stripe: this host stages only its (stripe rows x column
            # span) block — its own share of the file's byte ranges.
            r0, r1, lo, hi = _local_block(in_sharding, (k, W))
            with timer.phase("stage segment (io)"):
                seg = np.zeros((r1 - r0, (hi - lo) * sym), dtype=np.uint8)
                for i in range(r0, r1):
                    b0 = i * chunk + off + lo * sym
                    b1 = min(
                        b0 + (hi - lo) * sym, (i + 1) * chunk, total_size
                    )
                    n = max(0, b1 - b0)
                    if n:
                        seg[i - r0, :n] = src[b0 : b0 + n]
                return seg.view(np.uint16) if sym == 2 else seg

        parity_fps = [open(tmps[name], "r+b") for name in parity_names]
        try:

            def drain(tag, parity_sharded) -> None:
                off, cols = tag
                if not writes_parity:
                    # Replica holder (stripe rows >= 1): row 0 writes the
                    # identical bytes.  Block for window backpressure only
                    # — no device-to-host copy of parity this host drops.
                    with timer.phase("encode compute"):
                        jax.block_until_ready(parity_sharded)
                    return
                with timer.phase("encode compute"):
                    shards = _trimmed_shards(parity_sharded, cols, sym)
                with timer.phase("write parity (io)"):
                    for col0, data in shards:
                        for j in range(p):
                            os.pwrite(
                                parity_fps[j].fileno(),
                                data[j].tobytes(),
                                off + col0,
                            )

            # Out-of-order write-behind: every drain is an os.pwrite at its
            # own offset into pre-sized temps (no cross-segment state), so
            # RS_IO_WRITERS workers may race freely.
            with SegmentPrefetcher(
                _segment_spans(chunk, seg_cols), stage, depth=pipeline_depth
            ) as prefetch, _drain_ctx(None, ordered=False) as dex, AsyncWindow(
                pipeline_depth, drain, executor=dex
            ) as window:
                for (off, cols), local_seg in prefetch:
                    with timer.phase("encode dispatch"), _dispatch_span(
                        "encode", off, cols
                    ):
                        Bd = put_sharded(local_seg, mesh, stripe_sharded)
                        parity = sharded_gf_matmul(
                            np.asarray(codec.parity_block), Bd,
                            mesh=mesh, w=codec.w, strategy=codec.strategy,
                            stripe_sharded=stripe_sharded,
                        )
                    window.push((off, cols), parity)
        finally:
            for fp in parity_fps:
                fp.close()
        multihost_utils.sync_global_devices("rs_encode_parity_written")

        if lead:
            if crcs is not None:
                # Parity rows were written by many hosts; the lead reads the
                # finished temp files back for the checksum lines.
                with timer.phase("write metadata (io)"):
                    for j, name in enumerate(parity_names):
                        mm = np.memmap(tmps[name], dtype=np.uint8, mode="r")
                        crcs[k + j] = chunk_crc32(mm, chunk, copy_step)
            meta_tmp = tmps[metadata_file_name(file_name)]
            with timer.phase("write metadata (io)"):
                write_metadata(
                    meta_tmp, total_size, p, k, codec.total_matrix, w=codec.w
                )
                if crcs is not None:
                    append_checksums(meta_tmp, crcs)
            for name in written[:-1]:
                os.replace(tmps[name], name)
                committed.append(name)
            os.replace(meta_tmp, metadata_file_name(file_name))
    except BaseException:
        # Same atomicity contract as the single-process path, applied to
        # the SHARED filesystem: unlink every temp (any process can — the
        # paths are common, and losing the unlink race to a peer cleaning
        # the same path is fine), and retract chunks this encode promoted
        # that did not pre-exist.  A process that fails before a barrier
        # leaves its peers blocked in sync_global_devices until the jax
        # coordinator tears the job down — the shared-FS state is clean
        # either way.
        _unlink_shared_tmps(tmps.values())
        _unlink_shared_tmps(
            name for name in committed if name not in preexisting
        )
        raise
    multihost_utils.sync_global_devices("rs_encode_promoted")
    return written


@_observed_file_op("encode_fleet")
def encode_fleet(
    files,
    native_num: int,
    parity_num: int,
    *,
    generator: str = "vandermonde",
    strategy: str = "auto",
    segment_bytes: int = DEFAULT_SEGMENT_BYTES,
    pipeline_depth: int = 2,
    checksums: bool = False,
    w: int = 8,
    layout: str = "row",
    timer: PhaseTimer | None = None,
) -> dict[str, list[str]]:
    """Encode many files back to back through one shared write-behind lane.

    The fleet-level pipeline overlap: file j+1's native-chunk copies,
    stripe reads and GEMM dispatches stream on this thread while file j's
    parity D2H + writes drain on the shared writer lane, with each file's
    metadata write and atomic promote committed behind its own writes.
    The shared plan cache makes the interleave compile-free after the
    first file (identical (k, p, w, strategy) plans).  Single-host by
    construction (multi-process encodes are collectives — no ``mesh``).

    All-or-nothing per *file* (each keeps :func:`encode_file`'s atomicity
    contract), fail-fast across the fleet: the first failing file raises,
    later files are not attempted, and every uncommitted file's temps are
    cleaned up.  Returns ``{file: [paths written]}``.
    """
    timer = timer or PhaseTimer(enabled=False)
    files = list(files)
    results: dict[str, list[str]] = {}
    with _fleet_lane() as pipe:
        for f in files:
            results[f] = encode_file(
                f, native_num, parity_num,
                generator=generator, strategy=strategy,
                segment_bytes=segment_bytes,
                pipeline_depth=pipeline_depth,
                checksums=checksums, w=w, layout=layout,
                timer=timer, _fleet=pipe,
            )
    return results


@_observed_file_op("decode_fleet")
def decode_fleet(
    files,
    outputs: dict[str, str] | None = None,
    *,
    strategy: str = "auto",
    segment_bytes: int = DEFAULT_SEGMENT_BYTES,
    pipeline_depth: int = 2,
    verify_checksums: bool | None = None,
    timer: PhaseTimer | None = None,
) -> dict[str, str]:
    """Auto-decode many archives through one shared write-behind lane.

    Batch counterpart of :func:`auto_decode_file` (survivor discovery per
    archive, CRC-verified subset selection, conf written next to each
    archive), with the fleet-level overlap of :func:`encode_fleet`:
    archive j+1's scan + survivor reads + recovery dispatches run while
    archive j's output writes drain, and each archive's truncate + atomic
    rename commits behind its own writes.  ``outputs`` optionally maps
    ``in_file`` to an output path (default: in place, like decode).

    Fail-fast: the first unrecoverable or failing archive raises; outputs
    already committed stay, uncommitted temps are cleaned up.  Returns
    ``{file: output path}``.
    """
    timer = timer or PhaseTimer(enabled=False)
    files = list(files)
    outputs = outputs or {}
    results: dict[str, str] = {}
    with _fleet_lane() as pipe:
        for f in files:
            results[f] = auto_decode_file(
                f, outputs.get(f),
                strategy=strategy, segment_bytes=segment_bytes,
                pipeline_depth=pipeline_depth,
                verify_checksums=verify_checksums,
                timer=timer, _fleet=pipe,
            )
    return results


@_observed_file_op("decode")
def decode_file(
    in_file: str,
    conf_file: str,
    output: str | None = None,
    *,
    strategy: str = "auto",
    segment_bytes: int = DEFAULT_SEGMENT_BYTES,
    pipeline_depth: int = 2,
    mesh=None,
    stripe_sharded: bool = False,
    verify_checksums: bool | None = None,
    timer: PhaseTimer | None = None,
    _fleet: FleetPipeline | None = None,
    _fallback_rows: list[int] | None = None,
) -> str:
    """Rebuild ``in_file`` from the k surviving chunks listed in
    ``conf_file``.  Returns the output path (defaults to ``in_file``,
    mirroring the reference's overwrite-input default, decode.cu:410-427).

    ``verify_checksums``: None (default) verifies survivors against the
    CRC32 extension lines when .METADATA carries them; True requires them;
    False skips verification.  Raises :class:`ChunkIntegrityError` naming
    the corrupt chunks so the caller can retry with different survivors.

    ``_fallback_rows`` (private, supplied by :func:`auto_decode_file`):
    extra healthy chunk indices whose files live next to ``in_file``.
    With a pool, a *mid-stream* survivor failure — a read error that
    outlives its retries, attributable to one chunk — triggers degraded
    decode: the failed survivor is swapped for a pool chunk, the decode
    matrix is re-derived, and streaming resumes from the first
    uncommitted segment instead of aborting the run
    (docs/RESILIENCE.md).  When no pool chunk can cover the failure, it
    surfaces as :class:`ChunkIntegrityError` naming the survivor (the
    open-time contract), so auto-decode's outer loop can exclude it and
    reselect.
    """
    timer = timer or PhaseTimer(enabled=False)
    if len(_mesh_processes(mesh)) > 1:
        if _fleet is not None:
            raise ValueError(
                "fleet decode is single-host; multi-process decodes are "
                "collectives with their own barriers"
            )
        # The multi-process path does its own lead-verified checksum
        # pre-pass and collective recovery.
        return _decode_file_multiprocess(
            in_file, conf_file, output,
            strategy=strategy, segment_bytes=segment_bytes,
            pipeline_depth=pipeline_depth, mesh=mesh,
            stripe_sharded=stripe_sharded,
            verify_checksums=verify_checksums, timer=timer,
        )
    with timer.phase("read metadata (io)"):
        meta = read_archive_meta(metadata_file_name(in_file))
        total_size, p, k = meta.total_size, meta.parity_num, meta.native_num
        total_mat, w, crcs = meta.total_mat, meta.w, meta.crcs
        layout = meta.layout
    _check_gfwidth(w, metadata_file_name(in_file))
    if total_mat is None:
        total_mat = _regenerate_total_matrix(p, k, w)
    if int(total_mat.max(initial=0)) >= (1 << w):
        raise ValueError(
            f"metadata matrix entry {int(total_mat.max())} out of range for "
            f"GF(2^{w}) — corrupt or foreign .METADATA"
        )
    sym = w // 8
    chunk = meta.chunk
    names = read_conf(conf_file)
    if len(names) != k:
        raise ValueError(f"conf file lists {len(names)} chunks, need k={k}")
    rows = [parse_chunk_index(nm) for nm in names]

    conf_dir = os.path.dirname(os.path.abspath(conf_file))

    def resolve(nm: str) -> str:
        for cand in (nm, os.path.join(conf_dir, os.path.basename(nm)),
                     os.path.join(os.path.dirname(os.path.abspath(in_file)),
                                  os.path.basename(nm))):
            if os.path.exists(cand):
                return cand
        raise FileNotFoundError(f"surviving chunk {nm!r} not found")

    with timer.phase("open chunks (io)"):
        maps = []
        paths = []
        bad_open: dict[int, str] = {}
        for pos, nm in enumerate(names):
            path = resolve(nm)
            try:
                mm = _open_chunk(path, chunk, index=rows[pos])
            except ChunkIntegrityError as e:
                bad_open.update(e.bad_chunks)
                continue
            except OSError:
                # The TOCTOU window: this chunk existed when the conf (or
                # auto-decode scan) selected it but vanished or became
                # unreadable (retries included) before this open.  Collect
                # and name it instead of dying on a raw error so
                # auto_decode_file can exclude it and reselect.  A conf
                # naming a chunk that was NEVER found still raises
                # FileNotFoundError from resolve() above.
                bad_open[rows[pos]] = path
                continue
            maps.append(mm)
            paths.append(path)
        if bad_open:
            raise ChunkIntegrityError(
                bad_open,
                reason="survivor chunks unreadable, truncated or vanished "
                "after selection",
            )

    if verify_checksums is not False:
        if verify_checksums and not crcs:
            raise ValueError(
                f"{metadata_file_name(in_file)!r} has no checksum lines "
                "but verify_checksums=True"
            )
        if crcs:
            uncovered = [r for r in rows if r not in crcs]
            if verify_checksums and uncovered:
                raise ValueError(
                    f"metadata has no CRC for survivor chunk(s) {uncovered} "
                    "but verify_checksums=True"
                )
            # Verification is a separate pre-pass (reads survivors once more
            # than strictly needed): corruption is detected BEFORE any device
            # compute or output writing, and the error names the bad chunks
            # while the conf can still be fixed.
            with timer.phase("verify checksums"):
                bad = {}
                for row, mm, path in zip(rows, maps, paths):
                    if row not in crcs:
                        continue
                    if chunk_crc32(mm, chunk, segment_bytes) != crcs[row]:
                        bad[row] = path
                if bad:
                    raise ChunkIntegrityError(bad)

    if total_size == 0:
        # Foreign zero-byte archive (the reference encoder sizes by ftell
        # with no empty-file guard, cpu-rs.c:492-495, so an empty input
        # yields totalSize=0 metadata): every chunk is zero bytes and the
        # original is the empty file.  Placed AFTER chunk resolution and
        # the checksum contract checks — a conf naming absent chunks or
        # verify_checksums=True without CRC lines still fails loudly.
        return _write_empty_atomic(output or in_file)

    codec = RSCodec(
        k, p, w=w, strategy=strategy, mesh=mesh, stripe_sharded=stripe_sharded
    )
    total_mat = total_mat.astype(codec.gf.dtype)

    # Partial-recovery optimisation: surviving NATIVE chunks are already the
    # answer — copy their bytes straight through and run the recovery GEMM
    # only for the missing native rows.  The reference always multiplies the
    # full k x k (decode.cu:89-227); here a 4-of-14 erasure does 4/10 of
    # that work, and the all-natives scenario does no device work at all.
    # (For survivor rows that are natives, the corresponding rows of the
    # inverse are unit vectors, so dropping them is exact, not approximate.)
    # Only valid when the metadata matrix is systematic (identity top block)
    # — a foreign encoder may write any matrix, and we trust the file.
    systematic = np.array_equal(total_mat[:k], np.eye(k, dtype=total_mat.dtype))

    out_path = output or in_file
    seg_cols = _segment_cols(chunk, k, segment_bytes)
    tmp_path = out_path + ".rs_tmp"
    segments = _segment_spans(chunk, seg_cols)

    # Mutable survivor state: the degraded-decode path swaps a mid-stream-
    # failing survivor for a fallback chunk and resumes, so everything
    # derived from the survivor set lives here and is rebuilt by _derive().
    st: dict = {
        "rows": list(rows), "maps": list(maps), "paths": list(paths),
        "fps": [],
    }

    def _derive() -> None:
        with timer.phase("invert matrix"):
            dec_mat = codec.decode_matrix_from(total_mat, st["rows"])
        native_pos = (
            {r: idx for idx, r in enumerate(st["rows"]) if r < k}
            if systematic else {}
        )
        missing = [i for i in range(k) if i not in native_pos]
        st["native_pos"] = native_pos
        st["rec_row"] = {i: j for j, i in enumerate(missing)}
        st["dec_missing"] = dec_mat[missing] if missing else None
        for fp in st["fps"]:
            if not fp.closed:
                fp.close()
        # Read fds for the pread gather — only the recovery path stages
        # segments; the all-natives path copies through the memmaps.
        st["fps"] = (
            [open(p_, "rb") for p_ in st["paths"]]
            if st["dec_missing"] is not None else []
        )

    _derive()

    try:
        out_fp = open(tmp_path, "wb")
    except BaseException:
        # cleanup() below closes these, but it cannot exist yet without
        # out_fp — an unwritable output target must not leak k chunk fds.
        for fp in st["fps"]:
            fp.close()
        raise

    def finalize() -> None:
        # Runs after every output write landed (standalone: after the
        # drain executor's barrier; fleet: behind this file's drains on
        # the shared writer lane).
        out_fp.truncate(total_size)
        out_fp.close()
        for fp in st["fps"]:
            fp.close()
        os.replace(tmp_path, out_path)

    def cleanup() -> None:
        if not out_fp.closed:
            out_fp.close()
        for fp in st["fps"]:
            if not fp.closed:
                fp.close()
        if os.path.exists(tmp_path):
            os.unlink(tmp_path)

    # Contiguous segments fully written.  Drains commit in dispatch order
    # (ordered lane / FIFO window), and dispatch order is ascending from
    # each attempt's resume point, so the max committed index is also the
    # length of the committed prefix — the degraded path's resume point.
    committed = {"n": 0}

    def write_row(i: int, off: int, cols: int, row_bytes: np.ndarray):
        lo = i * chunk + off
        if lo >= total_size:
            return
        hi = min(lo + cols, total_size)
        out_fp.seek(lo)
        out_fp.write(row_bytes[: hi - lo].tobytes())
        _obs_metrics.counter(
            "rs_io_write_bytes_total",
            "bytes write by the staging-I/O layer",
        ).labels(call="stream_write").inc(hi - lo)

    def write_interleaved(off: int, cols: int, blk: np.ndarray):
        _write_deinterleaved_block(out_fp, off, cols, blk, sym, total_size)

    def _stream(segs) -> None:
        # Bind THIS attempt's derived state into the closures: drains a
        # fleet lane already queued keep the survivor set their recovery
        # GEMM actually used (any valid set recovers identical bytes, so
        # mixed-attempt drains still write a consistent output).
        native_pos = st["native_pos"]
        rec_row = st["rec_row"]
        dec_missing = st["dec_missing"]
        maps_l, paths_l = st["maps"], st["paths"]
        rows_l, fps_l = st["rows"], st["fps"]

        def drain(tag, rec):
            off, cols = tag
            with timer.phase("decode compute"):
                rec_np = np.asarray(rec) if rec is not None else None
            if rec_np is not None and rec_np.dtype != np.uint8:
                rec_np = np.ascontiguousarray(rec_np).view(np.uint8)  # LE
            with timer.phase("write output (io)"):
                if layout == "interleaved":
                    blk = np.empty((k, cols), dtype=np.uint8)
                    for i in range(k):
                        if i in native_pos:
                            blk[i] = maps_l[native_pos[i]][off : off + cols]
                        else:
                            blk[i] = rec_np[rec_row[i]][:cols]
                    write_interleaved(off, cols, blk)
                else:
                    for i in range(k):
                        if i in native_pos:
                            src_row = maps_l[native_pos[i]][off : off + cols]
                            write_row(i, off, cols, src_row)
                        else:
                            write_row(i, off, cols, rec_np[rec_row[i]])
            committed["n"] = max(committed["n"], off // seg_cols + 1)

        from . import native

        if dec_missing is None:
            with _drain_ctx(_fleet) as dex, AsyncWindow(
                pipeline_depth, drain, executor=dex
            ) as window:
                for off, cols in segs:
                    # all natives survived: pure copy, nothing staged
                    window.push((off, cols), None)
            return

        def stage(off: int, cols: int) -> np.ndarray:
            # Native pread gather (one syscall per surviving chunk);
            # memmap copies as fallback.  Runs on the prefetch worker so
            # read IO overlaps the drain's output writes.  A resilience
            # read boundary: per-survivor fault hook + transient-retry
            # (the gather fills a fresh buffer — idempotent).
            def attempt() -> np.ndarray:
                _faults.on_reads(paths_l, rows_l)
                return native.gather_rows(
                    fps_l, off, cols, fallback_maps=maps_l
                )

            with timer.phase("stage segment (io)"):
                return _retry.default_policy().call(
                    attempt, op="decode_stage"
                )

        # Ordered write-behind: the streaming shared-fp seek/write
        # commit must stay in column order, but it runs on the writer
        # lane — the dispatch loop never blocks on D2H or fp.write.
        with SegmentPrefetcher(
            segs, stage, depth=pipeline_depth
        ) as prefetch, _drain_ctx(_fleet) as dex, AsyncWindow(
            pipeline_depth, drain, executor=dex
        ) as window:
            staging = _staging_ring(
                prefetch, codec, seg_cols, sym, pipeline_depth
            )
            for (off, cols), seg in staging:
                with timer.phase("decode dispatch"), _dispatch_span(
                    "decode", off, cols
                ):
                    rec = codec.decode(dec_missing, seg)  # async
                window.push((off, cols), rec)

    def _attribute(e: BaseException) -> list[int]:
        """Survivor rows a mid-stream read failure pins on: injected
        faults carry their chunk index; real failures are probed with
        fstat (a chunk truncated or unlinked under us shows up here)."""
        if isinstance(e, _faults.InjectedReadError):
            return [e.index] if e.index in st["rows"] else []
        bad = []
        for r, fp in zip(st["rows"], st["fps"]):
            try:
                if os.fstat(fp.fileno()).st_size < chunk:
                    bad.append(r)
            except OSError:
                bad.append(r)
        return bad

    pool = [r for r in (_fallback_rows or []) if r not in set(st["rows"])]

    # Swapped-in pool chunks get the same read-time integrity treatment
    # the initial survivors got: CRC-verified whenever the pre-pass above
    # verified (verify_checksums=True, or default-on with CRC lines) —
    # a pool chunk that rotted after the scan must not decode silently.
    verify_swaps = verify_checksums is not False and bool(crcs)

    def _reselect(bad: list[int]) -> bool:
        """Swap the failed survivors for pool chunks and re-derive the
        decode state; False when the pool cannot cover them (or every
        replacement set hits a singular submatrix)."""
        from .ops.inverse import SingularMatrixError

        keep = [
            (r, m, p_)
            for r, m, p_ in zip(st["rows"], st["maps"], st["paths"])
            if r not in bad
        ]
        while True:
            fresh = []
            while pool and len(keep) + len(fresh) < k:
                r = pool.pop(0)
                p_ = chunk_file_name(in_file, r)
                try:
                    m = _open_chunk(p_, chunk, index=r)
                    if (
                        verify_swaps and r in crcs
                        and chunk_crc32(m, chunk, segment_bytes) != crcs[r]
                    ):
                        continue  # rotted after the scan; try the next
                except (ValueError, OSError):
                    continue  # this fallback is damaged too; try the next
                fresh.append((r, m, p_))
            if len(keep) + len(fresh) < k:
                return False
            merged = keep + fresh
            st["rows"] = [r for r, _, _ in merged]
            st["maps"] = [m for _, m, _ in merged]
            st["paths"] = [p_ for _, _, p_ in merged]
            try:
                _derive()
            except SingularMatrixError:
                continue  # rare non-MDS corner: try further pool chunks
            return True

    key = _fleet.register(cleanup) if _fleet is not None else None
    reselects = 0
    max_reselects = max(0, _retry.int_env("RS_RETRY_RESELECT", 3))
    try:
        while True:
            try:
                _stream(segments[committed["n"]:])
                break
            except OSError as e:
                bad = [r for r in _attribute(e) if r in st["rows"]]
                if not bad:
                    raise  # unattributable (e.g. a write-side error)
                # Snapshot the failing rows' paths NOW: a failed
                # _reselect leaves st mutated with the bad rows already
                # dropped, and the error below must still name them.
                bad_paths = {
                    r: p_ for r, p_ in zip(st["rows"], st["paths"])
                    if r in bad
                }
                swapped = False
                if reselects < max_reselects:
                    if _fleet is not None:
                        # Let this archive's queued drains land (their
                        # bytes are correct for their segments) so
                        # ``committed`` is final before the resume point
                        # is chosen.
                        _fleet.executor.flush()
                    swapped = _reselect(bad)
                if not swapped:
                    # Attributed but unswappable: surface the failing
                    # survivor BY NAME so auto_decode_file's outer loop
                    # can exclude it, rescan and reselect — the same
                    # contract as an open-time (TOCTOU) failure.
                    raise ChunkIntegrityError(
                        bad_paths,
                        reason="survivor chunk failed mid-stream reads "
                        "past retries",
                    ) from e
                reselects += 1
                _obs_tracing.instant(
                    "degraded_reselect", lane="retry",
                    bad=",".join(map(str, bad)),
                    resume_segment=committed["n"],
                )
        if reselects:
            _obs_metrics.counter(
                "rs_degraded_decodes_total",
                "decodes completed after mid-stream survivor reselection",
            ).labels(stage="midstream").inc()
        if _fleet is not None:
            _fleet.commit(key, finalize)
        else:
            finalize()
    except BaseException:
        if _fleet is None:
            cleanup()
        raise
    return out_path


def _local_col_span(sharding, k: int, W: int) -> tuple[int, int]:
    """This process's contiguous column range of a (k, W) cols-sharded
    global array (shared by the multi-process encode/decode/repair
    collectives)."""
    idx = sharding.addressable_devices_indices_map((k, W))
    spans = sorted((s[1].start, s[1].stop) for s in idx.values())
    lo, hi = spans[0][0], spans[-1][1]
    if any(a[1] != b[0] for a, b in zip(spans, spans[1:])):
        raise ValueError(
            "mesh cols axis gives this process a non-contiguous "
            "column range; build the mesh from jax.devices() order"
        )
    return lo, hi


def _local_block(sharding, shape) -> tuple[int, int, int, int]:
    """This process's contiguous (row, col) block of a 2-D sharded global
    array — the staging layout of the wide-stripe (row-sharded) encode
    collective, generalising :func:`_local_col_span` to both axes.

    Returns ``(r0, r1, c0, c1)``.  Each axis must tile contiguously and the
    process's shards must form the full cartesian block (meshes built from
    ``jax.devices()`` order do)."""
    idx = sharding.addressable_devices_indices_map(shape)

    def axis_span(a: int) -> tuple[int, int]:
        spans = sorted({
            (s[a].start or 0,
             shape[a] if s[a].stop is None else s[a].stop)
            for s in idx.values()
        })
        if any(x[1] != y[0] for x, y in zip(spans, spans[1:])):
            raise ValueError(
                f"mesh axis {a} gives this process a non-contiguous range; "
                "build the mesh from jax.devices() order"
            )
        return spans[0][0], spans[-1][1]

    r0, r1 = axis_span(0)
    c0, c1 = axis_span(1)
    return r0, r1, c0, c1


def _stripe_io_roles(mesh, stripe_sharded: bool):
    """Input sharding and write role for the wide-stripe collectives.

    Returns ``(in_sharding, writes_output)``: the data sharding
    (``P(STRIPE, COLS)`` under wide-stripe, ``P(None, COLS)`` otherwise)
    and whether THIS process writes the GEMM output.  Under stripe
    sharding the output is psum-replicated along the stripe axis, so only
    hosts whose devices sit on stripe index 0 write (located by axis NAME
    — a mesh built with transposed axis order still elects a writer set
    that covers every column shard).  Shared by the encode, decode and
    repair collectives so the election rule cannot drift between them.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from .parallel.mesh import COLS, STRIPE

    in_sharding = NamedSharding(
        mesh, P(STRIPE if stripe_sharded else None, COLS)
    )
    if not stripe_sharded:
        return in_sharding, True
    ax = list(mesh.axis_names).index(STRIPE)
    row0 = np.take(np.asarray(mesh.devices), 0, axis=ax)
    writes = jax.process_index() in {
        d.process_index for d in row0.flat
    }
    return in_sharding, writes


def _make_padded_stage(fps, maps, chunk, cols_size, sharding, k, timer, sym=1):
    """Segment stager shared by the multi-process decode and repair
    collectives: reads this process's block of the k survivor files —
    its column span, and (when ``sharding`` also shards the stripe/k axis,
    the wide-stripe mode) only its survivor rows — zero-filling the pad
    columns past the chunk end (equal per-device shards need the padded
    width; the pad's decoded garbage is dropped by the trimmed writes).
    Sharding spans are in SYMBOL units (``sym`` bytes each — 2 for w=16,
    whose segments come back as uint16 views); the file reads convert
    back to byte offsets."""
    from . import native

    def stage(off: int, cols: int):
        off_s, cols_s, chunk_s = off // sym, cols // sym, chunk // sym
        W = ((cols_s + cols_size - 1) // cols_size) * cols_size
        r0, r1, lo, hi = _local_block(sharding, (k, W))
        readable = max(0, min(off_s + hi, chunk_s) - (off_s + lo))
        with timer.phase("stage segment (io)"):
            seg = np.zeros((r1 - r0, (hi - lo) * sym), dtype=np.uint8)
            if readable:
                seg[:, : readable * sym] = native.gather_rows(
                    fps[r0:r1], (off_s + lo) * sym, readable * sym,
                    fallback_maps=maps[r0:r1],
                )
            return seg.view(np.uint16) if sym == 2 else seg

    return stage


def _trimmed_shards(sharded, cols: int, sym: int = 1):
    """Materialise the addressable shards of a cols-sharded GEMM output as
    ``(byte_col0, uint8 rows)`` pairs, trimmed to the segment's real width
    (the zero-pad columns staged for equal per-device shards are dropped
    here).  Blocks on the device; callers time it under their compute
    phase.  ``sym``-byte symbols are flattened to little-endian bytes, the
    chunk-file byte order."""
    out = []
    seen: set = set()
    cols_s = cols // sym
    for sh in sharded.addressable_shards:
        col0 = sh.index[1].start or 0  # None for an unsharded cols axis
        if col0 in seen:
            # stripe-replicated output: every stripe row holds an identical
            # replica of each column shard — materialise one per range.
            continue
        seen.add(col0)
        data = np.asarray(sh.data)
        n_cols = min(data.shape[1], cols_s - col0)
        if n_cols <= 0:
            continue
        rows = np.ascontiguousarray(data[:, :n_cols])
        if rows.dtype != np.uint8:
            rows = rows.view(np.uint8)
        out.append((col0 * sym, rows))
    return out


def _unlink_shared_tmps(paths) -> None:
    """Best-effort cleanup of shared-FS temp files from a failing
    collective: every process runs this near-simultaneously against the
    same paths, so losing the exists/unlink race to a peer is success, not
    an error to bury the original exception under."""
    for tmp in paths:
        try:
            os.unlink(tmp)
        except FileNotFoundError:
            pass


def _decode_file_multiprocess(
    in_file: str,
    conf_file: str,
    output: str | None,
    *,
    strategy: str,
    segment_bytes: int,
    pipeline_depth: int,
    mesh,
    stripe_sharded: bool = False,
    verify_checksums: bool | None,
    timer: PhaseTimer,
) -> str:
    """Multi-host file decode over a process-spanning mesh (collective).

    Mirrors :func:`_encode_file_multiprocess` (including its wide-stripe
    composition: ``stripe_sharded`` shards the SURVIVOR axis across hosts,
    each staging only its survivor rows, with stripe-row-0 hosts writing
    the psum-replicated recovery): every host stages only its
    column span of each survivor segment, the recovery GEMM runs sharded
    over the mesh, and each host pwrites its addressable output shards into
    a shared-filesystem temp the lead process pre-sizes and atomically
    promotes.  Surviving-native passthrough rows are copied round-robin
    across hosts (partial recovery — only the missing rows ride the
    device).  The checksum pre-pass runs on the lead only and its verdict
    is broadcast, so a corrupt survivor raises the same
    :class:`ChunkIntegrityError` on every process instead of wedging peers
    at a barrier.  Requirements: shared filesystem, w=8 or w=16 (same
    contract as multi-process encode).
    """
    import jax
    from jax.experimental import multihost_utils

    from .parallel.mesh import COLS
    from .parallel.sharded import put_sharded, sharded_gf_matmul

    procs = _mesh_processes(mesh)
    lead = _is_lead(procs)

    with timer.phase("read metadata (io)"):
        meta_mp = read_archive_meta(metadata_file_name(in_file))
        total_size, p, k = (
            meta_mp.total_size, meta_mp.parity_num, meta_mp.native_num
        )
        total_mat, w, crcs = meta_mp.total_mat, meta_mp.w, meta_mp.crcs
    _check_gfwidth(w, metadata_file_name(in_file))
    if meta_mp.layout != "row":
        raise ValueError(
            f"{in_file!r} uses the {meta_mp.layout!r} chunk layout; "
            "multi-process decode handles row-layout archives only — "
            "decode single-host"
        )
    sym = w // 8
    if total_mat is None:
        total_mat = _regenerate_total_matrix(p, k, w)
    if int(total_mat.max(initial=0)) >= (1 << w):
        raise ValueError(
            f"metadata matrix entry {int(total_mat.max())} out of range for "
            f"GF(2^{w}) — corrupt or foreign .METADATA"
        )
    chunk = chunk_size_for(total_size, k, sym)
    names = read_conf(conf_file)
    if len(names) != k:
        raise ValueError(f"conf file lists {len(names)} chunks, need k={k}")
    rows = [parse_chunk_index(nm) for nm in names]

    conf_dir = os.path.dirname(os.path.abspath(conf_file))

    def resolve(nm: str) -> str:
        for cand in (nm, os.path.join(conf_dir, os.path.basename(nm)),
                     os.path.join(os.path.dirname(os.path.abspath(in_file)),
                                  os.path.basename(nm))):
            if os.path.exists(cand):
                return cand
        raise FileNotFoundError(f"surviving chunk {nm!r} not found")

    with timer.phase("open chunks (io)"):
        maps, paths = [], []
        for pos, nm in enumerate(names):
            path = resolve(nm)
            mm = _open_chunk(path, chunk, index=rows[pos])
            maps.append(mm)
            paths.append(path)

    if verify_checksums is not False:
        if verify_checksums and not crcs:
            raise ValueError(
                f"{metadata_file_name(in_file)!r} has no checksum lines "
                "but verify_checksums=True"
            )
        if crcs:
            uncovered = [r for r in rows if r not in crcs]
            if verify_checksums and uncovered:
                raise ValueError(
                    f"metadata has no CRC for survivor chunk(s) {uncovered} "
                    "but verify_checksums=True"
                )
            # Lead-only CRC pass; verdict broadcast as a (k,) 0/1 mask so
            # every process raises (or proceeds) in lockstep.
            with timer.phase("verify checksums"):
                bad_mask = np.zeros(k, dtype=np.int32)
                if lead:
                    for pos, (row, mm) in enumerate(zip(rows, maps)):
                        if row in crcs and (
                            chunk_crc32(mm, chunk, segment_bytes) != crcs[row]
                        ):
                            bad_mask[pos] = 1
                bad_mask = np.asarray(
                    multihost_utils.broadcast_one_to_all(
                        bad_mask, is_source=lead
                    )
                )
                if bad_mask.any():
                    raise ChunkIntegrityError({
                        rows[pos]: paths[pos]
                        for pos in np.flatnonzero(bad_mask)
                    })

    if total_size == 0:
        # Foreign zero-byte archive (see decode_file — same placement,
        # after chunk resolution and the checksum contract checks): the
        # lead writes the empty output; all processes leave in lockstep.
        out_path = output or in_file
        if lead:
            _write_empty_atomic(out_path)
        multihost_utils.sync_global_devices("rs_decode_promoted")
        return out_path

    codec = RSCodec(
        k, p, w=w, strategy=strategy, mesh=mesh,
        stripe_sharded=stripe_sharded,
    )
    total_mat = total_mat.astype(codec.gf.dtype)
    with timer.phase("invert matrix"):
        dec_mat = codec.decode_matrix_from(total_mat, rows)

    # Same partial-recovery split as the single-process path.
    systematic = np.array_equal(total_mat[:k], np.eye(k, dtype=total_mat.dtype))
    native_pos = (
        {r: idx for idx, r in enumerate(rows) if r < k} if systematic else {}
    )
    missing = [i for i in range(k) if i not in native_pos]
    dec_missing = dec_mat[missing] if missing else None

    out_path = output or in_file
    tmp_path = out_path + ".rs_tmp"
    seg_cols = _segment_cols(chunk, k, segment_bytes)
    cols_size = mesh.shape[COLS]
    # Wide-stripe mode: the SURVIVOR axis shards across hosts too — each
    # stages only its survivor rows; the recovered output is replicated
    # along stripe (psum), so only stripe-row-0 hosts write it.
    in_sharding, writes_output = _stripe_io_roles(mesh, stripe_sharded)
    copy_step = max(1, segment_bytes)

    try:
        if lead:
            with open(tmp_path, "wb") as fp:
                fp.truncate(total_size)
        multihost_utils.sync_global_devices("rs_decode_tmp_created")

        out_fp = open(tmp_path, "r+b")
        fps = [open(p_, "rb") for p_ in paths] if dec_missing is not None else []
        try:
            def pwrite_row(i: int, off: int, row_bytes: np.ndarray) -> None:
                lo = i * chunk + off
                if lo >= total_size:
                    return
                hi = min(lo + row_bytes.shape[0], total_size)
                os.pwrite(
                    out_fp.fileno(),
                    np.ascontiguousarray(row_bytes[: hi - lo]).tobytes(),
                    lo,
                )

            # Surviving natives: straight host copies, split round-robin
            # across the participating hosts (no device involved).
            with timer.phase("write output (io)"):
                my_rank = procs.index(jax.process_index())
                for idx, i in enumerate(sorted(native_pos)):
                    if idx % len(procs) != my_rank:
                        continue
                    mm = maps[native_pos[i]]
                    for s in range(0, chunk, copy_step):
                        pwrite_row(i, s, mm[s : min(s + copy_step, chunk)])

            if dec_missing is not None:
                stage = _make_padded_stage(
                    fps, maps, chunk, cols_size, in_sharding, k, timer, sym
                )

                def drain(tag, rec_sharded) -> None:
                    off, cols = tag
                    if not writes_output:
                        # Replica holder: block for window backpressure
                        # only (stripe row 0 writes the identical bytes).
                        with timer.phase("decode compute"):
                            jax.block_until_ready(rec_sharded)
                        return
                    with timer.phase("decode compute"):
                        shards = _trimmed_shards(rec_sharded, cols, sym)
                    with timer.phase("write output (io)"):
                        for col0, data in shards:
                            for j, i in enumerate(missing):
                                pwrite_row(i, off + col0, data[j])

                # Out-of-order write-behind (offset-addressed pwrites into
                # the lead-pre-sized temp; no cross-segment state).
                with SegmentPrefetcher(
                    _segment_spans(chunk, seg_cols), stage,
                    depth=pipeline_depth,
                ) as prefetch, _drain_ctx(
                    None, ordered=False
                ) as dex, AsyncWindow(
                    pipeline_depth, drain, executor=dex
                ) as window:
                    for (off, cols), local_seg in prefetch:
                        with timer.phase("decode dispatch"), _dispatch_span(
                            "decode", off, cols
                        ):
                            Bd = put_sharded(local_seg, mesh, stripe_sharded)
                            rec = sharded_gf_matmul(
                                np.asarray(dec_missing), Bd,
                                mesh=mesh, w=w, strategy=codec.strategy,
                                stripe_sharded=stripe_sharded,
                            )
                        window.push((off, cols), rec)
        finally:
            out_fp.close()
            for fp in fps:
                fp.close()
        multihost_utils.sync_global_devices("rs_decode_written")
        if lead:
            os.replace(tmp_path, out_path)
    except BaseException:
        _unlink_shared_tmps([tmp_path])
        raise
    multihost_utils.sync_global_devices("rs_decode_promoted")
    return out_path


def _regenerate_total_matrix(p: int, k: int, w: int) -> np.ndarray:
    """Canonical [I; Vandermonde] total matrix for sizes-only (CPU-RS
    dialect) metadata — bit-identical to the reference's regeneration."""
    from .models.vandermonde import total_matrix
    from .ops.gf import get_field

    return total_matrix(p, k, get_field(w))


class _ChunkScan:
    """Result of scanning an encode's chunk set: metadata fields plus which
    chunk indices are healthy, CRC-failing, or missing."""

    def __init__(self, in_file, total_size, p, k, total_mat, w, crcs,
                 chunk, healthy, bad, layout="row", generation=0):
        self.in_file = in_file
        self.total_size = total_size
        self.p = p
        self.k = k
        self.total_mat = total_mat
        self.w = w
        self.crcs = crcs
        self.chunk = chunk
        self.healthy = healthy          # indices with full-size, CRC-clean files
        self.bad = bad                  # {index: path} damaged: truncated or CRC-fail
        self.layout = layout            # chunk layout (docs/UPDATE.md)
        self.generation = generation    # update/append commit counter
        self.missing = sorted(
            set(range(k + p)) - set(healthy) - set(bad)
        )

    @property
    def unhealthy(self):
        """All chunk indices needing repair (corrupt or absent)."""
        return sorted(set(self.bad) | set(self.missing))

    def excluding(self, bad: dict[int, str]) -> "_ChunkScan":
        """A view of this scan with ``bad`` chunks demoted from healthy —
        how auto-decode folds in failures discovered AFTER the scan
        (TOCTOU opens, mid-stream read errors) before reselecting."""
        return _ChunkScan(
            self.in_file, self.total_size, self.p, self.k, self.total_mat,
            self.w, self.crcs, self.chunk,
            [i for i in self.healthy if i not in bad],
            {**self.bad, **bad},
            layout=self.layout, generation=self.generation,
        )


def _scan_chunks(in_file: str, segment_bytes: int) -> _ChunkScan:
    """Discover chunk health next to ``in_file`` (size + CRC checks).

    The scrub instrumentation point: every archive scan counts itself
    and its per-chunk verdicts (``rs_scrub_archives_scanned_total`` /
    ``rs_scrub_chunks_total{state}``) and records one span on the
    ``scrub`` lane, so fleet-wide health sweeps (scan_file, repair_fleet,
    auto-decode discovery) all feed the same series, and the seconds of
    every completed scan go to ``rs_scrub_scan_seconds_total``.
    """
    t0 = time.perf_counter()
    with _obs_tracing.span("scan_chunks", lane="scrub", file=in_file):
        meta_path = metadata_file_name(in_file)
        meta = read_archive_meta(meta_path)
        total_size, p, k = meta.total_size, meta.parity_num, meta.native_num
        total_mat, w, crcs = meta.total_mat, meta.w, meta.crcs
        _check_gfwidth(w, meta_path)
        if total_mat is None:
            total_mat = _regenerate_total_matrix(p, k, w)
        if int(total_mat.max(initial=0)) >= (1 << w):
            raise ValueError(
                f"metadata matrix entry {int(total_mat.max())} out of range "
                f"for GF(2^{w}) — corrupt or foreign .METADATA"
            )
        # Layout-aware chunk length: interleaved archives (the append-mode
        # extension, docs/UPDATE.md) size chunks by columns, not rows.
        # Everything below — size check, CRC over the whole chunk file,
        # health verdicts — is layout-agnostic given the right length.
        chunk = meta.chunk
        chunk_states = _obs_metrics.counter(
            "rs_scrub_chunks_total", "chunk verdicts from archive scans"
        )
        healthy: list[int] = []
        bad: dict[int, str] = {}
        # Per-index damage verdicts for the health plane (obs/health.py):
        # one rs_damage "scan" event per scan, whose FULL state map (an
        # empty one included — a clean scan clears prior damage) is the
        # fleet model's scrub-freshness signal.
        damage_states: dict[int, str] = {}
        for i in range(k + p):
            path = chunk_file_name(in_file, i)
            if not os.path.exists(path):
                chunk_states.labels(state="missing").inc()
                damage_states[i] = "missing"
                continue
            if os.path.getsize(path) < chunk:
                bad[i] = path  # present but truncated — damage, not loss
                chunk_states.labels(state="truncated").inc()
                damage_states[i] = "truncated"
                continue
            if i in crcs:
                try:
                    # empty-safe for chunk == 0; scope="scrub" addresses
                    # the fault plane's scrub boundary
                    mm = _open_chunk(path, chunk, index=i, scope="scrub")
                except ChunkIntegrityError:
                    # Shrank between the getsize above and this open.
                    bad[i] = path
                    chunk_states.labels(state="truncated").inc()
                    damage_states[i] = "truncated"
                    continue
                except OSError:
                    # Degraded read: a chunk that stays unreadable after
                    # retries is damage to record, not a reason to fail
                    # the whole archive scan — scrub carries on and
                    # repair treats it like any other corrupt chunk.
                    bad[i] = path
                    chunk_states.labels(state="read_error").inc()
                    damage_states[i] = "read_error"
                    continue
                if chunk_crc32(mm, chunk, segment_bytes) != crcs[i]:
                    bad[i] = path
                    chunk_states.labels(state="crc_mismatch").inc()
                    damage_states[i] = "crc_mismatch"
                    continue
            healthy.append(i)
            chunk_states.labels(state="healthy").inc()
        _obs_metrics.counter(
            "rs_scrub_archives_scanned_total", "archive health scans"
        ).labels(outcome="damaged" if bad or len(healthy) < k + p
                 else "clean").inc()
        tolerance = erasure_tolerance(total_mat, k, w)
        _obs_health.record_damage(
            "scan", in_file, states=damage_states, k=k, p=p, w=w,
            generation=meta.generation,
            tolerance=None if tolerance == p else tolerance,
        )
        _obs_metrics.counter(
            "rs_scrub_scan_seconds_total",
            "wall seconds of completed archive health scans",
        ).inc(time.perf_counter() - t0)
        return _ChunkScan(
            in_file, total_size, p, k, total_mat, w, crcs, chunk, healthy,
            bad, layout=meta.layout, generation=meta.generation,
        )


# -- generation-keyed survivor-subset cache -----------------------------------
#
# Decode-side warm-path amortization (docs/PLAN.md "Generation-keyed
# schedule entries"): every auto-decode attempt, scrub verdict and
# repair pass used to re-run the subset search and re-invert the k x k
# submatrix — and, under ``strategy="xor"``, every DISTINCT survivor
# subset compiles its own inverse schedule.  This cache pins one chosen
# subset + verified inverse per (archive, generation): subset churn
# (different parity chunks dying and coming back, natives reappearing,
# fleet re-passes) keeps resolving to the pinned subset as long as it is
# still fully healthy, so the xor schedule for its inverse compiles
# exactly once per archive generation.  An update/append bumps the
# metadata generation and invalidates the entry; a total-matrix change
# (re-encode under the same name, different generator) is caught by the
# matrix digest.  ``PLAN_CACHE.clear()`` clears this too — the pinned
# inverse's schedule lives in the caches that clear drops.

_SUBSET_CACHE: "OrderedDict[str, dict]" = OrderedDict()
_SUBSET_LOCK = threading.Lock()
_SUBSET_CACHE_MAX = 128
_SUBSET_STATS = {"hits": 0, "misses": 0, "stale": 0}


def clear_subset_cache() -> None:
    """Drop the generation-keyed survivor-subset cache (paired with
    ``PLAN_CACHE.clear()``; stats reset too)."""
    with _SUBSET_LOCK:
        _SUBSET_CACHE.clear()
        for key in _SUBSET_STATS:
            _SUBSET_STATS[key] = 0


def subset_cache_stats() -> dict:
    """Doctor surface: entry count + this process's hit/miss/stale
    tallies (``rs doctor`` strategies section)."""
    with _SUBSET_LOCK:
        return {"entries": len(_SUBSET_CACHE), **_SUBSET_STATS}


def _subset_mat_digest(scan: _ChunkScan) -> str:
    from .ops.xor_gemm import matrix_digest

    return matrix_digest(scan.total_mat, scan.w)


def _cached_subset(scan: _ChunkScan):
    """The pinned (chosen, inverse) for this archive generation, or None
    when absent, generation-stale, matrix-mismatched, or no longer fully
    healthy in this scan."""
    key = os.path.abspath(scan.in_file)
    with _SUBSET_LOCK:
        ent = _SUBSET_CACHE.get(key)
    if ent is None:
        return None
    if (
        ent["generation"] != scan.generation
        or ent["mat_digest"] != _subset_mat_digest(scan)
        or len(ent["chosen"]) != scan.k
    ):
        with _SUBSET_LOCK:
            if _SUBSET_CACHE.get(key) is ent:
                del _SUBSET_CACHE[key]
            _SUBSET_STATS["stale"] += 1
        return None
    if not set(ent["chosen"]) <= set(scan.healthy):
        # Not stale — the pinned subset just isn't available under THIS
        # scan's damage; a later scan with those chunks back reuses it.
        return None
    with _SUBSET_LOCK:
        if key in _SUBSET_CACHE:
            _SUBSET_CACHE.move_to_end(key)
        _SUBSET_STATS["hits"] += 1
    return list(ent["chosen"]), ent["inv"]


def _remember_subset(scan: _ChunkScan, chosen, inv) -> None:
    key = os.path.abspath(scan.in_file)
    ent = {
        "generation": scan.generation,
        "mat_digest": _subset_mat_digest(scan),
        "chosen": tuple(int(c) for c in chosen),
        "inv": inv,
    }
    with _SUBSET_LOCK:
        _SUBSET_CACHE[key] = ent
        _SUBSET_CACHE.move_to_end(key)
        while len(_SUBSET_CACHE) > _SUBSET_CACHE_MAX:
            _SUBSET_CACHE.popitem(last=False)
        _SUBSET_STATS["misses"] += 1


def _select_decodable_subset(scan: _ChunkScan, *, cap: int = 100,
                             skip: int = 0):
    """Pick k healthy chunk indices whose submatrix inverts; returns
    ``(chosen, inverse)`` so callers don't re-invert.

    Natives-first candidate order (partial recovery makes them free), then
    parity; lazily falls back through other subsets on singularity.  The cap
    bounds pathological non-MDS matrices; Vandermonde/Cauchy submatrices
    are near-always invertible so the first try is the common case.

    ``skip``/``cap`` window the candidate stream so a caller that caught
    :class:`UndecidedSubsetError` can continue the search where the last
    batch stopped (:func:`_select_subset_retrying`) instead of redoing —
    and then abandoning — the same ``cap`` singular candidates.

    A fresh-window call (``skip == 0``) first consults the
    generation-keyed subset cache: the archive's pinned subset — still
    fully healthy under this scan, same generation, same matrix — comes
    back with zero search, zero inversion and (under ``strategy="xor"``)
    zero new schedule compiles.
    """
    from itertools import combinations

    from .ops.gf import get_field
    from .ops.inverse import SingularMatrixError, invert_matrix

    k = scan.k
    if len(scan.healthy) < k:
        raise ValueError(
            f"only {len(scan.healthy)} healthy chunks of the k={k} needed "
            f"(corrupt: {sorted(scan.bad)}, missing: {scan.missing})"
        )
    if skip == 0:
        hit = _cached_subset(scan)
        if hit is not None:
            return hit
    gf = get_field(scan.w)
    mat = scan.total_mat.astype(gf.dtype)
    capped = False
    for attempt, subset in enumerate(combinations(scan.healthy, k)):
        if attempt < skip:
            continue
        if attempt >= skip + cap:
            capped = True
            break
        try:
            inv = invert_matrix(mat[list(subset)], gf)
            _remember_subset(scan, subset, inv)
            return list(subset), inv
        except SingularMatrixError:
            continue
    # Distinguish "search space exhausted" from "search cap hit": with the
    # cap hit, a later subset could still invert, so the archive is not
    # proven unrecoverable.
    if capped:
        raise UndecidedSubsetError(
            f"no decodable k={k} subset within candidate subsets "
            f"[{skip}, {skip + cap}) of healthy chunks {scan.healthy}; "
            "more combinations exist — this archive is not proven "
            "unrecoverable"
        )
    raise ValueError(
        f"no decodable k={k} subset among healthy chunks {scan.healthy}"
    )


def _select_subset_retrying(scan: _ChunkScan, attempts: int | None = None):
    """Surface the singular-minor retry discipline (ops/inverse.py's
    verify-and-fallback) at the subset level: on
    :class:`UndecidedSubsetError` keep searching the next candidate batch
    instead of propagating, up to ``RS_RETRY_SUBSET_ATTEMPTS`` batches of
    100 (bounded — the candidate space is combinatorial)."""
    cap = 100
    attempts = (
        max(1, _retry.int_env("RS_RETRY_SUBSET_ATTEMPTS", 3))
        if attempts is None else max(1, attempts)
    )
    last: UndecidedSubsetError | None = None
    for batch in range(attempts):
        try:
            return _select_decodable_subset(scan, cap=cap, skip=batch * cap)
        except UndecidedSubsetError as e:
            last = e
            _obs_metrics.counter(
                "rs_retries_total", "retry-policy outcomes"
            ).labels(outcome="subset_retry").inc()
    raise last


@_observed_file_op("auto_decode")
def auto_decode_file(
    in_file: str,
    output: str | None = None,
    *,
    conf_out: str | None = None,
    **decode_kwargs,
) -> str:
    """Decode without a hand-written conf: discover surviving chunks, drop
    corrupt ones, pick a decodable k-subset, and rebuild the file.

    The reference has no equivalent — its conf file is the (manual) fault
    model (unit-test.sh, SURVEY §4).  This automates the full self-healing
    flow the CRC32 extension enables:

    1. scan for ``_<i>_<name>`` chunk files next to ``in_file``;
    2. discard wrong-sized chunks, and (when .METADATA carries CRC lines)
       chunks whose bytes fail their checksum;
    3. choose k survivors, natives first (cheapest: partial recovery copies
       them through), falling back to other subsets if the selected
       submatrix is singular;
    4. write the chosen survivor list as a conf file (``conf_out``, default
       ``<in_file>.auto.conf`` — an auditable artifact in the reference's
       own format) and run :func:`decode_file` with it.

    Raises ValueError when fewer than k healthy chunks remain or no
    decodable subset exists.  ``decode_kwargs`` pass through to decode_file.

    Resilience (docs/RESILIENCE.md): this is the degraded-read entry
    point.  Survivors that fail AFTER the scan selected them — truncated
    or unlinked in the scan-to-decode window (TOCTOU), CRC-failing at
    read time, or erroring mid-stream past their retries — surface as
    :class:`ChunkIntegrityError`; this function excludes the named chunks,
    rescans, reselects a fresh subset and redecodes, up to
    ``RS_RETRY_RESELECT`` attempts.  The unselected healthy chunks are
    also handed to :func:`decode_file` as a fallback pool, so a
    *mid-stream* failure first tries an in-place survivor swap that
    resumes from the failed segment instead of restarting.  A subset
    search that hits its candidate cap (:class:`UndecidedSubsetError`)
    continues into the next candidate batches instead of propagating
    (``RS_RETRY_SUBSET_ATTEMPTS``).

    Integrity note: the scan CRC-verifies the chunks it selects, and the
    inner decode skips re-verification by default — corruption appearing in
    the scan-to-decode window (TOCTOU) is caught only when it changes a
    chunk's size or readability.  Callers needing end-to-end integrity on
    live-mutating storage should pass ``verify_checksums=True`` explicitly
    to re-check content at read time.
    """
    conf_path = conf_out or (in_file + ".auto.conf")
    procs = _mesh_processes(decode_kwargs.get("mesh"))
    if len(procs) > 1:
        # With a process-spanning mesh this is a collective: only the LEAD
        # scans (one CRC read of the archive, not one per host) and writes
        # the conf to the shared filesystem; peers wait at the barrier.
        # The scan verdict — ok or error — is broadcast before that
        # barrier so a lead-side failure (corrupt metadata, unrecoverable
        # archive) raises on every process instead of wedging the peers
        # until coordinator teardown.  No degraded retry loop here: a
        # mid-collective survivor swap would need its own barrier
        # choreography on every process.
        from jax.experimental import multihost_utils

        scan_err: Exception | None = None
        if _is_lead(procs):
            try:
                scan = _scan_chunks(
                    in_file,
                    decode_kwargs.get("segment_bytes", DEFAULT_SEGMENT_BYTES),
                )
                chosen, _ = _select_subset_retrying(scan)
                write_conf(
                    conf_path,
                    [os.path.basename(chunk_file_name(in_file, i))
                     for i in chosen],
                )
            except Exception as e:
                scan_err = e
        _broadcast_lead_verdict(
            scan_err, procs, "archive scan / survivor selection"
        )
        multihost_utils.sync_global_devices("rs_auto_conf_written")
        if decode_kwargs.get("verify_checksums") is None:
            decode_kwargs["verify_checksums"] = False
        return decode_file(in_file, conf_path, output, **decode_kwargs)

    attempts = max(1, _retry.int_env("RS_RETRY_RESELECT", 3) + 1)
    excluded: dict[int, str] = {}
    last: Exception | None = None
    locate_mode = _locate_mode()

    def _locate_kwargs() -> dict:
        out = {
            key: decode_kwargs[key]
            for key in ("strategy", "segment_bytes", "pipeline_depth",
                        "timer")
            if key in decode_kwargs
        }
        out["conf_out"] = conf_path
        return out

    for attempt in range(attempts):
        scan = _scan_chunks(
            in_file, decode_kwargs.get("segment_bytes", DEFAULT_SEGMENT_BYTES)
        )
        if excluded:
            scan = scan.excluding(excluded)
        # Escalation rung 0 — locate-first when CRC verification cannot
        # protect this decode: the archive carries NO checksum lines, so
        # silent bitrot would pass straight into the output.  (When CRC
        # lines exist, the _scan_chunks above already read and verified
        # every chunk — even under the caller's verify_checksums=False,
        # which only skips decode_file's SECOND pass — so rot cannot
        # reach the erasure decode and locate would be pure overhead.)
        # RS_LOCATE=force engages it unconditionally, RS_LOCATE=off
        # never.  Prerequisites (systematic matrix, erasures <= p,
        # non-empty archive) fall back to the erasure ladder below; a
        # transient locate failure falls back too — the erasure ladder
        # owns the retry/degraded machinery.
        crc_off = not scan.crcs
        if (
            attempt == 0
            and scan.total_size > 0
            and (locate_mode == "force"
                 or (locate_mode == "auto" and crc_off))
            and _locate_context(scan) is not None
        ):
            from .gf_decode import UnlocatableError

            try:
                return locate_decode_file(
                    in_file, output, _scan=scan, **_locate_kwargs()
                )
            except UnlocatableError:
                raise  # never fall back to a silently-wrong erasure decode
            except (ValueError, OSError) as e:
                # Anything else locate trips over (transient I/O, subset
                # search cap, foreign-metadata corners) belongs to the
                # erasure ladder below — it owns the retry/reselect
                # machinery and raises the canonical errors.
                _obs_tracing.instant(
                    "locate_fallback", lane="retry",
                    error=type(e).__name__,
                )
        chosen, _ = _select_subset_retrying(scan)
        write_conf(
            conf_path,
            [os.path.basename(chunk_file_name(in_file, i)) for i in chosen],
        )
        kwargs = dict(decode_kwargs)
        # The scan above already CRC-verified exactly the chunks it
        # selected — don't pay a second full read in decode_file unless
        # the caller explicitly demanded verification.
        if kwargs.get("verify_checksums") is None:
            kwargs["verify_checksums"] = False
        try:
            out = decode_file(
                in_file, conf_path, output,
                _fallback_rows=[i for i in scan.healthy if i not in chosen],
                **kwargs,
            )
        except (ChunkIntegrityError, FileNotFoundError) as e:
            last = e
            if isinstance(e, ChunkIntegrityError):
                excluded.update(e.bad_chunks)
                # Survivors that failed AFTER the scan selected them
                # (TOCTOU opens, mid-stream read errors) are damage the
                # scan's state map missed — feed them to the health
                # plane under their own event so the fleet model sees
                # decode-discovered loss too.
                _obs_health.record_damage(
                    "decode_failure", in_file,
                    chunks=sorted(e.bad_chunks),
                    k=scan.k, p=scan.p, w=scan.w,
                    generation=scan.generation,
                )
            if attempt + 1 >= attempts:
                # Escalation's final rung: the reselect loop is
                # exhausted — survivors keep failing under the erasure
                # model.  One error-locating attempt (fresh scan, all
                # present chunks, syndrome-verified corrections) before
                # giving up; its own failure re-raises the LADDER's
                # error, the actionable one.
                if locate_mode != "off":
                    rescan = _scan_chunks(
                        in_file,
                        decode_kwargs.get(
                            "segment_bytes", DEFAULT_SEGMENT_BYTES
                        ),
                    )
                    if _locate_context(rescan) is not None:
                        try:
                            out = locate_decode_file(
                                in_file, output, _scan=rescan,
                                **_locate_kwargs()
                            )
                        except (ValueError, OSError):
                            raise e
                        _obs_metrics.counter(
                            "rs_degraded_decodes_total",
                            "decodes completed after survivor "
                            "reselection",
                        ).labels(stage="locate").inc()
                        return out
                raise
            _obs_tracing.instant(
                "degraded_reselect", lane="retry", attempt=attempt + 1,
                error=type(e).__name__,
            )
            continue
        if attempt:
            _obs_metrics.counter(
                "rs_degraded_decodes_total",
                "decodes completed after survivor reselection",
            ).labels(stage="reselect").inc()
        return out
    raise last  # unreachable: the last attempt re-raises above


# -- error-locating decode (gf_decode/, docs/RESILIENCE.md) -------------------
#
# The escalation ladder's final rung: silent bitrot — corruption in a
# chunk that passes no CRC — is invisible to the erasure path, which
# would propagate it into the output.  The locate path reads ALL present
# chunks, computes parity-check syndromes per segment (a plan-cached
# GF-GEMM, codec.syndrome), solves the key equation for error locations
# + magnitudes (gf_decode/bw.py), patches the located symbols in place,
# and only then runs the normal inverse-GEMM reconstruction.  Columns
# whose damage exceeds t = floor((p - erasures)/2) raise
# UnlocatableError — never a silently wrong output.


def _locate_mode() -> str:
    """RS_LOCATE knob: ``auto`` (default — engage when CRC verification
    is unavailable/off), ``off`` (never), ``force`` (locate-first even
    with CRCs)."""
    v = os.environ.get("RS_LOCATE", "auto").strip().lower()
    if v in ("0", "off", "no", "false"):
        return "off"
    if v in ("1", "force", "always"):
        return "force"
    return "auto"


def _locate_context(scan: "_ChunkScan"):
    """A gf_decode.LocateContext for this scan, or None when the locate
    prerequisites don't hold (non-systematic foreign matrix, more
    erasures than parity, zero-size archive) — callers fall back to the
    erasure-only ladder."""
    from .gf_decode import LocateContext

    if scan.chunk == 0 or len(scan.healthy) < scan.k:
        return None
    try:
        return LocateContext(
            scan.total_mat, scan.k, scan.p, scan.w, scan.healthy
        )
    except ValueError:
        return None


def _count_syndrome_verdict(verdict: str) -> None:
    _obs_metrics.counter(
        "rs_syndrome_checks_total",
        "per-segment syndrome-check verdicts (error-locating decode)",
    ).labels(verdict=verdict).inc()


def _count_located(n: int, w: int) -> None:
    if n:
        _obs_metrics.counter(
            "rs_located_errors_total",
            "symbol errors located and corrected by syndrome decode",
        ).labels(w=w).inc(n)


def _locate_segment_fixes(ctx, codec, seg, seg_cols, sym, off, cols, timer,
                          want_packed: bool = False):
    """One segment's syndrome check: dispatch S = check @ seg through the
    plan cache, locate on host, return ``(fixes, packed)`` — the verified
    corrections dict (column -> [(chunk, magnitude)]) plus, with
    ``want_packed`` under ``strategy="xor"``, the segment's
    :class:`..ops.xor_gemm.PackedOperand` so the caller's recovery GEMM
    reuses the pack stage this syndrome dispatch already paid
    (docs/XOR.md "Packed-operand reuse"; None otherwise).  Raises
    gf_decode.UnlocatableError past the t bound (counted before it
    propagates)."""
    from .gf_decode import UnlocatableError

    if ctx.r == 0:
        _count_syndrome_verdict("no_headroom")
        return {}, None
    with timer.phase("syndrome dispatch"), _dispatch_span(
        "syndrome", off, cols
    ):
        staged = codec.stage_segment(
            seg, cap=seg_cols // sym, sym=sym
        )
        packed = codec.pack_operand(staged) if want_packed else None
        S = codec.syndrome(
            ctx.check, packed if packed is not None else staged
        )  # async
    with timer.phase("syndrome locate"):
        S_np = np.asarray(S).astype(np.int64)
        try:
            fixes = ctx.locate(S_np)
        except UnlocatableError:
            _count_syndrome_verdict("unlocatable")
            raise
    _count_syndrome_verdict("silent_bitrot" if fixes else "clean")
    _count_located(sum(len(v) for v in fixes.values()), ctx.w)
    return fixes, packed


def _syndrome_sweep(
    in_file: str,
    scan: "_ChunkScan",
    *,
    strategy: str = "auto",
    segment_bytes: int = DEFAULT_SEGMENT_BYTES,
    timer: PhaseTimer | None = None,
) -> tuple[str, set[int], int]:
    """Read-only syndrome pre-check over an archive's present chunks (the
    scrub half of the locate path — ``rs scrub --syndrome``).

    Returns ``(verdict, located_chunks, symbol_errors, complete)``;
    verdict is one of ``clean`` / ``silent_bitrot`` (with the rotten
    chunk indices attributed — no CRCs involved) / ``unlocatable``
    (damage beyond the t bound somewhere; the sweep stops at the first
    such segment, so ``complete`` is False and ``located_chunks`` covers
    only the segments checked up to that point — a PARTIAL attribution,
    each entry individually verified) / ``no_headroom`` (erasures
    consumed the check, or the metadata is foreign/non-systematic —
    nothing checkable)."""
    from . import native
    from .gf_decode import UnlocatableError

    timer = timer or PhaseTimer(enabled=False)
    ctx = _locate_context(scan)
    if ctx is None or ctx.r == 0:
        _count_syndrome_verdict("no_headroom")
        return "no_headroom", set(), 0, True
    codec = RSCodec(scan.k, scan.p, w=scan.w, strategy=strategy)
    sym = scan.w // 8
    seg_cols = _segment_cols(scan.chunk, scan.k, segment_bytes)
    paths = [chunk_file_name(in_file, i) for i in ctx.survivors]
    fps = [open(p_, "rb") for p_ in paths]
    maps = [np.memmap(p_, dtype=np.uint8, mode="r") for p_ in paths]
    located: set[int] = set()
    errors = 0
    try:
        def stage(off: int, cols: int) -> np.ndarray:
            def attempt() -> np.ndarray:
                _faults.on_reads(paths, ctx.survivors, scope="scrub")
                return native.gather_rows(fps, off, cols, fallback_maps=maps)

            with timer.phase("stage segment (io)"):
                return _retry.default_policy().call(
                    attempt, op="syndrome_stage"
                )

        with SegmentPrefetcher(
            _segment_spans(scan.chunk, seg_cols), stage, depth=2
        ) as prefetch:
            for (off, cols), seg in prefetch:
                try:
                    fixes, _ = _locate_segment_fixes(
                        ctx, codec, seg, seg_cols, sym, off, cols, timer
                    )
                except UnlocatableError:
                    return "unlocatable", located, errors, False
                for col_fixes in fixes.values():
                    for chunk_idx, _mag in col_fixes:
                        located.add(chunk_idx)
                    errors += len(col_fixes)
    finally:
        for fp in fps:
            fp.close()
    return (
        ("silent_bitrot" if located else "clean"), located, errors, True
    )


@_observed_file_op("locate_decode")
def locate_decode_file(
    in_file: str,
    output: str | None = None,
    *,
    strategy: str = "auto",
    segment_bytes: int = DEFAULT_SEGMENT_BYTES,
    pipeline_depth: int = 2,
    conf_out: str | None = None,
    timer: PhaseTimer | None = None,
    _scan: "_ChunkScan | None" = None,
) -> str:
    """Rebuild ``in_file`` with error-LOCATING decode (``rs decode
    --locate``): no conf and no CRCs needed — silent bitrot in up to
    ``t = floor((p - missing)/2)`` chunks per symbol column is found,
    attributed and corrected from the code's own redundancy before the
    normal inverse-GEMM reconstruction runs.

    Reads ALL present full-size chunks; missing/truncated ones are
    erasures (classical trade: 2·errors + erasures <= p per column).
    Raises :class:`gf_decode.UnlocatableError` when any column's damage
    exceeds the bound — the archive may be wrong in ways the code cannot
    pin down, and fabricating bytes is worse than failing.  Semantics,
    miscorrection bounds and knobs: docs/RESILIENCE.md "Error location".
    """
    from . import native
    from .ops.gf import get_field

    timer = timer or PhaseTimer(enabled=False)
    t_start = time.perf_counter()
    # ``_scan`` (private, supplied by auto_decode_file's escalation
    # rungs): reuse the ladder's fresh scan instead of re-reading — and
    # re-CRC-ing, on checksummed archives — the whole chunk set.
    if _scan is not None:
        scan = _scan
    else:
        with timer.phase("scan chunks (io)"):
            scan = _scan_chunks(in_file, segment_bytes)
    if scan.total_size == 0:
        # Zero-size foreign archive: same contract as decode_file.
        _select_decodable_subset(scan)
        return _write_empty_atomic(output or in_file)
    ctx = _locate_context(scan)
    if ctx is None:
        from .gf_decode import is_systematic

        if not is_systematic(scan.total_mat, scan.k):
            raise ValueError(
                f"{in_file!r}: error-locating decode needs a systematic "
                "total matrix; this archive's metadata is foreign — use "
                "the erasure decoder (rs -d --auto)"
            )
        raise ValueError(
            f"only {len(scan.healthy)} healthy chunks of the k={scan.k} "
            f"needed (corrupt: {sorted(scan.bad)}, missing: "
            f"{scan.missing}) — past erasure recovery, locate cannot help"
        )
    k, p, w = scan.k, scan.p, scan.w
    sym = w // 8
    chunk = scan.chunk
    seg_cols = _segment_cols(chunk, k, segment_bytes)
    codec = RSCodec(k, p, w=w, strategy=strategy)
    gf = get_field(w)

    # Recovery GEMM for natives lost to ERASURE (located errors are
    # patched in place, so present natives pass straight through).  With
    # no native missing — the dominant silent-bitrot case — there is
    # nothing to invert: the k natives themselves are the (trivially
    # decodable) survivor set, and the subset search would be dead work
    # whose UndecidedSubsetError corner could fail an otherwise
    # recoverable archive.
    missing = [i for i in range(k) if i not in set(ctx.survivors)]
    if missing:
        with timer.phase("invert matrix"):
            chosen, inv = _select_decodable_subset(scan)
        dec_missing = np.asarray(inv).astype(gf.dtype)[missing]
    else:
        chosen, dec_missing = list(range(k)), None
    row_of = {c: i for i, c in enumerate(ctx.survivors)}
    chosen_rows = [row_of[c] for c in chosen]
    rec_row = {i: j for j, i in enumerate(missing)}

    if conf_out:
        write_conf(
            conf_out,
            [os.path.basename(chunk_file_name(in_file, i)) for i in chosen],
        )

    out_path = output or in_file
    tmp_path = out_path + ".rs_tmp"
    paths = [chunk_file_name(in_file, i) for i in ctx.survivors]
    fps = [open(p_, "rb") for p_ in paths]
    maps = [np.memmap(p_, dtype=np.uint8, mode="r") for p_ in paths]
    try:
        out_fp = open(tmp_path, "wb")
    except BaseException:
        for fp in fps:
            fp.close()
        raise

    def write_row(i: int, off: int, cols: int, row_bytes: np.ndarray):
        lo = i * chunk + off
        if lo >= scan.total_size:
            return
        hi = min(lo + cols, scan.total_size)
        out_fp.seek(lo)
        out_fp.write(np.ascontiguousarray(row_bytes[: hi - lo]).tobytes())
        _obs_metrics.counter(
            "rs_io_write_bytes_total",
            "bytes write by the staging-I/O layer",
        ).labels(call="stream_write").inc(hi - lo)

    def stage(off: int, cols: int) -> np.ndarray:
        def attempt() -> np.ndarray:
            _faults.on_reads(paths, ctx.survivors)
            return native.gather_rows(fps, off, cols, fallback_maps=maps)

        with timer.phase("stage segment (io)"):
            return _retry.default_policy().call(attempt, op="locate_stage")

    try:
        from .gf_decode import correct_segment

        # Sequential segment loop (prefetch overlaps the reads): the
        # host-side locate between the syndrome GEMM and the recovery
        # GEMM is a true pipeline barrier — np.asarray(S) both fences
        # the async staging H2D (so the later in-place patch cannot race
        # it) and hands the solver concrete syndromes.  Robustness path,
        # not the hot path; the write-behind lanes stay with decode_file.
        with SegmentPrefetcher(
            _segment_spans(chunk, seg_cols), stage, depth=pipeline_depth
        ) as prefetch:
            for (off, cols), seg in prefetch:
                # Packed-domain reuse (docs/XOR.md): under strategy="xor"
                # the syndrome dispatch packs the full survivor stack
                # into bit-planes; the recovery GEMM below consumes the
                # SAME rows, so it selects its survivor subset's planes
                # from the returned handle instead of round-tripping
                # through byte domain and re-packing — the pack stage
                # (~60% of xor wall) runs once per segment, not twice.
                fixes, packed = _locate_segment_fixes(
                    ctx, codec, seg, seg_cols, sym, off, cols, timer,
                    want_packed=dec_missing is not None,
                )
                if fixes:
                    segv = seg.view(np.uint16) if sym == 2 else seg
                    correct_segment(segv, fixes, row_of)
                    # The planes pre-date the in-place patch: a corrected
                    # segment re-stages below so the recovery GEMM reads
                    # the patched bytes, never the stale planes.
                    packed = None
                rec_np = None
                if dec_missing is not None:
                    with timer.phase("locate dispatch"), _dispatch_span(
                        "decode", off, cols
                    ):
                        if packed is not None:
                            rec = codec.decode(
                                dec_missing, packed.select(chosen_rows)
                            )
                        else:
                            staged = codec.stage_segment(
                                np.ascontiguousarray(seg[chosen_rows]),
                                cap=seg_cols // sym, sym=sym,
                            )
                            rec = codec.decode(dec_missing, staged)
                    with timer.phase("decode compute"):
                        rec_np = np.asarray(rec)
                    if rec_np.dtype != np.uint8:
                        rec_np = np.ascontiguousarray(rec_np).view(np.uint8)
                with timer.phase("write output (io)"):
                    if scan.layout == "interleaved":
                        blk = np.empty((k, cols), dtype=np.uint8)
                        for i in range(k):
                            blk[i] = (
                                seg[row_of[i], :cols] if i in row_of
                                else rec_np[rec_row[i]][:cols]
                            )
                        _write_deinterleaved_block(
                            out_fp, off, cols, blk, sym, scan.total_size
                        )
                    else:
                        for i in range(k):
                            if i in row_of:
                                write_row(i, off, cols, seg[row_of[i]])
                        for i in missing:
                            write_row(i, off, cols, rec_np[rec_row[i]])
        out_fp.truncate(scan.total_size)
        out_fp.close()
        for fp in fps:
            fp.close()
        os.replace(tmp_path, out_path)
    except BaseException:
        if not out_fp.closed:
            out_fp.close()
        for fp in fps:
            if not fp.closed:
                fp.close()
        if os.path.exists(tmp_path):
            os.unlink(tmp_path)
        raise
    _obs_metrics.quantile(
        "rs_locate_decode_wall_seconds",
        "error-locating decode wall seconds (streaming quantiles)",
    ).observe(time.perf_counter() - t_start)
    return out_path


@_observed_file_op("repair")
def repair_file(
    in_file: str,
    *,
    strategy: str = "auto",
    segment_bytes: int = DEFAULT_SEGMENT_BYTES,
    pipeline_depth: int = 2,
    mesh=None,
    stripe_sharded: bool = False,
    timer: PhaseTimer | None = None,
) -> list[int]:
    """Regenerate every lost or corrupt chunk of an encode, in place.

    The reference can only restore the original *file* (decode.cu); a
    storage deployment also needs to heal the *archive* — rebuild missing
    chunk files, parity included, so future failures stay survivable.  Any
    target chunk row t is a GF-linear function of any decodable survivor
    set: ``row_t = T[t] . inv(T[surv])``, so all targets rebuild in ONE
    streamed GEMM over the survivors (natives and parity alike — no
    decode-then-re-encode double pass).

    Returns the list of chunk indices rebuilt ([] when the archive is
    already healthy).  Rebuilt chunks' CRC lines in .METADATA are refreshed
    when checksums are present.  Raises ValueError when fewer than k
    healthy chunks remain.

    Survivors come from the repair planner (:func:`_plan_repair`): where
    the total matrix has local groups (an LRC), a lost chunk whose group
    is otherwise healthy is rebuilt from that group alone; otherwise from
    k survivors whose submatrix inverts, as for any MDS code.

    With a ``mesh`` the rebuild GEMM fans out across devices exactly like
    encode/decode (archive repair is the same bulk-data shape — the
    reference runs its decode multi-GPU, decode.cu:335-378);
    ``stripe_sharded`` additionally shards the survivor/k axis.
    """
    timer = timer or PhaseTimer(enabled=False)
    if len(_mesh_processes(mesh)) > 1:
        return _count_repair_outcome(_repair_file_multiprocess(
            in_file, strategy=strategy, segment_bytes=segment_bytes,
            pipeline_depth=pipeline_depth, mesh=mesh,
            stripe_sharded=stripe_sharded, timer=timer,
        ), in_file)
    with timer.phase("scan chunks (io)"):
        scan = _scan_chunks(in_file, segment_bytes)
    targets = scan.unhealthy
    if not targets:
        return _count_repair_outcome([])
    if scan.chunk == 0:
        # Zero-size foreign archive: every chunk is the empty file, so
        # "rebuild" is recreating empties — no survivors read, no GEMM.
        # Still subject to the >=k-healthy contract (raises otherwise) so
        # repairability matches scan_file's decodable verdict: an archive
        # that cannot produce a valid k-chunk conf is not "repairable".
        _repair_select_or_fail(scan)
        for t in targets:
            _write_empty_atomic(chunk_file_name(in_file, t))
        if scan.crcs:
            rewrite_checksums(
                metadata_file_name(in_file),
                {**scan.crcs, **{t: 0 for t in targets}},  # crc32(b"") == 0
            )
        return _count_repair_outcome(targets, in_file, scan)
    with timer.phase("invert matrix"):
        chosen, rebuild_mat = _repair_select_or_fail(scan)
    return _count_repair_outcome(_repair_streamed(
        in_file, scan, chosen, rebuild_mat, strategy=strategy,
        segment_bytes=segment_bytes, pipeline_depth=pipeline_depth,
        mesh=mesh, stripe_sharded=stripe_sharded, timer=timer,
    ), in_file, scan)


def _count_repair_outcome(rebuilt: list[int], in_file: str | None = None,
                          scan: "_ChunkScan | None" = None) -> list[int]:
    """Count one archive's repair verdict (the scrub/repair loop's
    outcome series): ``rs_repair_outcomes_total{outcome}`` plus the
    rebuilt-chunk volume.  Identity on its argument so the return sites
    stay one-liners.  With ``in_file``, a non-empty rebuild also appends
    one ``rs_damage`` "repair" event so the health plane clears the
    rebuilt chunks from the archive's damage map."""
    _obs_metrics.counter(
        "rs_repair_outcomes_total", "archive repair outcomes"
    ).labels(outcome="rebuilt" if rebuilt else "healthy").inc()
    if rebuilt:
        _obs_metrics.counter(
            "rs_repair_chunks_rebuilt_total",
            "chunk files regenerated by repair",
        ).inc(len(rebuilt))
        if in_file is not None:
            _obs_health.record_damage(
                "repair", in_file, chunks=rebuilt,
                k=scan.k if scan else None, p=scan.p if scan else None,
                w=scan.w if scan else None,
                generation=scan.generation if scan else None,
            )
    return rebuilt


def _plan_repair(scan: "_ChunkScan") -> tuple[list[int], np.ndarray]:
    """The survivors a repair reads and the (targets x survivors) matrix
    that rebuilds every unhealthy chunk from them.

    Local plan: each target has a local group in the total matrix
    (:func:`.models.lrc.local_plans`, from the supports of its parity
    rows) whose other members are all healthy, and together the groups
    read fewer than k chunks: the union of the groups, each target's row
    holding its own group's coefficients.  Otherwise the global plan: k
    healthy chunks whose submatrix inverts
    (:func:`_select_decodable_subset`, subset cache included) and the
    rebuild rows ``T[targets] . inv``.  An MDS matrix has no local
    groups, so for it this is exactly the subset and matrix repair has
    always used.
    """
    from .ops.gf import get_field

    targets = scan.unhealthy
    with _obs_tracing.span("repair_plan", file=scan.in_file,
                           targets=len(targets)):
        plans = local_plans(scan.total_mat, scan.k, scan.w)
        local = [plans.get(t) for t in targets]
        chosen: list[int] = []
        if all(pl is not None and set(pl[0]) <= set(scan.healthy)
               for pl in local):
            chosen = sorted(set().union(*(pl[0] for pl in local)))
        if not chosen or len(chosen) >= scan.k:
            return _global_plan(scan, *_select_decodable_subset(scan))
        rebuild_mat = np.zeros((len(targets), len(chosen)),
                               get_field(scan.w).dtype)
        col = {c: j for j, c in enumerate(chosen)}
        for r, (survivors, coef) in enumerate(local):
            rebuild_mat[r, [col[c] for c in survivors]] = coef
    return chosen, rebuild_mat


def _global_plan(scan: "_ChunkScan", chosen, inv):
    """The global repair plan from k survivors and the inverse of their
    submatrix: ``(chosen, T[targets] . inv)``."""
    from .ops.gf import get_field

    gf = get_field(scan.w)
    inv = np.asarray(inv).astype(gf.dtype)
    rebuild_mat = gf.matmul(
        scan.total_mat.astype(gf.dtype)[scan.unhealthy], inv
    )  # (targets, k)
    return list(chosen), rebuild_mat


def _repair_select_or_fail(scan: "_ChunkScan"):
    """The repair plan of a single-archive repair (:func:`_plan_repair`),
    recording the failure to the health plane before it propagates: an
    archive repair cannot fix is the strongest at-risk signal the fleet
    model has (the repair-failure term in docs/HEALTH.md's risk
    formula)."""
    try:
        return _plan_repair(scan)
    except ValueError as e:
        _obs_health.record_damage(
            "repair_failed", scan.in_file,
            k=scan.k, p=scan.p, w=scan.w, generation=scan.generation,
            verdict="undecided" if isinstance(e, UndecidedSubsetError)
            else "unrecoverable",
        )
        raise


def _repair_streamed(
    in_file: str,
    scan: "_ChunkScan",
    chosen: list[int],
    rebuild_mat: np.ndarray,
    *,
    strategy: str,
    segment_bytes: int,
    pipeline_depth: int,
    mesh,
    stripe_sharded: bool,
    timer: PhaseTimer,
    fleet: FleetPipeline | None = None,
) -> list[int]:
    """The streaming rebuild half of :func:`repair_file`: given a completed
    scan, the survivors ``chosen`` and the (targets x survivors)
    ``rebuild_mat`` of a repair plan, regenerate every unhealthy chunk
    reading only ``chosen``.  Split out so :func:`repair_fleet` can supply
    inverses computed in one batched on-device dispatch — and, with
    ``fleet``, ride the fleet's shared write-behind lane: this archive's
    promote/checksum commit queues behind its writes while the caller
    already streams the next archive's reads and dispatches."""
    targets = scan.unhealthy
    codec = RSCodec(
        scan.k, scan.p, w=scan.w, strategy=strategy,
        mesh=mesh, stripe_sharded=stripe_sharded,
    )
    sym = scan.w // 8
    chunk = scan.chunk
    seg_cols = _segment_cols(chunk, len(chosen), segment_bytes)
    # A plan that reads fewer than k survivors is a local one.
    _obs_metrics.counter(
        "rs_repair_plans_total",
        "chunks rebuilt by plan: local (< k survivors) or global (k)",
    ).labels(
        plan="local" if len(chosen) < scan.k else "global"
    ).inc(len(targets))
    _obs_metrics.counter(
        "rs_repair_survivors_read_total",
        "survivor chunk files read by streamed rebuilds",
    ).inc(len(chosen))

    from . import native

    surv_fps = [open(chunk_file_name(in_file, i), "rb") for i in chosen]
    surv_maps = [
        np.memmap(chunk_file_name(in_file, i), dtype=np.uint8, mode="r")
        for i in chosen
    ]
    # Rebuild into temp files; atomically swap in only when every segment
    # landed (a failed repair must not destroy a corrupt-but-present chunk:
    # its surviving bytes may still matter to a different recovery tool).
    tmp_paths = {t: chunk_file_name(in_file, t) + ".rs_tmp" for t in targets}
    out_fps = {t: open(tmp_paths[t], "wb") for t in targets}
    new_crcs: dict[int, int] = {}

    def drain(tag, rebuilt):
        off, cols = tag
        with timer.phase("repair compute"):
            reb = np.asarray(rebuilt)
        if reb.dtype != np.uint8:
            reb = np.ascontiguousarray(reb).view(np.uint8)
        # CRC advance committed only after the write lands — the writer
        # lane may retry this whole drain (see _drain_parity).
        delta = None
        if scan.crcs:
            with _obs_tracing.span("crc", lane="crc"):
                delta = {t: crc32_of(reb[j], new_crcs.get(t, 0))
                         for j, t in enumerate(targets)}
        with timer.phase("write chunks (io)"):
            native.scatter_write([out_fps[t] for t in targets], reb, off)
        if delta is not None:
            new_crcs.update(delta)

    surv_paths = [chunk_file_name(in_file, i) for i in chosen]

    def stage(off: int, cols: int) -> np.ndarray:
        # On the prefetch worker: survivor reads overlap rebuilt-chunk
        # writes.  Resilience read boundary (fault hook + transient-retry
        # into a fresh buffer), like the decode stage.
        def attempt() -> np.ndarray:
            _faults.on_reads(surv_paths, chosen)
            return native.gather_rows(
                surv_fps, off, cols, fallback_maps=surv_maps
            )

        with timer.phase("stage segment (io)"):
            return _retry.default_policy().call(attempt, op="repair_stage")

    def finalize() -> None:
        # Promote only after every rebuilt segment landed: standalone this
        # runs after the drain barrier; in a fleet it queues on the ordered
        # writer lane behind this archive's writes.
        for t in targets:
            out_fps[t].close()
        for fp in surv_fps:
            fp.close()
        for t in targets:
            os.replace(tmp_paths[t], chunk_file_name(in_file, t))
        if scan.crcs:
            with timer.phase("write metadata (io)"):
                rewrite_checksums(
                    metadata_file_name(in_file), {**scan.crcs, **new_crcs}
                )

    def cleanup() -> None:
        for fp in surv_fps:
            if not fp.closed:
                fp.close()
        for t, fp in out_fps.items():
            if not fp.closed:
                fp.close()
            if os.path.exists(tmp_paths[t]):
                os.unlink(tmp_paths[t])

    key = fleet.register(cleanup) if fleet is not None else None
    try:
        # Ordered write-behind lane: scatter_write's no-toolchain fallback
        # shares fp positions and the incremental CRC needs column order.
        with SegmentPrefetcher(
            _segment_spans(chunk, seg_cols), stage, depth=pipeline_depth
        ) as prefetch, _drain_ctx(fleet) as dex, AsyncWindow(
            pipeline_depth, drain, executor=dex
        ) as window:
            staging = _staging_ring(
                prefetch, codec, seg_cols, sym, pipeline_depth
            )
            for (off, cols), seg in staging:
                with timer.phase("repair dispatch"), _dispatch_span(
                    "repair", off, cols
                ):
                    rebuilt = codec.decode(rebuild_mat, seg)  # async GEMM
                window.push((off, cols), rebuilt)
        if fleet is not None:
            fleet.commit(key, finalize)
        else:
            finalize()
    except BaseException:
        if fleet is None:
            cleanup()
        raise
    return targets


def _repair_file_multiprocess(
    in_file: str,
    *,
    strategy: str,
    segment_bytes: int,
    pipeline_depth: int,
    mesh,
    stripe_sharded: bool = False,
    timer: PhaseTimer,
) -> list[int]:
    """Multi-host archive repair over a process-spanning mesh (collective).

    The lead process scans chunk health (the CRC pass reads every present
    chunk once — doing it on all hosts would multiply that IO) and
    broadcasts the per-chunk state, so every process derives the same
    survivor subset and rebuild matrix deterministically.  The rebuild GEMM
    then streams exactly like multi-process encode: each host stages its
    block of the survivors (column span; survivor-row span too under
    ``stripe_sharded``, the wide-stripe composition), and pwrites its
    addressable shards of every rebuilt chunk into lead-pre-sized
    shared-filesystem temps that the lead atomically promotes — under
    stripe sharding the rebuilt output is psum-replicated, so stripe-row-0
    hosts write it.  Requirements: shared filesystem, w=8 or w=16.
    """
    import jax
    from jax.experimental import multihost_utils

    from .ops.gf import get_field
    from .parallel.mesh import COLS
    from .parallel.sharded import put_sharded, sharded_gf_matmul

    procs = _mesh_processes(mesh)
    lead = _is_lead(procs)

    # Health state: lead scans (CRC IO once, not once per host), peers get
    # the verdict as a (k+p,) array: 0 = missing, 1 = healthy, 2 = damaged.
    with timer.phase("scan chunks (io)"):
        meta = metadata_file_name(in_file)
        meta_obj = read_archive_meta(meta)
        total_size, p, k = (
            meta_obj.total_size, meta_obj.parity_num, meta_obj.native_num
        )
        total_mat, w, crcs = meta_obj.total_mat, meta_obj.w, meta_obj.crcs
        _check_gfwidth(w, meta)
        sym = w // 8
        if total_mat is None:
            total_mat = _regenerate_total_matrix(p, k, w)
        state = np.zeros(k + p, dtype=np.int32)
        scan_err: Exception | None = None
        if lead:
            # A lead-side scan failure must reach the peers as an error,
            # not leave them wedged at the broadcast: sentinel the whole
            # state array (-1 is outside the 0/1/2 health encoding), then
            # raise in lockstep after the collective.
            try:
                scan = _scan_chunks(in_file, segment_bytes)
                state[scan.healthy] = 1
                state[sorted(scan.bad)] = 2
            except Exception as e:
                scan_err = e
                state[:] = -1
        state = np.asarray(
            multihost_utils.broadcast_one_to_all(state, is_source=lead)
        )
        if (state < 0).any():
            if scan_err is not None:
                raise scan_err
            raise RuntimeError(
                "chunk scan failed on the lead process "
                f"(process {procs[0]}); see its log for the cause"
            )
    healthy = [int(i) for i in np.flatnonzero(state == 1)]
    bad = {
        int(i): chunk_file_name(in_file, int(i))
        for i in np.flatnonzero(state == 2)
    }
    # Repair is chunk-layout-agnostic (column-wise linear algebra over
    # whole chunk files) — only the expected chunk LENGTH differs.
    chunk = chunk_size_for_layout(total_size, k, sym, meta_obj.layout)
    scan_view = _ChunkScan(
        in_file, total_size, p, k, total_mat, w, crcs, chunk, healthy,
        bad, layout=meta_obj.layout, generation=meta_obj.generation,
    )
    targets = scan_view.unhealthy
    if not targets:
        return []
    if chunk == 0:
        # Zero-size foreign archive (see repair_file): the lead recreates
        # the empty chunks; all processes leave in lockstep.  Same
        # >=k-healthy contract as the general path (raises everywhere —
        # all processes share the broadcast health state).
        _select_decodable_subset(scan_view)
        if lead:
            for t in targets:
                _write_empty_atomic(chunk_file_name(in_file, t))
            if crcs:
                rewrite_checksums(
                    meta, {**crcs, **{t: 0 for t in targets}}
                )
        multihost_utils.sync_global_devices("rs_repair_promoted")
        return targets

    with timer.phase("invert matrix"):
        chosen, inv = _select_decodable_subset(scan_view)
        gf = get_field(w)
        mat = total_mat.astype(gf.dtype)
        rebuild_mat = gf.matmul(mat[targets], inv)  # (targets, k)

    codec = RSCodec(
        k, p, w=w, strategy=strategy, mesh=mesh,
        stripe_sharded=stripe_sharded,
    )
    seg_cols = _segment_cols(chunk, k, segment_bytes)
    cols_size = mesh.shape[COLS]
    in_sharding, writes_output = _stripe_io_roles(mesh, stripe_sharded)
    tmp_paths = {t: chunk_file_name(in_file, t) + ".rs_tmp" for t in targets}
    new_crcs: dict[int, int] = {}

    try:
        if lead:
            for t in targets:
                with open(tmp_paths[t], "wb") as fp:
                    fp.truncate(chunk)
        multihost_utils.sync_global_devices("rs_repair_tmps_created")

        surv_fps = [
            open(chunk_file_name(in_file, i), "rb") for i in chosen
        ]
        surv_maps = [
            np.memmap(chunk_file_name(in_file, i), dtype=np.uint8, mode="r")
            for i in chosen
        ]
        out_fps = {t: open(tmp_paths[t], "r+b") for t in targets}
        try:
            stage = _make_padded_stage(
                surv_fps, surv_maps, chunk, cols_size, in_sharding, k,
                timer, sym,
            )

            def drain(tag, rebuilt_sharded) -> None:
                off, cols = tag
                if not writes_output:
                    with timer.phase("repair compute"):
                        jax.block_until_ready(rebuilt_sharded)
                    return
                with timer.phase("repair compute"):
                    shards = _trimmed_shards(rebuilt_sharded, cols, sym)
                with timer.phase("write chunks (io)"):
                    for col0, data in shards:
                        for j, t in enumerate(targets):
                            os.pwrite(
                                out_fps[t].fileno(),
                                data[j].tobytes(),
                                off + col0,
                            )

            # Out-of-order write-behind (offset-addressed pwrites into the
            # lead-pre-sized temps; CRCs recomputed from files afterwards).
            with SegmentPrefetcher(
                _segment_spans(chunk, seg_cols), stage, depth=pipeline_depth
            ) as prefetch, _drain_ctx(None, ordered=False) as dex, AsyncWindow(
                pipeline_depth, drain, executor=dex
            ) as window:
                for (off, cols), local_seg in prefetch:
                    with timer.phase("repair dispatch"), _dispatch_span(
                        "repair", off, cols
                    ):
                        Bd = put_sharded(local_seg, mesh, stripe_sharded)
                        rebuilt = sharded_gf_matmul(
                            np.asarray(rebuild_mat), Bd,
                            mesh=mesh, w=w, strategy=codec.strategy,
                            stripe_sharded=stripe_sharded,
                        )
                    window.push((off, cols), rebuilt)
        finally:
            for fp in surv_fps:
                fp.close()
            for fp in out_fps.values():
                fp.close()
        multihost_utils.sync_global_devices("rs_repair_written")

        if lead:
            if crcs:
                with timer.phase("write metadata (io)"):
                    for t in targets:
                        mm = np.memmap(tmp_paths[t], dtype=np.uint8, mode="r")
                        new_crcs[t] = chunk_crc32(mm, chunk, segment_bytes)
            for t in targets:
                os.replace(tmp_paths[t], chunk_file_name(in_file, t))
            if crcs:
                with timer.phase("write metadata (io)"):
                    rewrite_checksums(meta, {**crcs, **new_crcs})
    except BaseException:
        _unlink_shared_tmps(tmp_paths.values())
        raise
    multihost_utils.sync_global_devices("rs_repair_promoted")
    return targets


@_observed_file_op("repair_fleet")
def repair_fleet(
    files,
    *,
    strategy: str = "auto",
    segment_bytes: int = DEFAULT_SEGMENT_BYTES,
    pipeline_depth: int = 2,
    timer: PhaseTimer | None = None,
) -> dict[str, list[int]]:
    """Scrub-and-repair many archives in one pass (fleet scrubbing).

    The reference's dormant GPU inverter (matrix.cu:667-744) and its
    blocked-inversion experiment (decode-gj.cu:1059-1201) pointed at
    putting matrix inversion on the device; the shape where that
    parallelism actually occurs in a storage system is ACROSS archives — a
    periodic scrub finds many damaged archives, each needing its own
    survivor-subset inverse.  This entry point scans every archive, solves
    all the k x k survivor inversions of each (k, w) config in ONE batched
    on-device dispatch (:func:`.ops.inverse.invert_matrix_jax_batch`),
    host-verifies each inverse with a single GF matmul (falling back to
    the host inverter on any mismatch or singular flag), then streams each
    archive's rebuild exactly like :func:`repair_file`.

    All-or-nothing validation: every archive is scanned and its inverse
    solved BEFORE any rebuild is written; if any archive is unscannable or
    unrecoverable, raises ValueError naming every such archive and repairs
    nothing.  Single-host (no mesh): fleet parallelism batches the
    inversions, and the per-archive rebuild pipelines *interleave* through
    one shared write-behind lane (parallel/io_executor.py): archive j+1's
    survivor reads and GEMM dispatches overlap archive j's D2H + chunk
    writes, with each archive's promote/CRC commit queued behind its own
    writes.  The shared plan cache means the interleave adds zero
    compiles; ``RS_IO_WRITERS=0`` restores the fully sequential rebuild.

    Returns ``{file: [rebuilt chunk indices]}`` ([] for healthy archives).
    """
    from .ops.gf import get_field
    from .ops.inverse import invert_matrix_jax_batch, mds_nopivot_order

    timer = timer or PhaseTimer(enabled=False)
    files = list(files)
    errors: dict[str, str] = {}
    with timer.phase("scan chunks (io)"):
        scans: dict[str, _ChunkScan] = {}
        for f in files:
            try:
                scans[f] = _scan_chunks(f, segment_bytes)
            except Exception as e:
                errors[f] = f"{type(e).__name__}: {e}"
    # First-choice survivor subsets, grouped by (k, w) so each group is one
    # stacked (b, k, k) inversion dispatch.  ``healthy`` is in chunk-index
    # order, so healthy[:k] is exactly the natives-first candidate
    # _select_decodable_subset would try first (the near-always-invertible
    # common case for Vandermonde/Cauchy).
    chosen_inv: dict[str, tuple[list[int], np.ndarray]] = {}
    plans: dict[str, tuple[list[int], np.ndarray]] = {}
    groups: dict[tuple[int, int], list[str]] = {}
    for f, s in scans.items():
        if not s.unhealthy:
            continue
        if s.chunk and local_plans(s.total_mat, s.k, s.w):
            # A code with local groups (LRC): the planner reads a group
            # where it can, and inverts its own k x k where it cannot.
            try:
                plans[f] = _plan_repair(s)
            except ValueError as e:
                errors[f] = str(e)
            continue
        if s.chunk == 0:
            # Zero-size archives skip inversion but NOT validation: an
            # unrecoverable one must surface here, before any rebuild (the
            # all-or-nothing contract), with the same >=k-healthy rule
            # repair_file applies.
            try:
                _select_decodable_subset(s)
            except ValueError as e:
                errors[f] = str(e)
            continue
        if len(s.healthy) < s.k:
            errors[f] = (
                f"only {len(s.healthy)} healthy chunks of the k={s.k} needed "
                f"(corrupt: {sorted(s.bad)}, missing: {s.missing})"
            )
            continue
        # Re-pass reuse: a fleet sweeping the same archives (the scrub ->
        # repair loop) skips the batched inversion dispatch for every
        # archive whose pinned subset is still healthy at this generation.
        hit = _cached_subset(s)
        if hit is not None:
            chosen_inv[f] = hit
            continue
        groups.setdefault((s.k, s.w), []).append(f)
    with timer.phase("invert matrices (batched)"):
        from .utils.backend import tpu_devices_present

        for (k, w), group in groups.items():
            gf = get_field(w)
            min_batch = _device_invert_min_batch_tpu(k)
            if tpu_devices_present() and (
                min_batch is None or len(group) < min_batch
            ):
                # Measured routing — see _device_invert_min_batch_tpu for
                # the k x batch grid and its capture citation.
                for f in group:
                    try:
                        chosen_inv[f] = _select_decodable_subset(scans[f])
                    except ValueError as e:
                        errors[f] = str(e)
                continue
            # Scan-free elimination (pivot=False): with each surviving
            # native's identity row placed AT its own position
            # (mds_nopivot_order), pivoting is only ever needed inside the
            # tiny parity Schur complement — rare, flagged by ok=False,
            # and re-solved through the host search below.  Every inverse
            # is verified before use either way.  On TPU the no-pivot
            # times are indistinguishable from the pivoting ones
            # (inverse_nopivot_tpu_20260801T*: the elimination scan, not
            # the pivot search, is the cost), so this stays the dispatch
            # for its CPU win (1.25x, builder smoke) and simpler kernel.
            ordered = {
                f: mds_nopivot_order(scans[f].healthy[:k], k) for f in group
            }
            subs = [
                scans[f].total_mat[ordered[f]].astype(gf.dtype)
                for f in group
            ]
            invs, oks = invert_matrix_jax_batch(np.stack(subs), w, pivot=False)
            invs = np.asarray(invs).astype(gf.dtype)
            oks = np.asarray(oks)
            eye = np.eye(k, dtype=gf.dtype)
            for j, f in enumerate(group):
                verified = bool(oks[j]) and np.array_equal(
                    gf.matmul(subs[j], invs[j]), eye
                )
                if verified:
                    chosen_inv[f] = (ordered[f], invs[j])
                    _remember_subset(scans[f], ordered[f], invs[j])
                    continue
                # Singular first candidate (or a device-inverse mismatch —
                # never observed, but a wrong inverse must not write wrong
                # chunk bytes): the host search tries the other subsets.
                try:
                    chosen_inv[f] = _select_decodable_subset(scans[f])
                except ValueError as e:
                    errors[f] = str(e)
    if errors:
        _obs_metrics.counter(
            "rs_repair_outcomes_total", "archive repair outcomes"
        ).labels(outcome="unrecoverable").inc(len(errors))
        for f in sorted(errors):
            s = scans[f]
            _obs_health.record_damage(
                "repair_failed", f, k=s.k, p=s.p, w=s.w,
                generation=s.generation, verdict="unrecoverable",
            )
        raise ValueError(
            "unrecoverable archives (nothing repaired): "
            + "; ".join(f"{f}: {msg}" for f, msg in sorted(errors.items()))
        )
    for f, (chosen, inv) in chosen_inv.items():
        plans[f] = _global_plan(scans[f], chosen, inv)
    # Fleet scheduler: one shared ordered write-behind lane; each archive
    # commits behind its own writes while the next archive's reads and
    # dispatches already stream on this thread.
    results: dict[str, list[int]] = {}
    with _fleet_lane() as pipe:
        for f in files:
            s = scans[f]
            if not s.unhealthy:
                results[f] = _count_repair_outcome([])
            elif s.chunk == 0:
                # Zero-size archives take repair_file's empty-rebuild
                # path (no streamed writes to overlap).
                results[f] = repair_file(
                    f, strategy=strategy, segment_bytes=segment_bytes,
                    pipeline_depth=pipeline_depth, timer=timer,
                )
            else:
                chosen, rebuild_mat = plans[f]
                results[f] = _count_repair_outcome(_repair_streamed(
                    f, s, chosen, rebuild_mat, strategy=strategy,
                    segment_bytes=segment_bytes,
                    pipeline_depth=pipeline_depth,
                    mesh=None, stripe_sharded=False, timer=timer,
                    fleet=pipe,
                ), f, s)
    return results


# -- partial-stripe updates and append-mode encoding (update/) ---------------
#
# RS linearity: parity' = parity ⊕ E·Δ, so a byte-range edit moves only
# its touched segment columns, and an append (interleaved layout) only
# the tail column block — docs/UPDATE.md.  Both ops are crash-atomic:
# undo journal before any in-place byte, atomic generation-bumping
# .METADATA rewrite as the commit point, rollback on failure or at the
# next open (recover_archive).


@_observed_file_op("update")
def update_file(
    file_name: str,
    at: int,
    data=None,
    *,
    src: str | None = None,
    strategy: str = "auto",
    segment_bytes: int = DEFAULT_SEGMENT_BYTES,
    timer: PhaseTimer | None = None,
) -> dict:
    """Overwrite bytes [at, at+len) of the archived file in place —
    ``rs update ARCHIVE --at OFF --in DELTA``.

    Only the affected segment columns are read and rewritten: Δ = new ⊕
    old per touched native column, ``E·Δ`` dispatched as a plan-cached
    GF-GEMM (op="update" — it reuses the warm encode executable), parity
    XOR-patched through an ordered pwrite lane, per-chunk CRC lines fixed
    by seekable crc32-combine (no full-chunk re-hash), and the metadata
    committed atomically with a generation bump.  Pass the new bytes as
    ``data`` or a file path as ``src``.  Returns the op summary dict
    (bytes, segments, chunks_touched, generation).  Works on both chunk
    layouts; requires the touched chunks healthy (repair first
    otherwise).
    """
    from .update import apply_update

    out = apply_update(
        file_name, at, data, src=src, strategy=strategy,
        segment_bytes=segment_bytes, timer=timer,
    )
    # Generation moved past the last verified scrub: the health plane
    # treats the archive as scrub-stale until it is re-scanned.
    _obs_health.record_damage(
        "update", file_name, generation=out.get("generation"),
    )
    return out


@_observed_file_op("append")
def append_file(
    file_name: str,
    data=None,
    *,
    src: str | None = None,
    strategy: str = "auto",
    segment_bytes: int = DEFAULT_SEGMENT_BYTES,
    timer: PhaseTimer | None = None,
) -> dict:
    """Grow the archived file by the payload bytes — ``rs append ARCHIVE
    --in DATA``.

    Interleaved-layout archives (``rs -e ... --layout interleaved``)
    extend every chunk by just the tail column block: cold columns are
    never read or written, and only the tail segment's parity is
    regenerated.  Row-layout (reference) archives accept appends bounded
    by their tail-padding slack (a larger chunk size would re-stripe the
    whole file).  Torn appends are detected and rolled back at the next
    open (undo journal + metadata generation).  Returns the op summary
    dict with the new ``total_size``.
    """
    from .update import apply_append

    out = apply_append(
        file_name, data, src=src, strategy=strategy,
        segment_bytes=segment_bytes, timer=timer,
    )
    _obs_health.record_damage(
        "update", file_name, generation=out.get("generation"),
    )
    return out


@_observed_file_op("update_many")
def update_file_many(
    file_name: str,
    edits,
    *,
    strategy: str = "auto",
    segment_bytes: int = DEFAULT_SEGMENT_BYTES,
    timer: PhaseTimer | None = None,
    group_edits: int | None = None,
    group_tag: str | None = None,
    stage_hook=None,
) -> dict:
    """Apply an ORDERED batch of edits/appends to one archive under
    group commit — ``rs update ARCHIVE --edits FILE`` and the daemon's
    ``/update`` write combining (docs/UPDATE.md "Group commit").

    ``edits`` is a list of dicts: ``{"op": "update", "at": OFF,
    "data": bytes | "src": path}`` or ``{"op": "append", "data"/"src":
    ...}``.  Semantically byte-identical to applying the batch one
    :func:`update_file` / :func:`append_file` call at a time (later
    edits win overlapping bytes; an edit may target bytes an earlier
    append in the same batch created) — but the batch merges into
    touched column windows with ONE stacked ``E·Δ`` GEMM per window
    block, and commits under ONE journal fsync chain, ONE ``.METADATA``
    rewrite and ONE generation bump per window group (all-or-nothing:
    a torn group rolls back every edit via the journal; no edit is
    acknowledged before its group is durable).  ``RS_UPDATE_GROUP_WINDOW``
    caps edits per group (larger batches split into consecutive groups);
    ``group_edits`` overrides it for this call — pass ``len(edits)`` to
    force the whole batch into ONE all-or-nothing group.  Returns the
    aggregate summary dict (``edits``, ``groups``, ``windows``,
    ``segments``, ``chunks_touched``, ``total_size``, ``generation``).
    ``group_tag`` / ``stage_hook`` are the daemon write combiner's
    lifecycle joins (group id in span + summary; ``device_done`` stage
    callback — update/group.py).
    """
    from .update import apply_update_many

    out = apply_update_many(
        file_name, edits, strategy=strategy,
        segment_bytes=segment_bytes, timer=timer, group_edits=group_edits,
        group_tag=group_tag, stage_hook=stage_hook,
    )
    _obs_health.record_damage(
        "update", file_name, generation=out.get("generation"),
    )
    return out


def recover_archive(file_name: str) -> str:
    """Resolve a pending update/append journal next to ``file_name``
    (run automatically at the top of every update/append; exposed for
    ``rs update --recover`` and post-crash decode hygiene).  Returns
    ``none`` / ``stale_discarded`` / ``invalid_discarded`` /
    ``rolled_back``."""
    from .update import recover

    return recover(file_name)


# -- object-store façade (store/) ---------------------------------------------
#
# Many small objects share erasure-coded stripe archives instead of
# paying per-object metadata/chunks/journal (docs/STORE.md): a durable
# object index maps key -> (archive, byte range, CRC32), committed
# crash-atomically alongside the archive metadata it references.  PUT
# rides the group-commit append lane, GET decodes only the object's
# touched column windows, DELETE tombstones + zeroes via delta-parity,
# and compaction retires dead-heavy archives all-or-nothing.


@_observed_file_op("object_put")
def put_object(
    root: str,
    bucket: str,
    key: str,
    data=None,
    *,
    src: str | None = None,
    create: bool = True,
    k: int | None = None,
    p: int | None = None,
    w: int | None = None,
    stripe_bytes: int | None = None,
) -> dict:
    """Store one object under ``key`` in ``bucket`` — ``rs object put``.

    The payload comes as ``data`` bytes or a ``src`` file path.  The
    bucket is created on first use (``create=False`` refuses instead);
    the shape knobs apply only at creation — an existing bucket's
    manifest wins.  Returns the location dict (``arc``, ``at``, ``len``,
    ``crc``, ``gen``).  For PUT bursts, :func:`put_objects` commits the
    whole batch under ONE group-committed stripe append + ONE index
    fsync (the daemon's ``/o/`` write combining calls it)."""
    if (data is None) == (src is None):
        raise ValueError("pass exactly one of data= or src=")
    if src is not None:
        with open(src, "rb") as fp:
            data = fp.read()
    return put_objects(root, bucket, [(key, data)],
                       create=create, k=k, p=p, w=w,
                       stripe_bytes=stripe_bytes)[0]


def put_objects(
    root: str,
    bucket: str,
    items,
    *,
    create: bool = True,
    k: int | None = None,
    p: int | None = None,
    w: int | None = None,
    stripe_bytes: int | None = None,
) -> list[dict]:
    """Batch PUT: an ordered list of ``(key, bytes)`` committed as one
    group (one journal fsync chain, one metadata rewrite, one index
    fsync) — all-or-nothing; later duplicates win."""
    from . import store as _store

    b = _store.open_bucket(root, bucket, create=create, k=k, p=p, w=w,
                           stripe_bytes=stripe_bytes)
    return b.put_many(items)


@_observed_file_op("object_get")
def get_object(root: str, bucket: str, key: str) -> bytes:
    """Read one object's bytes — ``rs object get``.  Reconstructs ONLY
    the object's byte range (touched column windows; degraded decode
    when a native chunk is damaged), verified against the object's own
    CRC32 from the index — never silently wrong."""
    from . import store as _store

    return _store.open_bucket(root, bucket).get(key)


@_observed_file_op("object_delete")
def delete_object(root: str, bucket: str, key: str) -> dict:
    """Delete one object — ``rs object rm``: durable tombstone first
    (the commit point), then the dead range is zeroed through the
    delta-parity patch lane; space returns at the next compaction."""
    from . import store as _store

    return _store.open_bucket(root, bucket).delete(key)


def list_objects(root: str, bucket: str, *,
                 prefix: str = "") -> list[dict]:
    """Live objects in the bucket (tombstoned keys excluded), sorted by
    key — ``rs object ls``.  ``prefix`` narrows to keys starting with
    it; for bounded pages over a huge bucket use
    :func:`list_objects_page`."""
    from . import store as _store

    return _store.open_bucket(root, bucket).list_objects(prefix=prefix)


def list_objects_page(root: str, bucket: str, *, prefix: str = "",
                      limit: int = 0, cursor: str | None = None) -> dict:
    """One bounded page of live objects — ``rs object ls --limit``:
    ``{"objects", "truncated", "next"}`` where ``next`` is the opaque
    cursor resuming after the page's last key (None on the final
    page).  ``limit <= 0`` uses ``RS_STORE_LIST_LIMIT`` semantics from
    the caller (here: no bound)."""
    from . import store as _store

    return _store.open_bucket(root, bucket).list_page(
        prefix=prefix, limit=limit, cursor=cursor)


def stat_object(root: str, bucket: str, key: str) -> dict:
    """One object's index entry (archive, range, CRC, generation pin)
    — ``rs object stat``."""
    from . import store as _store

    return _store.open_bucket(root, bucket).stat(key)


@_observed_file_op("object_compact")
def compact_bucket(root: str, bucket: str, *, force: bool = False) -> dict:
    """Rewrite live objects out of dead-heavy sealed archives and
    retire them all-or-nothing — ``rs object compact``
    (``RS_STORE_COMPACT_DEAD_FRAC`` sets the trigger; ``force=True``
    compacts any sealed archive with dead bytes)."""
    from . import store as _store

    return _store.open_bucket(root, bucket).compact(force=force)


@_observed_file_op("scan")
def scan_file(
    in_file: str,
    *,
    segment_bytes: int = DEFAULT_SEGMENT_BYTES,
    syndrome: bool = False,
) -> dict:
    """Read-only archive health report (the scrubbing half of repair).

    Returns ``{"k", "p", "w", "checksummed", "healthy", "corrupt",
    "missing", "decodable"}`` — ``corrupt`` lists present-but-damaged
    chunks (truncated or CRC-failing), ``missing`` absent ones, and
    ``decodable`` means the original file can be rebuilt (>= k healthy
    chunks with an invertible subset) — which equally means every damaged
    chunk is repairable.  ``decodable`` is tri-state: ``True`` / ``False``
    / ``"unknown"`` when the subset search hit its cap without a verdict
    (only reachable with pathological non-MDS matrices).

    ``syndrome=True`` (``rs scrub --syndrome``) adds the error-locating
    pre-check: parity-check syndromes over every present chunk attribute
    SILENT bitrot — corruption no size check and no CRC would catch — to
    its chunk index (``state="silent_bitrot"``), without reading a single
    checksum.  Located chunks are demoted from ``healthy`` into
    ``corrupt`` and ``decodable`` is re-derived; a verdict of
    ``unlocatable`` (per-column damage beyond t = floor((p-missing)/2))
    degrades ``decodable`` to ``"unknown"`` — the erasure math could
    still rebuild *bytes*, but nothing proves they'd be the right ones.
    The report gains ``{"syndrome": {"verdict", "silent_bitrot",
    "symbol_errors", "complete"}}`` — ``complete`` is False when the
    sweep stopped at an unlocatable segment, in which case
    ``silent_bitrot`` is a verified-but-PARTIAL attribution (and is not
    merged into ``corrupt``).
    """
    scan = _scan_chunks(in_file, segment_bytes)
    syn_report = None
    if syndrome:
        verdict, located, nerr, complete = _syndrome_sweep(
            in_file, scan, segment_bytes=segment_bytes
        )
        syn_report = {
            "verdict": verdict,
            "silent_bitrot": sorted(located),
            "symbol_errors": nerr,
            "complete": complete,
        }
        # Demote located chunks only on a COMPLETE attribution: the
        # unlocatable sweep stops at the first over-t segment, so its
        # located set covers a prefix of the archive — each entry is
        # individually verified, but presenting it as the damage set
        # (and feeding it to repair planning) would understate the rot.
        if located and verdict == "silent_bitrot":
            _obs_metrics.counter(
                "rs_scrub_chunks_total", "chunk verdicts from archive scans"
            ).labels(state="silent_bitrot").inc(len(located))
            scan = scan.excluding(
                {i: chunk_file_name(in_file, i) for i in located}
            )
        if located or verdict == "unlocatable":
            # Health plane: every located chunk is an individually
            # verified attribution (partial sweeps included — the
            # verdict field carries the completeness caveat).
            _obs_health.record_damage(
                "syndrome", in_file, chunks=sorted(located),
                k=scan.k, p=scan.p, w=scan.w,
                generation=scan.generation, verdict=verdict,
            )
    try:
        _select_decodable_subset(scan)
        ok = True
    except UndecidedSubsetError:
        ok = "unknown"
    except ValueError:
        ok = False
    if syn_report is not None and syn_report["verdict"] == "unlocatable":
        # Erasure-decodable maybe, but bytes unprovable: not True.
        ok = "unknown" if ok is True else ok
    _obs_metrics.counter(
        "rs_scrub_verdicts_total", "scan_file decodability verdicts"
    ).labels(decodable=str(ok)).inc()
    from .update.journal import journal_path

    report = {
        "k": scan.k,
        "p": scan.p,
        "w": scan.w,
        "checksummed": bool(scan.crcs),
        "layout": scan.layout,            # chunk layout (docs/UPDATE.md)
        "generation": scan.generation,    # update/append commit counter
        # A pending journal means the last update/append tore mid-patch:
        # recover_archive (or the next update/append) rolls it back.
        # Scrub REPORTS it — a read-only scan must not mutate the archive.
        "pending_journal": os.path.exists(journal_path(in_file)),
        "healthy": scan.healthy,
        "corrupt": sorted(scan.bad),  # present but truncated or CRC-failing
        "missing": scan.missing,      # absent files
        "decodable": ok,              # decodable implies repairable (one GEMM)
    }
    if syn_report is not None:
        report["syndrome"] = syn_report
    return report
