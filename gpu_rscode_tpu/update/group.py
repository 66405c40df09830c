"""Group-commit write combining — one durability chain for many edits.

A burst of N small updates/appends through :func:`.engine.apply_update`
pays N× the per-op tax: N journal creates + fsyncs, N ``E·Δ`` GEMM
dispatches, N crash-safe .METADATA rewrites (temp + fsync + rename +
dir fsync) and N generation bumps — even when the edits land in the
same column windows.  This module is the classic group-commit answer
(docs/UPDATE.md "Group commit"): :func:`apply_update_many` takes an
ORDERED batch of edits/appends against one archive and

1. merges them last-writer-wins into a span overlay (sequential
   semantics: edit j sees the totals left by appends 1..j-1, and a later
   edit of the same bytes wins — byte-identical to applying the batch
   one op at a time);
2. maps the merged spans to their touched column windows, assembles ONE
   stacked Δ per window block and dispatches ONE ``E·Δ`` GEMM per block
   through the warm plan cache (the op-free plan key means every window
   shares encode's executable — docs/PLAN.md);
3. journals the old bytes of EVERY region in the group, then commits the
   whole window group under ONE journal fsync chain, ONE ordered patch
   drain, ONE .METADATA rewrite and ONE generation bump.

All-or-nothing: the single journal covers the whole group, so a torn
group (crash at any ``RS_UPDATE_CRASH`` stage) rolls back EVERY edit via
the existing :func:`.journal.recover` path, and no edit is acknowledged
before its window group is durable — acks follow the commit point, so no
REDO journal is needed.

``RS_UPDATE_GROUP_WINDOW`` caps how many edits one commit group may
coalesce (default 1024): a larger batch splits into consecutive window
groups, each individually all-or-nothing with its own generation bump.
``RS_UPDATE_GROUP_BYTES`` bounds the in-RAM staged write set of one
group; past it the engine interleaves extra journal-sync + patch-drain
cycles (still one commit — durability ordering is preserved, only the
"one fsync" amortization degrades, and the fsync counters say so).
"""

from __future__ import annotations

import os
import threading
import time

import numpy as np

from ..codec import RSCodec
from ..obs import metrics as _metrics, tracing as _tracing
from ..parallel.io_executor import DrainExecutor
from ..utils.env import int_env as _int_env
from ..utils.fileformat import (
    chunk_size_for_layout,
    metadata_file_name,
    read_archive_meta,
    rewrite_metadata_lines,
)
from ..utils.timing import PhaseTimer
from . import journal as _journal
from .engine import (
    SimulatedCrash,  # noqa: F401  (re-exported crash surface)
    UpdateError,
    _account_crc,
    _assemble_interleaved_block,
    _assemble_row_block,
    _block_bytes,
    _check_width,
    _collect_block,
    _crash_point,
    _load_payload,
    _open_chunks,
    _parity_coeffs,
)
from .layout import touched_rows, touched_windows

DEFAULT_GROUP_WINDOW = 1024
DEFAULT_GROUP_BYTES = 256 * 1024 * 1024


def group_window() -> int:
    """Max edits one commit group coalesces (``RS_UPDATE_GROUP_WINDOW``,
    >= 1; larger batches split into consecutive groups)."""
    return max(1, _int_env("RS_UPDATE_GROUP_WINDOW", DEFAULT_GROUP_WINDOW))


def _group_bytes_budget() -> int:
    return max(1 << 20, _int_env("RS_UPDATE_GROUP_BYTES",
                                 DEFAULT_GROUP_BYTES))


# Process-lifetime tallies (rs doctor / daemon GET /stats read these even
# with the metrics registry disabled).
_STATS_LOCK = threading.Lock()
_STATS = {
    "groups": 0,            # committed window groups
    "edits": 0,             # edits coalesced into those groups
    "bytes": 0,             # payload bytes through grouped commits
    "max_group_seen": 0,    # largest committed group
    "journal_fsyncs": 0,    # journal sync calls across all groups
    "metadata_commits": 0,  # .METADATA rewrites across all groups
}


def group_stats() -> dict:
    """Live group-commit tallies plus the effective config — the doctor /
    daemon introspection surface."""
    with _STATS_LOCK:
        out = dict(_STATS)
    out["window_max_edits"] = group_window()
    return out


def _tally(edits: int, nbytes: int, journal_fsyncs: int) -> None:
    with _STATS_LOCK:
        _STATS["groups"] += 1
        _STATS["edits"] += edits
        _STATS["bytes"] += nbytes
        _STATS["max_group_seen"] = max(_STATS["max_group_seen"], edits)
        _STATS["journal_fsyncs"] += journal_fsyncs
        _STATS["metadata_commits"] += 1


def _fsync_counter():
    return _metrics.counter(
        "rs_update_group_fsyncs_total",
        "fsync calls in grouped update commits, by chain stage",
    )


class _Overlay:
    """Last-writer-wins byte-span overlay: ascending, disjoint
    ``(at, payload)`` ranges.  A later write splits/trims whatever it
    overlaps — exactly the bytes a sequential application would leave."""

    def __init__(self):
        self.spans: list[tuple[int, np.ndarray]] = []

    def write(self, at: int, payload: np.ndarray) -> None:
        length = int(payload.shape[0])
        if length == 0:
            return
        end = at + length
        out = []
        for s_at, s_pl in self.spans:
            s_end = s_at + int(s_pl.shape[0])
            if s_end <= at or s_at >= end:
                out.append((s_at, s_pl))
                continue
            if s_at < at:
                out.append((s_at, s_pl[: at - s_at]))
            if s_end > end:
                out.append((end, s_pl[end - s_at :]))
        out.append((at, payload))
        out.sort(key=lambda t: t[0])
        self.spans = out


def _merge_windows(wins: list[tuple[int, int]]) -> list[tuple[int, int]]:
    """Union of column windows: sorted, overlapping/adjacent merged —
    the group's window set, each getting one Δ stack per block."""
    out: list[list[int]] = []
    for lo, hi in sorted(wins):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def _parse_edits(edits) -> list[dict]:
    """Normalize the caller's edit list: each item is a dict with
    ``op`` ("update" | "append"), ``at`` (update only) and exactly one
    of ``data`` / ``src``.  Payloads load eagerly (``src`` memmaps, so a
    large file does not materialise)."""
    parsed = []
    for i, e in enumerate(edits):
        if not isinstance(e, dict):
            raise ValueError(
                f"edit {i}: want a dict with op/at/data|src, got "
                f"{type(e).__name__}"
            )
        op = e.get("op", "update")
        if op not in ("update", "append"):
            raise ValueError(
                f"edit {i}: op must be 'update' or 'append', got {op!r}"
            )
        try:
            payload = _load_payload(e.get("data"), e.get("src"))
        except ValueError as err:
            raise ValueError(f"edit {i}: {err}") from None
        if op == "update":
            if "at" not in e:
                raise ValueError(f"edit {i}: update needs an 'at' offset")
            at = int(e["at"])
            if at < 0:
                raise ValueError(f"edit {i}: negative offset {at}")
        else:
            at = None
        parsed.append({"op": op, "at": at, "payload": payload})
    return parsed


def apply_update_many(
    file_name: str,
    edits,
    *,
    strategy: str = "auto",
    segment_bytes: int = 64 * 1024 * 1024,
    timer: PhaseTimer | None = None,
    group_edits: int | None = None,
    group_tag: str | None = None,
    stage_hook=None,
) -> dict:
    """Apply an ordered batch of edits/appends as group-committed window
    groups — byte-identical to applying them sequentially, at a fraction
    of the durability/dispatch tax (module doc).  ``group_edits``
    overrides ``RS_UPDATE_GROUP_WINDOW`` for this call — the daemon's
    write combiner passes the whole batch so its harvest commits as ONE
    all-or-nothing group (its isolation fallback depends on a failed
    batch having committed nothing).  ``group_tag`` names the commit in
    the dispatch trace span and the returned summary (``group_id``) —
    the daemon's write combiner passes its group id here so one combined
    commit joins to the N request ids it acknowledges.  ``stage_hook``
    (a ``callable(stage_name)``) fires at the lifecycle boundaries the
    caller cannot observe from outside — currently ``"device_done"``,
    after the last ``E·Δ`` GEMM is collected and before the journal
    fsync chain begins (docs/SERVE.md "Request lifecycle").  Returns the
    aggregate summary dict (``edits``, ``groups``, ``windows``,
    ``segments``, ``chunks_touched``, ``total_size``, ``generation``)."""
    timer = timer or PhaseTimer(enabled=False)
    parsed = _parse_edits(edits)
    window = max(1, group_edits) if group_edits else group_window()
    summary: dict | None = None
    groups = 0
    for g0 in range(0, max(1, len(parsed)), window):
        part = _apply_group(
            file_name, parsed[g0 : g0 + window], base=g0,
            strategy=strategy, segment_bytes=segment_bytes, timer=timer,
            group_tag=group_tag, stage_hook=stage_hook,
        )
        groups += 1
        if summary is None:
            summary = part
        else:
            summary["edits"] += part["edits"]
            summary["bytes"] += part["bytes"]
            summary["windows"] += part["windows"]
            summary["segments"] += part["segments"]
            summary["journal_fsyncs"] += part["journal_fsyncs"]
            summary["chunks_touched"] = sorted(
                set(summary["chunks_touched"]) | set(part["chunks_touched"])
            )
            summary["total_size"] = part["total_size"]
            summary["generation"] = part["generation"]
    assert summary is not None
    summary["groups"] = groups
    if group_tag is not None:
        summary["group_id"] = group_tag
    return summary


def _apply_group(file_name, edits, *, base, strategy, segment_bytes,
                 timer, group_tag=None, stage_hook=None):
    from ..ops.gf import get_field

    t_start = time.perf_counter()
    recovered = _journal.recover(file_name)
    meta_path = metadata_file_name(file_name)
    meta = read_archive_meta(meta_path)
    k, p, w = meta.native_num, meta.parity_num, meta.w
    _check_width(meta)
    sym = meta.sym
    total0 = meta.total_size

    # Sequential-semantics validation + last-writer-wins merge: edit j
    # is validated against the running total its predecessors left.
    overlay = _Overlay()
    total = total0
    payload_bytes = 0
    for i, e in enumerate(edits):
        length = int(e["payload"].shape[0])
        if e["op"] == "update":
            at = e["at"]
            if length and at + length > total:
                raise UpdateError(
                    f"edit {base + i}: update range [{at}, {at + length}) "
                    f"falls outside the archive's {total} bytes at that "
                    "point in the batch; use an append edit to grow it"
                )
        else:
            at = total
            total += length
        payload_bytes += length
        overlay.write(at, e["payload"])
    grow = total > total0

    summary_base = {
        "op": "group", "edits": len(edits), "bytes": payload_bytes,
        "layout": meta.layout, "recovered": recovered,
    }
    if not overlay.spans:
        return {
            **summary_base, "windows": 0, "segments": 0,
            "chunks_touched": [], "journal_fsyncs": 0,
            "total_size": total0, "generation": meta.generation,
        }

    gf = get_field(w)
    E = _parity_coeffs(meta, gf)
    chunk_old = meta.chunk
    if grow:
        chunk_new = chunk_size_for_layout(total, k, sym, meta.layout)
        if meta.layout == "row" and chunk_new != chunk_old:
            slack = k * chunk_old - total0
            raise UpdateError(
                f"group appends {total - total0} byte(s), overflowing the "
                f"row-major archive's {slack} byte(s) of tail-padding "
                "slack (growing the chunk size would re-stripe every "
                "byte); re-encode, or encode with --layout interleaved "
                "for unbounded appends"
            )
    else:
        chunk_new = chunk_old
        if chunk_old == 0:
            raise UpdateError("zero-size archive has nothing to update")

    wins: list[tuple[int, int]] = []
    rows_set: set[int] = set()
    for at, pl in overlay.spans:
        length = int(pl.shape[0])
        wins += touched_windows(meta.layout, at, length, k, sym, chunk_new)
        rows_set |= set(touched_rows(meta.layout, at, length, k, chunk_new))
    windows = _merge_windows(wins)
    rows = sorted(rows_set)
    all_idx = rows + [i for i in range(k, k + p) if i not in rows]

    fps: dict[int, object] = {}
    try:
        _open_chunks(file_name, all_idx, chunk_old, fps)
        codec = RSCodec(k, p, w=w, strategy=strategy)
        crcs = dict(meta.crcs) if meta.crcs else None
        touched: set[int] = set()
        blocks = 0
        journal_fsyncs = 0
        jr = _journal.Journal(
            file_name, meta.generation, "group",
            {i: chunk_old for i in all_idx},
        )
        committed = False
        try:
            step = _block_bytes(k, sym, segment_bytes)
            budget = _group_bytes_budget()
            # Writes already journaled but not yet submitted to the lane:
            # the whole group's set in the common case — ONE journal sync
            # covers everything before the first chunk byte changes.
            pending: list[tuple[int, int, bytes]] = []
            pending_bytes = 0
            first_n_native = None

            with DrainExecutor(ordered=True, name="rs-io-patch") as lane:

                def drain_pending():
                    nonlocal pending, pending_bytes, journal_fsyncs
                    journal_fsyncs += jr.sync()
                    for idx, off, new in pending:
                        lane.submit_pwrite(fps[idx].fileno(), new, off)
                        touched.add(idx)
                    lane.flush()
                    pending = []
                    pending_bytes = 0

                # Small-window stacking: every window block shares the
                # op-free plan key, so adjacent small windows' deltas
                # concatenate into ONE staged segment and ONE E·Δ GEMM
                # up to the plan-bucket cap (the 64-scattered-4KiB burst
                # dispatches once, not 64 times); a full-width block
                # flushes alone, exactly like the single-op engine.
                batch: list[tuple] = []  # (b0, b1, delta, native_writes)
                batch_w = 0

                def flush_batch():
                    nonlocal batch, batch_w, blocks, pending_bytes
                    nonlocal first_n_native
                    if not batch:
                        return
                    stacked = (
                        batch[0][2] if len(batch) == 1
                        else np.hstack([blk[2] for blk in batch])
                    )
                    span_args = dict(
                        op="group", off=int(batch[0][0]),
                        cols=int(stacked.shape[1]),
                    )
                    if group_tag is not None:
                        # The group <-> request-id join's trace side: a
                        # daemon Perfetto timeline resolves this dispatch
                        # to the write group (and through it, via the
                        # rs_request events, to the member request ids).
                        span_args["group"] = group_tag
                    with timer.phase("update dispatch"), _tracing.span(
                        "dispatch", lane="dispatch", **span_args,
                    ):
                        staged = codec.stage_segment(
                            stacked, cap=step // sym, sym=sym
                        )
                        pd = codec.update(E, staged)
                    with timer.phase("update compute"):
                        pd_np = np.asarray(pd)
                    if pd_np.dtype != np.uint8:
                        pd_np = np.ascontiguousarray(pd_np).view(np.uint8)
                    col = 0
                    for b0, b1, delta, nat in batch:
                        bw = b1 - b0
                        writes, n_native = _collect_block(
                            b0, b1, delta, nat,
                            pd_np[:, col : col + bw], fps, chunk_old,
                            k, p, meta.layout, timer,
                        )
                        col += bw
                        for idx, off, old, new in writes:
                            jr.record(
                                idx, off, old[: max(0, chunk_old - off)]
                            )
                            if crcs is not None:
                                _account_crc(
                                    crcs, idx, off, old, new, chunk_old
                                )
                            pending.append((idx, off, new))
                            pending_bytes += len(new) + len(old)
                        blocks += 1
                        if first_n_native is None:
                            first_n_native = n_native
                    batch = []
                    batch_w = 0
                    if pending_bytes > budget:
                        # RAM guard for huge groups: extra sync+drain
                        # cycles, still one commit (journal-before-
                        # patch ordering holds per cycle).
                        drain_pending()

                for wlo, whi in windows:
                    for b0 in range(wlo, whi, step):
                        b1 = min(b0 + step, whi)
                        if batch_w + (b1 - b0) > step:
                            flush_batch()
                        with timer.phase("update stage (io)"):
                            if meta.layout == "interleaved":
                                delta, nat = _assemble_interleaved_block(
                                    b0, b1, fps, overlay.spans, k, sym
                                )
                            else:
                                delta, nat = _assemble_row_block(
                                    b0, b1, rows, fps, overlay.spans,
                                    chunk_old, k
                                )
                        batch.append((b0, b1, delta, nat))
                        batch_w += b1 - b0
                flush_batch()
                if stage_hook is not None:
                    # Every E·Δ GEMM is collected; everything after this
                    # point is durability (journal sync chain, patch
                    # drain, chunk fsyncs, metadata commit) — the
                    # device/drain boundary of the lifecycle timeline.
                    stage_hook("device_done")

                journal_fsyncs += jr.sync()
                _crash_point("after_journal")
                cut = min(first_n_native or 0, len(pending)) or None
                for pos, (idx, off, new) in enumerate(pending):
                    if pos == cut:
                        # First block's natives patched, its parity and
                        # every later window not — the torn-group state
                        # recovery must undo in full.
                        lane.flush()
                        _crash_point("mid_patch")
                    lane.submit_pwrite(fps[idx].fileno(), new, off)
                    touched.add(idx)
                pending = []
                lane.flush()

            for fp in fps.values():
                os.fsync(fp.fileno())
            _fsync_counter().labels(stage="chunks").inc(len(fps))
            _crash_point("before_commit")
            with timer.phase("write metadata (io)"):
                new_gen = rewrite_metadata_lines(
                    meta_path, total_size=total if grow else None,
                    crcs=crcs, bump_generation=True,
                )
            jr.close(commit=True)
            committed = True
        except SimulatedCrash:
            jr.close(commit=False)  # disk stays torn; recover() heals
            raise
        except BaseException:
            if not committed:
                # All-or-nothing: roll the WHOLE group back from the
                # durable journal (same machinery a hard crash uses).
                jr.close(commit=False)
                _journal.recover(file_name)
            raise
    finally:
        for fp in fps.values():
            if not fp.closed:
                fp.close()

    _fsync_counter().labels(stage="journal").inc(journal_fsyncs)
    _fsync_counter().labels(stage="metadata").inc()
    _metrics.histogram(
        "rs_update_group_size",
        "edits coalesced per committed update group",
        buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256),
    ).observe(len(edits))
    _metrics.counter(
        "rs_update_group_coalesced_bytes_total",
        "payload bytes applied through grouped update commits",
    ).inc(payload_bytes)
    _metrics.counter(
        "rs_update_bytes_total",
        "payload bytes applied by delta update/append",
    ).labels(op="group").inc(payload_bytes)
    _metrics.counter(
        "rs_update_segments_touched_total",
        "column segment blocks patched by update/append",
    ).inc(blocks)
    _metrics.quantile(
        "rs_update_wall_seconds",
        "update/append wall seconds (streaming quantiles)",
    ).labels(op="group").observe(time.perf_counter() - t_start)
    _tally(len(edits), payload_bytes, journal_fsyncs)
    return {
        **summary_base,
        "windows": len(windows),
        "segments": blocks,
        "journal_fsyncs": journal_fsyncs,
        "chunks_touched": sorted(touched),
        "total_size": total,
        "generation": new_gen,
    }
