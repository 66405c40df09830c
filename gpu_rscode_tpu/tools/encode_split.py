"""Where each ``encode_file`` op's time goes, op by op, on this host's disk.

Encodes one seeded file of ``--bytes`` (default one HDFS RS(10,4) block
group, 10 x 128 MiB) at (``--k``, ``--p``) with checksums on, ``--ops``
times back to back over the same archive, as a writer replacing its
output does.  Each op records its own span trace (``trace_path=``) and
prints one JSON line, in seconds unless named otherwise:

* ``op_s``: the op's wall; ``head_s``/``tail_s``: from its start to the
  writer lane's first task, and from the lane's last task to its end (the
  commit: closes, .METADATA, the renames);
* ``promote_s``: the renames alone (the ``promote chunks (io)`` phase; 0
  on a program without it);
* ``lane_s`` and ``tasks``: the writer lane's busy time (union of its
  ``write_drain`` spans) and their count; ``lane_gap_s``: the lane's idle
  time between its first and last task;
* the lane split, each counted once in this order: ``crc_s`` (``crc``),
  ``natives_s`` (``write natives (io)``), ``parity_s`` (``write parity
  (io)``), ``d2h_s`` (``encode compute``: the wait for the device's
  parity), and ``task_other_s``, the rest of the lane's time (the task
  wrapper, retry and fault hooks, counters);
* ``gather_s``: the read stage (``stage segment (io)``); ``wait_writer_s``
  and ``wait_segment_s``: the dispatching thread's waits;
* ``dirty_kB``/``writeback_kB`` from ``/proc/meminfo`` after the op,
  where the host reports them.

``--disk N`` first times the disk alone: N rewrites of the same (k + p)
chunk files by ``os.pwrite``, close and rename over the previous set.

Usage: python -m gpu_rscode_tpu.tools.encode_split [--bytes B] [--ops N]
           [--k 10] [--p 4] [--seed S] [--disk N] [--dir D]
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

BLOCK_GROUP = 10 * 128 * 2**20

# The lane's parts, each counted once, in this order (a parent span that
# holds a ``crc`` span, as a native copy once did, is counted without it).
LANE_PARTS = (
    ("crc_s", "crc"),
    ("natives_s", "write natives (io)"),
    ("parity_s", "write parity (io)"),
    ("d2h_s", "encode compute"),
)


def _union(intervals):
    """Sorted, disjoint union of (start, end) pairs."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _intersect(a, b):
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append([s, e])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def split(events: list[dict]) -> dict:
    """The per-op split of one op's Chrome-trace events (see the module
    docstring); times in seconds."""
    spans: dict[str, list] = {}
    for ev in events:
        if ev.get("ph") == "X":
            spans.setdefault(ev["name"], []).append(
                (ev["ts"] / 1e6, (ev["ts"] + ev["dur"]) / 1e6)
            )

    def total(name: str) -> float:
        return sum(e - s for s, e in spans.get(name, ()))

    op_s, op_e = spans["encode"][0]
    drains = spans.get("write_drain", [])
    lane = _union(drains)
    row = {"op_s": op_e - op_s, "tasks": len(drains),
           "lane_s": _length(lane)}
    if lane:
        row["head_s"] = lane[0][0] - op_s
        row["tail_s"] = op_e - lane[-1][1]
        row["lane_gap_s"] = (lane[-1][1] - lane[0][0]) - row["lane_s"]
    counted: list = []
    for key, name in LANE_PARTS:
        mine = _intersect(_union(spans.get(name, ())), lane)
        row[key] = _length(mine) - _length(_intersect(mine, counted))
        counted = _union(counted + mine)
    row["task_other_s"] = row["lane_s"] - _length(counted)
    row["promote_s"] = total("promote chunks (io)")
    row["gather_s"] = total("stage segment (io)")
    row["wait_writer_s"] = total("wait_writer")
    row["wait_segment_s"] = total("wait_segment")
    return {k: round(v, 6) if isinstance(v, float) else v
            for k, v in row.items()}


def _meminfo() -> dict:
    got = {}
    try:
        with open("/proc/meminfo") as fp:
            for line in fp:
                name, _, rest = line.partition(":")
                if name in ("Dirty", "Writeback"):
                    got[name.lower() + "_kB"] = int(rest.split()[0])
    except OSError:
        pass
    return got


def _seeded_file(path: str, size: int, seed: int) -> None:
    words = np.random.Generator(np.random.SFC64(seed)).bit_generator \
        .random_raw(-(-size // 8))
    with open(path, "wb") as fp:
        fp.write(words.view(np.uint8)[:size].data)


def disk_part(d: str, n_files: int, chunk: int, ops: int) -> None:
    buf = memoryview(
        np.random.default_rng(1).integers(0, 256, min(chunk, 64 << 20),
                                          np.uint8)
    )
    for op in range(ops):
        t0 = time.perf_counter()
        for i in range(n_files):
            tmp, name = f"{d}/_{i}_disk.rs_tmp", f"{d}/_{i}_disk"
            fd = os.open(tmp, os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o644)
            try:
                for off in range(0, chunk, len(buf)):
                    os.pwrite(fd, buf[:chunk - off], off)
            finally:
                os.close(fd)
            os.replace(tmp, name)
        dt = time.perf_counter() - t0
        print(json.dumps({"part": "disk", "op": op, "op_s": round(dt, 6),
                          "GBps": round(n_files * chunk / dt / 1e9, 6),
                          **_meminfo()}), flush=True)


def encode_part(d: str, size: int, k: int, p: int, seed: int,
                ops: int) -> None:
    from .. import api

    path = f"{d}/bg.bin"
    _seeded_file(path, size, seed)
    api.encode_file(path, k, p, checksums=True)  # warm: compiles, opens
    t0 = time.perf_counter()
    os.sync()
    print(json.dumps({"part": "setup", "sync_s": round(
        time.perf_counter() - t0, 6)}), flush=True)
    trace = f"{d}/op.trace.json"
    for op in range(ops):
        api.encode_file(path, k, p, checksums=True, trace_path=trace)
        with open(trace) as fp:
            events = json.load(fp)["traceEvents"]
        row = {"part": "encode", "op": op, **split(events), **_meminfo()}
        row["GBps"] = round(size / row["op_s"] / 1e9, 6)
        print(json.dumps(row), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--bytes", type=int, default=BLOCK_GROUP)
    ap.add_argument("--ops", type=int, default=10)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--p", type=int, default=4)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--disk", type=int, default=0,
                    help="rewrites of k + p chunk files before the encodes")
    ap.add_argument("--dir", default=None,
                    help="work directory (default: a new one under TMPDIR, "
                         "removed at exit)")
    args = ap.parse_args(argv)

    from ..obs.runlog import capture_header

    print(json.dumps(capture_header("encode_split")), flush=True)
    d = args.dir or tempfile.mkdtemp(prefix="encode_split_")
    try:
        if args.disk:
            chunk = -(-args.bytes // args.k)
            disk_part(d, args.k + args.p, chunk, args.disk)
        encode_part(d, args.bytes, args.k, args.p, args.seed, args.ops)
    finally:
        if args.dir is None:
            shutil.rmtree(d, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
