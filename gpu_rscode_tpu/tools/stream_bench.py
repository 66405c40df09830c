"""End-to-end streaming file bench — the reference's total-GPU-time study.

The reference's headline table (design.tex:477-500, BASELINE.md) reports
*total* encode/decode time for a 1.1 GB file including the PCIe copies that
dominate it (~52 %).  This tool reproduces that experiment for the TPU
framework: write a temp file, stream-encode it (``api.encode_file``),
worst-case-erase, stream-decode, and report end-to-end GB/s with the
computation-vs-communication phase split (utils/timing.py).

The phase split shows where the time goes and whether the pipeline
overlaps (``--depth`` maps the reference's ``-s`` stream knob).

Usage: python -m gpu_rscode_tpu.tools.stream_bench [--mb 256] [--k 10]
       [--p 4] [--depth 2] [--strategy pallas] [--seg-mb 64]
"""

from __future__ import annotations

import argparse
import json
import os
import tempfile
import time

import numpy as np

from ..api import decode_file, encode_file
from ..utils.fileformat import chunk_file_name, write_conf
from ..utils.backend import device_platform
from ..utils.timing import PhaseTimer


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m gpu_rscode_tpu.tools.stream_bench"
    )
    ap.add_argument("--mb", type=int, default=256, help="file size MB")
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--p", type=int, default=4)
    ap.add_argument("--depth", type=int, default=2, help="pipeline depth (-s)")
    ap.add_argument("--strategy", default="pallas")
    ap.add_argument("--seg-mb", type=int, default=64, help="segment MB")
    ap.add_argument("--dir", default=None, help="work dir (default: tmpdir)")
    args = ap.parse_args(argv)

    from ..obs.runlog import capture_header

    print(json.dumps(capture_header("stream_bench")), flush=True)

    k, p = args.k, args.p
    size = args.mb * 1024 * 1024
    with tempfile.TemporaryDirectory(dir=args.dir) as td:
        path = os.path.join(td, "payload.bin")
        rng = np.random.default_rng(7)
        with open(path, "wb") as fp:
            left = size
            while left:
                step = min(left, 64 * 1024 * 1024)
                fp.write(rng.integers(0, 256, step, dtype=np.uint8).tobytes())
                left -= step
        digest_src = _digest(path)

        enc_timer = PhaseTimer()
        t0 = time.perf_counter()
        encode_file(
            path, k, p,
            strategy=args.strategy,
            segment_bytes=args.seg_mb * 1024 * 1024,
            pipeline_depth=args.depth,
            timer=enc_timer,
        )
        enc_wall = time.perf_counter() - t0
        print(f"encode ({args.mb} MB, k={k}, p={p}, depth={args.depth}):")
        print(enc_timer.summary(size))

        # Worst-case erasure: drop the first p chunks (the reference's
        # unit-test.sh pattern) so every surviving stripe needs real recovery.
        survivors = [chunk_file_name(path, i) for i in range(p, p + k)]
        conf = os.path.join(td, "conf")
        write_conf(conf, survivors)
        for i in range(p):
            os.remove(chunk_file_name(path, i))
        out = os.path.join(td, "recovered.bin")

        dec_timer = PhaseTimer()
        t0 = time.perf_counter()
        decode_file(
            path, conf, out,
            strategy=args.strategy,
            segment_bytes=args.seg_mb * 1024 * 1024,
            pipeline_depth=args.depth,
            timer=dec_timer,
        )
        dec_wall = time.perf_counter() - t0
        print(f"decode (worst-case {p}-erasure):")
        print(dec_timer.summary(size))

        ok = _digest(out) == digest_src
        result = {
            "metric": f"stream_file_k{k}_n{k + p}_{device_platform()}",
            "unit": "GB/s",
            "file_mb": args.mb,
            "depth": args.depth,
            "strategy": args.strategy,
            "encode_gbps": round(size / enc_wall / 1e9, 3),
            "decode_gbps": round(size / dec_wall / 1e9, 3),
            "bit_exact": ok,
        }
        print(json.dumps(result))
        return 0 if ok else 1


def _digest(path: str) -> str:
    import hashlib

    h = hashlib.sha256()
    with open(path, "rb") as fp:
        while True:
            b = fp.read(1 << 24)
            if not b:
                break
            h.update(b)
    return h.hexdigest()


if __name__ == "__main__":
    raise SystemExit(main())
