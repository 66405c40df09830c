"""Multiply-strategy benchmark — the reference's cpu-rs-* study, one command.

The reference shipped eight CPU binaries, each swapping the GF(2^8) multiply
strategy, plus a GF(16) branch, to find the fastest inner loop (SURVEY C13;
design.tex:469-512 shows the choice was worth 1.5x end-to-end).  This tool
reruns that study for the TPU-era strategies on the current backend:

    python -m gpu_rscode_tpu.tools.strategy_bench [--size MB] [--k K] [--p P]

Reports GB/s of stripe encode per strategy (bitplane / table / pallas on the
accelerator, cpu native, numpy oracle) and prints a JSON summary line.
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np


def bench_strategy(fn, warmup: int = 1, iters: int = 3) -> float:
    for _ in range(warmup):
        r = fn()
    _block(r)
    t0 = time.perf_counter()
    for _ in range(iters):
        r = fn()
    _block(r)
    return (time.perf_counter() - t0) / iters


def _block(r):
    if hasattr(r, "block_until_ready"):
        r.block_until_ready()
    return r


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m gpu_rscode_tpu.tools.strategy_bench")
    ap.add_argument("--size", type=float, default=64.0, help="data MB per stripe")
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--p", type=int, default=4)
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument(
        "--strategies",
        default="bitplane,table,xor,pallas,cpu,numpy",
        help="comma list from bitplane,table,xor,pallas,cpu,numpy",
    )
    args = ap.parse_args(argv)

    import jax

    from ..obs.runlog import capture_header

    print(json.dumps(capture_header("strategy_bench")), flush=True)

    from ..utils.backend import device_platform

    from .. import native
    from ..models.vandermonde import vandermonde_matrix
    from ..ops.gemm import gf_matmul_jit
    from ..ops.gf import get_field
    from ..ops.pallas_gemm import gf_matmul_pallas
    from ..ops.xor_gemm import gf_matmul_xor

    k, p = args.k, args.p
    m = int(args.size * 1e6 / k)
    A = vandermonde_matrix(p, k)
    rng = np.random.default_rng(0)
    B = rng.integers(0, 256, size=(k, m), dtype=np.uint8)
    Bd = jax.device_put(B)
    Ad = jax.device_put(A)
    data_bytes = k * m

    runners = {
        "bitplane": lambda: gf_matmul_jit(Ad, Bd, strategy="bitplane"),
        "table": lambda: gf_matmul_jit(Ad, Bd, strategy="table"),
        "xor": lambda: gf_matmul_xor(A, Bd, 8),
        "pallas": lambda: gf_matmul_pallas(Ad, Bd),
        "cpu": lambda: native.gemm(A, B),
        "numpy": lambda: get_field(8).matmul(A, B),
    }
    results = {}
    for name in args.strategies.split(","):
        name = name.strip()
        if name not in runners:
            continue
        try:
            dt = bench_strategy(runners[name], iters=args.iters)
            gbps = data_bytes / dt / 1e9
            results[name] = round(gbps, 3)
            print(f"{name:>9}: {gbps:8.3f} GB/s   ({1e3 * dt:8.2f} ms / stripe)")
        except Exception as e:  # a strategy failing must not kill the study
            results[name] = None
            print(f"{name:>9}: FAILED ({type(e).__name__}: {e})")
    print(
        json.dumps(
            {
                "metric": f"strategy_bench_k{k}_p{p}_{device_platform()}",
                "unit": "GB/s",
                "results": results,
            }
        )
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
