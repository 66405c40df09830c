"""k-scaling study of the production fused kernel — VERDICT r3 item 5.

The reference documents throughput degradation for k >= ~32
(design.tex:462-466); the TPU kernel's MXU contraction depth is k*w (k=128
=> 1024), and the r3 tile/acc defaults were decided at a single (k=10, p=4)
point.  This sweep runs the PRODUCTION ``gf_matmul_pallas`` across
k in {4, 10, 32, 64, 128} x tile in {8192, 16384, 32768} x acc in
{int8, bf16}, bit-verifying a slab per configuration, and prints one
commented-jsonl line each — the committed capture answers whether the
defaults (tile 16384, int8) hold across configs and how depth scales.

p is held at 4 (parity count does not change the expansion work, which is
the kernel's bound); data per timed call stays >= the --mb floor (default
320 MB — smaller calls are dominated by dispatch overhead).

Usage: python -m gpu_rscode_tpu.tools.k_sweep [--mb 320] [--trials 2]
"""

from __future__ import annotations

import argparse
import json
import sys


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mb", type=int, default=320, help="data MB per call")
    ap.add_argument("--trials", type=int, default=2)
    ap.add_argument("--ks", type=str, default="4,10,32,64,128")
    ap.add_argument("--tiles", type=str, default="8192,16384,32768")
    args = ap.parse_args()

    import numpy as np

    import jax
    import jax.numpy as jnp

    from ..obs.runlog import capture_header

    print(json.dumps(capture_header("k_sweep")), flush=True)

    from .. import native
    from ..models.vandermonde import vandermonde_matrix
    from ..ops.pallas_gemm import gf_matmul_pallas
    from ..utils.backend import device_platform
    from ._bench_timing import time_device_fn

    label = device_platform()
    p = 4
    ks = [int(x) for x in args.ks.split(",")]
    tiles = [int(x) for x in args.tiles.split(",")]
    accs = [("int8", jnp.int8), ("bf16", jnp.bfloat16)]
    print(
        f"# k-sweep on {label}: p={p} ks={ks} tiles={tiles} "
        f"accs={[a for a, _ in accs]} data>={args.mb}MB trials={args.trials}",
        file=sys.stderr, flush=True,
    )

    rng = np.random.default_rng(0)
    for k in ks:
        m = (args.mb * 1024 * 1024) // k
        m = (m // 512) * 512
        A = vandermonde_matrix(p, k)
        B_host = rng.integers(0, 256, size=(k, m), dtype=np.uint8)
        Ad = jax.device_put(A)
        Bd = jax.device_put(B_host)
        Bd_small = jax.device_put(B_host[:, :4096])
        oracle = native.gemm(A, B_host[:, :4096])
        data_bytes = k * m
        best = (None, 0.0)
        for acc_name, acc in accs:
            for tile in tiles:
                key = f"k{k}_acc-{acc_name}@{tile}"
                try:
                    got = np.asarray(
                        gf_matmul_pallas(
                            Ad, Bd_small, tile=tile, acc_dtype=acc
                        )
                    )
                    if not np.array_equal(got, oracle):
                        print(json.dumps({key: "MISMATCH"}), flush=True)
                        continue

                    def run(t=tile, a=acc):
                        return gf_matmul_pallas(Ad, Bd, tile=t, acc_dtype=a)

                    dt = time_device_fn(run, trials=args.trials)
                    gbps = round(data_bytes / dt / 1e9, 2)
                    if gbps > best[1]:
                        best = (key, gbps)
                    print(json.dumps({key: gbps}), flush=True)
                except Exception as e:  # noqa: BLE001 — sweep must survive
                    msg = str(e).replace("\n", " ")[:120]
                    print(
                        json.dumps({key: f"fail:{type(e).__name__}: {msg}"}),
                        flush=True,
                    )
        print(
            json.dumps({f"k{k}_best": {"config": best[0], "gbps": best[1],
                                       "contraction_depth": k * 8}}),
            flush=True,
        )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
