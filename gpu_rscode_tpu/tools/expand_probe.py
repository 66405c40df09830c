"""Hardware probe of the bit-expansion formulations — VERDICT r3 item 2/8.

The fused kernel is VPU-expansion-bound: the r3 floors capture showed
compute-only 64.9 GB/s vs a 286 GB/s DMA floor (kernel_floors_tpu_*.jsonl),
so the expansion formulation IS the single-chip frontier.  This tool runs
the production kernel end-to-end with each candidate expansion at proper
scale (>= 320 MB per timed call — smaller calls are dominated by dispatch
overhead), bit-verifies a slab against the CPU oracle first, and prints one
commented-jsonl verdict per formulation for bench_captures/.

Round-4 candidates (all avoid the ops Mosaic refused in r3 — 8-bit iota,
int8 subi; see ops/pallas_gemm.py):

* ``shift``        — production baseline (int32 lanes, iota shifts).
* ``shift_raw``    — shift WITHOUT the ``& 1``: (b >> s) === bit_s (mod 2)
                     and the accumulator is only read modulo 2, so the
                     mask is algebraically redundant — w fewer VPU ops
                     per input byte on the proven-lowerable path.
* ``packed32``     — 4 bytes per int32 lane, one shift-mask per plane,
                     bitcast back to int8 (candidate b).
* ``sign16``       — {0,-1} sign-replication in int16-only lanes
                     (candidate d).
* ``shift_u8``     — unrolled constant shifts in uint8 lanes.
* ``nibble_const`` — the one-hot nibble/MXU strategy (the reference's
                     fastest-kernel idea, gf16.h:1-22) with unrolled
                     scalar compares instead of iota.
* ``sign``/``nibble`` — the r3 formulations, re-probed in case the
                     toolchain moved.

Candidate (c) of the verdict (grid over output-row blocks) is NOT probed:
the expansion is computed once per column tile and already shared by all
p*w output rows — there is no second row-block to amortise it over at
p=4, and growing p only grows MXU work, not expansion work.  Candidate
(a)'s pure-MXU unpack (contract bytes against a constant operator) is not
expressible: bit extraction is not linear over the integers, so any
MXU-side expansion must go through compares (= the nibble one-hot family).

Usage: python -m gpu_rscode_tpu.tools.expand_probe [--mb 320] [--trials 3]
"""

from __future__ import annotations

import argparse
import json
import sys


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mb", type=int, default=320, help="data MB per call")
    ap.add_argument("--trials", type=int, default=3)
    ap.add_argument("--tile", type=int, default=None)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--p", type=int, default=4)
    ap.add_argument("--acc", choices=["int8", "bf16"], default=None,
                    help="accumulator override (default: kernel's "
                         "depth-aware choice)")
    ap.add_argument("--refold", choices=["sum", "dot"], default=None,
                    help="parity refold: VPU shift-sum or MXU dot "
                         "(default: kernel's choice / RS_PALLAS_REFOLD)")
    ap.add_argument(
        "--expand", nargs="+",
        default=["shift", "shift_raw", "pack2", "packed32", "sign16",
                 "shift_u8", "nibble_const", "nibble32", "sign", "nibble"],
    )
    args = ap.parse_args()

    import numpy as np

    from .. import native
    from ..models.vandermonde import vandermonde_matrix
    from ..ops.pallas_gemm import gf_matmul_pallas
    from ..utils.backend import device_platform
    from ._bench_timing import time_device_fn

    import jax
    import jax.numpy as jnp

    from ..obs.runlog import capture_header

    print(json.dumps(capture_header("expand_probe")), flush=True)

    label = device_platform()
    k, p = args.k, args.p
    m = (args.mb * 1024 * 1024) // k
    tile = args.tile  # None -> the kernel's depth-aware default
    acc = {"int8": jnp.int8, "bf16": jnp.bfloat16, None: None}[args.acc]
    print(
        f"# expand probe on {label}: k={k} p={p} data={k * m / 1e6:.0f} MB "
        f"tile={tile or 'auto'} acc={args.acc or 'auto'} "
        f"refold={args.refold or 'auto'} trials={args.trials}",
        file=sys.stderr, flush=True,
    )

    A = vandermonde_matrix(p, k)
    rng = np.random.default_rng(0)
    B_host = rng.integers(0, 256, size=(k, m), dtype=np.uint8)
    Ad = jax.device_put(A)
    Bd = jax.device_put(B_host)
    Bd_small = jax.device_put(B_host[:, :4096])
    oracle = native.gemm(A, B_host[:, :4096])

    results = {}
    for expand in args.expand:
        try:
            got = np.asarray(
                gf_matmul_pallas(Ad, Bd_small, expand=expand, tile=tile,
                                 acc_dtype=acc, refold=args.refold)
            )
            if not np.array_equal(got, oracle):
                results[expand] = "fail:OracleMismatch"
                print(json.dumps({expand: results[expand]}), flush=True)
                continue

            def run(e=expand):
                return gf_matmul_pallas(Ad, Bd, expand=e, tile=tile,
                                        acc_dtype=acc, refold=args.refold)

            dt = time_device_fn(run, trials=args.trials)
            gbps = k * m / dt / 1e9
            results[expand] = round(gbps, 2)
        except Exception as e:  # noqa: BLE001 — each verdict must print
            msg = str(e).replace("\n", " ")[:160]
            results[expand] = f"fail:{type(e).__name__}: {msg}"
        print(json.dumps({expand: results[expand]}), flush=True)

    best = max(
        (v, k_) for k_, v in results.items() if isinstance(v, float)
    ) if any(isinstance(v, float) for v in results.values()) else None
    print(f"# best: {best[1]} @ {best[0]} GB/s" if best else "# no formulation ran",
          file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
