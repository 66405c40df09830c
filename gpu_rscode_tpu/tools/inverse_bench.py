"""Batched on-device k x k inversion vs the host loop — repair_fleet's win.

``api.repair_fleet`` solves every damaged archive's survivor-subset inverse
in one vmapped device dispatch (``ops.inverse.invert_matrix_jax_batch``, the
production reincarnation of the reference's dormant GPU inverter
matrix.cu:667-744 / blocked experiment decode-gj.cu:1059-1201).  This tool
measures that amortisation: B random invertible k x k GF(2^8) survivor
submatrices inverted (a) on device in one dispatch — pivoting and
(round 5) scan-free no-pivot variants, (b) on host one ``invert_matrix``
call at a time — the paths repair_fleet chooses between.  The r5 capture
(inverse_nopivot_tpu_20260801T001751Z.jsonl) REFUTED the theory that the
per-step argmax caused the k=128 loss: no-pivot == pivoting on TPU at
every cell (the elimination scan itself is the cost).  Its k x batch
grid is the measurement behind api._device_invert_min_batch_tpu.

Usage: python -m gpu_rscode_tpu.tools.inverse_bench [--batch 256] [--k 32]
Prints one JSON line per (batch, k) combination (commented-jsonl capture
convention: ``#`` lines are context, data lines are JSON).
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, nargs="+", default=[64, 256, 1024])
    ap.add_argument("--k", type=int, nargs="+", default=[10, 32, 128])
    ap.add_argument("--trials", type=int, default=3)
    args = ap.parse_args()

    import numpy as np

    from ..models.vandermonde import total_matrix
    from ..ops.gf import get_field
    from ..ops.inverse import (
        invert_matrix,
        invert_matrix_jax_batch,
        mds_nopivot_order,
    )
    from ..utils.backend import device_platform

    import jax

    from ..obs.runlog import capture_header

    print(json.dumps(capture_header("inverse_bench")), flush=True)

    label = device_platform()
    print(f"# backend={label}", file=sys.stderr, flush=True)
    gf = get_field(8)
    rng = np.random.default_rng(0)

    for k in args.k:
        # Survivor submatrices of the (2k, k) total matrix: the exact shape
        # repair_fleet inverts (k rows chosen from natives+parity).
        T = total_matrix(k, k, gf)
        n = 2 * k
        for batch in args.batch:
            # The production arrangement (repair_fleet): surviving-native
            # identity rows at their own positions so the no-pivot variant
            # measures the shape it actually dispatches on.
            subs = np.stack([
                T[mds_nopivot_order(
                    np.sort(rng.choice(n, size=k, replace=False)), k
                )]
                for _ in range(batch)
            ])
            dev_subs = jax.device_put(subs)

            def run(pivot=True):
                invs, oks = invert_matrix_jax_batch(dev_subs, 8, pivot=pivot)
                return jax.block_until_ready(invs), np.asarray(oks)

            invs, oks = run()  # warmup/compile
            dev_best = min(
                _timed(run) for _ in range(args.trials)
            )

            invs_np, oks_np = run(pivot=False)  # warmup/compile
            nopivot_best = min(
                _timed(lambda: run(pivot=False)) for _ in range(args.trials)
            )
            # The no-pivot result must agree with the pivoting one wherever
            # it claims success (it may flag extra ok=False on unlucky
            # leading minors; none expected for MDS subsets).
            agree = np.flatnonzero(np.asarray(oks_np))
            for j in agree[:4]:
                assert np.array_equal(
                    np.asarray(invs_np[j]), np.asarray(invs[j])
                ), f"no-pivot inverse mismatch at {j}"

            ok_idx = np.flatnonzero(oks)
            t0 = time.perf_counter()
            for j in ok_idx:
                invert_matrix(subs[j], gf)
            host_s = time.perf_counter() - t0
            host_per = host_s / max(1, len(ok_idx))

            # Bit-exactness of the device inverses vs the host inverter on
            # a sample (repair_fleet additionally verifies every inverse
            # with one GF matmul before trusting it).
            for j in ok_idx[:4]:
                want = invert_matrix(subs[j], gf)
                got = np.asarray(invs[j]).astype(gf.dtype)
                assert np.array_equal(got, want), f"inverse mismatch at {j}"

            print(json.dumps({
                "metric": f"batched_inverse_{label}",
                "k": k,
                "batch": batch,
                "invertible": int(len(ok_idx)),
                "nopivot_ok": int(len(agree)),
                "device_dispatch_s": round(dev_best, 6),
                "nopivot_dispatch_s": round(nopivot_best, 6),
                "device_per_matrix_us": round(1e6 * dev_best / batch, 2),
                "nopivot_per_matrix_us": round(1e6 * nopivot_best / batch, 2),
                "host_per_matrix_us": round(1e6 * host_per, 2),
                "speedup_vs_host_loop": round(
                    host_per * batch / dev_best, 2
                ),
                "nopivot_speedup_vs_host_loop": round(
                    host_per * batch / nopivot_best, 2
                ),
            }), flush=True)
    return 0


def _timed(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


if __name__ == "__main__":
    raise SystemExit(main())
