"""Sharded-dispatch overhead study — the >=20 GB/s aggregate north star.

VERDICT r4 task 7: single-chip 102.5 GB/s with zero-comm cols sharding
trivially projects past 20 GB/s aggregate on a v5e-8, but nothing measured
the per-SEGMENT cost the file layer adds on a mesh: ``put_sharded``
(``device_put`` scatter / ``make_array_from_process_local_data``) and the
sharded-jit dispatch itself.  This tool measures both on whatever mesh the
backend offers (intended: the 8-device virtual CPU mesh, where the
STRUCTURE — the mesh-vs-single overhead RATIO at a tiny segment, where
fixed costs dominate — transfers even though absolute CPU numbers do not):

* ``put_ms[mb]``       — host->mesh scatter per segment, per probed size
  (the file layer pays this once per segment per stripe op).
* ``dispatch_ms[mb]``  — sharded GEMM call, per probed size.  At the tiny
  size this IS the fixed per-dispatch cost (compute is negligible);
  ``dispatches_per_s`` is its reciprocal.
* ``overhead_vs_single`` — tiny-segment dispatch cost relative to the
  UNSHARDED single-device dispatch on the same backend (the portable
  number: how much the mesh machinery multiplies fixed cost).
* ``psum_bytes_per_seg_per_dev`` — stripe mode's analytic collective
  payload at the large segment: (p*w, m_loc) int32 pre-parity partials.

Usage: python -m gpu_rscode_tpu.tools.mesh_overhead [--mb 1 32] [--trials 3]
"""

from __future__ import annotations

import argparse
import json
import sys


def _stripe_factor(k: int, n_dev: int) -> int:
    """Largest stripe-axis size that divides both k and n_dev (mesh shape
    and k-sharding both require divisibility)."""
    import math

    return math.gcd(k, n_dev)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mb", type=int, nargs=2, default=[1, 32],
                    help="tiny and large segment sizes (MB)")
    ap.add_argument("--trials", type=int, default=3)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--p", type=int, default=4)
    args = ap.parse_args()

    import numpy as np

    from ..models.vandermonde import vandermonde_matrix
    from ..ops.gemm import gf_matmul_jit
    from ..parallel.mesh import make_mesh
    from ..parallel.sharded import put_sharded, sharded_gf_matmul
    from ..utils.backend import device_platform

    import jax

    from ..obs.runlog import capture_header

    print(json.dumps(capture_header("mesh_overhead")), flush=True)

    label = device_platform()
    k, p = args.k, args.p
    n_dev = len(jax.devices())
    mesh = make_mesh(n_dev, stripe=1)
    stripe_n = _stripe_factor(k, n_dev)
    stripe_mesh = make_mesh(n_dev, stripe=stripe_n)
    print(
        f"# mesh overhead on {label}: {n_dev} device(s), k={k} p={p} "
        f"segments {args.mb} MB, stripe axis {stripe_n}, "
        f"trials={args.trials}",
        file=sys.stderr, flush=True,
    )

    rng = np.random.default_rng(0)
    A = vandermonde_matrix(p, k)

    import time

    def time_host(fn, trials):
        best = float("inf")
        jax.block_until_ready(fn())  # warmup/compile, fully drained
        for _ in range(trials):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            best = min(best, time.perf_counter() - t0)
        return best

    rows = []
    for mode, msh, stripe_sharded in (
        ("single", None, False),
        ("cols", mesh, False),
        ("stripe", stripe_mesh, True),
    ):
        row = {
            "metric": f"mesh_overhead_{label}",
            "mode": mode,
            "devices": 1 if msh is None else n_dev,
        }
        for mb in args.mb:
            m = max(1, mb * 1024 * 1024 // k // 128) * 128
            B = rng.integers(0, 256, size=(k, m), dtype=np.uint8)
            if msh is None:
                put = lambda B=B: jax.device_put(B)
            else:
                put = lambda B=B, msh=msh, ss=stripe_sharded: put_sharded(
                    B, msh, ss
                )
            row[f"put_ms[{mb}mb]"] = round(
                1e3 * time_host(put, args.trials), 3
            )
            Bd = put()
            if msh is None:
                disp = lambda Bd=Bd: gf_matmul_jit(
                    A, Bd, w=8, strategy="bitplane"
                )
            else:
                disp = lambda Bd=Bd, msh=msh, ss=stripe_sharded: (
                    sharded_gf_matmul(
                        A, Bd, mesh=msh, w=8, strategy="bitplane",
                        stripe_sharded=ss,
                    )
                )
            # Blocking per-call timing (not the async-loop timer): a
            # per-dispatch overhead metric wants the full issue->complete
            # cost, and un-blocked queues of collective programs deadlock
            # the CPU in-process communicator's rendezvous.
            row[f"dispatch_ms[{mb}mb]"] = round(
                1e3 * time_host(disp, args.trials), 3
            )
        tiny = args.mb[0]
        row["dispatches_per_s_tiny"] = round(
            1e3 / max(row[f"dispatch_ms[{tiny}mb]"], 1e-6), 1
        )
        if stripe_sharded:
            m2 = max(1, args.mb[1] * 1024 * 1024 // k // 128) * 128
            m_loc = m2 // (n_dev // stripe_n)
            # int8 pre-parity planes since round 5 (parallel/sharded.py
            # narrows the collective; mod-256 wrap is parity-exact) —
            # p*w*1 bytes per column.  The 2026-07-31 capture of this
            # tool predates the narrowing and reported the int32 form
            # (4x this number).
            row["psum_bytes_per_seg_per_dev"] = int(p * 8 * m_loc)
        rows.append(row)
        print(json.dumps(row), flush=True)

    single = next(r for r in rows if r["mode"] == "single")
    tiny = args.mb[0]
    for r in rows:
        if r["mode"] == "single":
            continue
        print(json.dumps({
            "metric": f"mesh_overhead_ratio_{label}",
            "mode": r["mode"],
            "overhead_vs_single": round(
                r[f"dispatch_ms[{tiny}mb]"]
                / max(single[f"dispatch_ms[{tiny}mb]"], 1e-6),
                2,
            ),
            "put_vs_single": round(
                r[f"put_ms[{tiny}mb]"]
                / max(single[f"put_ms[{tiny}mb]"], 1e-6),
                2,
            ),
        }), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
