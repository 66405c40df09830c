"""Hardware probe: does the fused Pallas kernel lower under ``shard_map``?

VERDICT r4 gap 2: ``strategy="auto"`` on a mesh resolves to the fused
kernel (codec.py), but every mesh test runs interpret-mode on the virtual
CPU mesh and every real-TPU capture is single-device *unsharded*.  If
Mosaic refused the kernel inside ``shard_map`` on hardware, production
multi-chip would fail.  This tool closes that gap on a real chip: it
builds a 1-device ``(stripe, cols)`` mesh over the TPU and dispatches the
PRODUCTION sharded paths directly (``parallel.sharded.sharded_gf_matmul``
— a Mosaic refusal propagates and the committed log IS the deliverable):

* ``cols_pallas``   — cols-sharded fused kernel (the zero-comm production
  mesh path; reference analog: its multi-GPU mode provably runs the same
  kernel per device, encode.cu:240-292).
* ``stripe_pallas`` — stripe-sharded pre-parity fused kernel
  (``fold_parity=False``) + integer ``psum`` + fold: exercises BOTH the
  kernel's pre-parity emission and an XLA collective around it on
  hardware.
* ``cols_bitplane`` — the XLA bitplane path, for the same-shape comparison.

Each mode is bit-verified against the native CPU oracle on a slab before
timing.  Prints one commented-jsonl verdict per mode.

Usage: python -m gpu_rscode_tpu.tools.mesh_bench [--mb 320] [--trials 3]
"""

from __future__ import annotations

import argparse
import json
import sys


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mb", type=int, default=320, help="data MB per call")
    ap.add_argument("--trials", type=int, default=3)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--p", type=int, default=4)
    args = ap.parse_args()

    import numpy as np

    from .. import native
    from ..models.vandermonde import vandermonde_matrix
    from ..parallel.mesh import make_mesh
    from ..parallel.sharded import put_sharded, sharded_gf_matmul
    from ..utils.backend import device_platform
    from ._bench_timing import time_device_fn

    import jax

    from ..obs.runlog import capture_header

    print(json.dumps(capture_header("mesh_bench")), flush=True)

    label = device_platform()
    k, p = args.k, args.p
    m = (args.mb * 1024 * 1024) // k
    m = (m // 1024) * 1024  # lane-align so every mode shares one shape
    n_dev = len(jax.devices())
    print(
        f"# mesh probe on {label}: {n_dev} device(s), k={k} p={p} "
        f"data={k * m / 1e6:.0f} MB trials={args.trials}",
        file=sys.stderr, flush=True,
    )

    A = vandermonde_matrix(p, k)
    rng = np.random.default_rng(0)
    B_host = rng.integers(0, 256, size=(k, m), dtype=np.uint8)
    oracle = native.gemm(A, B_host[:, :4096])

    # cols mesh: (1, n) — every device a column slice.  stripe mesh: (n, 1)
    # — the contraction axis sharded (on 1 device this still exercises the
    # pre-parity kernel form + psum lowering on hardware, the thing no
    # committed capture shows).
    cols_mesh = make_mesh(n_dev, stripe=1)
    stripe_n = n_dev if k % n_dev == 0 else 1
    stripe_mesh = make_mesh(stripe_n, stripe=stripe_n)

    cases = {
        "cols_pallas": (cols_mesh, False, "pallas"),
        "stripe_pallas": (stripe_mesh, True, "pallas"),
        "cols_bitplane": (cols_mesh, False, "bitplane"),
    }
    results: dict[str, object] = {}
    for name, (mesh, stripe_sharded, strategy) in cases.items():
        try:
            Bd = put_sharded(B_host, mesh, stripe_sharded)

            def run(mesh=mesh, stripe_sharded=stripe_sharded,
                    strategy=strategy, Bd=Bd):
                return sharded_gf_matmul(
                    A, Bd, mesh=mesh, w=8, strategy=strategy,
                    stripe_sharded=stripe_sharded,
                )

            got = np.asarray(run())[:, :4096]
            if not np.array_equal(got, oracle):
                results[name] = "fail:OracleMismatch"
                print(json.dumps({name: results[name]}), flush=True)
                continue
            dt = time_device_fn(run, trials=args.trials)
            results[name] = round(k * m / dt / 1e9, 2)
        except Exception as e:  # noqa: BLE001 — the refusal IS the verdict
            msg = str(e).replace("\n", " ")[:200]
            results[name] = f"fail:{type(e).__name__}: {msg}"
        print(json.dumps({name: results[name]}), flush=True)

    print(
        json.dumps({
            "metric": f"mesh_gemm_bandwidth_k{k}_p{p}_{label}",
            "unit": "GB/s",
            "devices": n_dev,
            "mb": round(k * m / 1e6),
            "results": results,
        }),
        flush=True,
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
