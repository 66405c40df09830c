"""Pallas kernel tuning sweep — run on real TPU hardware.

Measures, with the latency-robust timing of bench.py:

- per-variant fused-kernel throughput across tile sizes,
- a DMA-only floor (kernel reads the input block, writes a slice — no
  compute), and a compute-only ceiling (input index-map pinned to block 0 so
  the B DMA happens once; full expand+matmul+fold every step),

so the encode kernel's defaults (``pallas_gemm.TPU_TILE`` / ``acc_dtype``)
stay justified by measurement, the way the reference justified its GF-table
strategy with the cpu-rs-* series (SURVEY.md C13).

Usage: python -m gpu_rscode_tpu.tools.kernel_sweep [--mb 64] [--trials 2]
"""

from __future__ import annotations

import argparse
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ..models.vandermonde import vandermonde_matrix
from ..ops.gemm import expand_bitmatrix_jnp, expand_nibblematrix_jnp
from .. import native
from ._bench_timing import time_device_fn as _time

K, P, W = 10, 4, 8


# --- kernel bodies ---------------------------------------------------------

def _body_base(a_ref, b_ref, o_ref, *, w, k, p):
    """Current production body: int32-domain expansion."""
    b = b_ref[:].astype(jnp.int32)
    tile = b.shape[-1]
    shifts = jax.lax.broadcasted_iota(jnp.int32, (1, w, 1), 1)
    planes = ((b[:, None, :] >> shifts) & 1).reshape(k * w, tile)
    acc = jnp.dot(
        a_ref[:], planes.astype(jnp.int8), preferred_element_type=jnp.int32
    )
    bits = acc & 1
    out_shifts = jax.lax.broadcasted_iota(jnp.int32, (1, w, 1), 1)
    o_ref[:] = jnp.sum(bits.reshape(p, w, tile) << out_shifts, axis=1).astype(
        o_ref.dtype
    )


def _body_cmp(a_ref, b_ref, o_ref, *, w, k, p):
    """Mask-compare expansion: (b & 2^s) != 0 — no variable shifts."""
    b = b_ref[:].astype(jnp.int32)
    tile = b.shape[-1]
    masks = jnp.left_shift(
        1, jax.lax.broadcasted_iota(jnp.int32, (1, w, 1), 1)
    )
    planes = ((b[:, None, :] & masks) != 0).reshape(k * w, tile)
    acc = jnp.dot(
        a_ref[:], planes.astype(jnp.int8), preferred_element_type=jnp.int32
    )
    bits = acc & 1
    out_shifts = jax.lax.broadcasted_iota(jnp.int32, (1, w, 1), 1)
    o_ref[:] = jnp.sum(bits.reshape(p, w, tile) << out_shifts, axis=1).astype(
        o_ref.dtype
    )


def _body_dma(a_ref, b_ref, o_ref, *, w, k, p):
    """DMA floor: forces the input block in, minimal compute."""
    o_ref[:] = b_ref[:p, :]


# The sign/nibble expanders are the production ones — the sweep must
# benchmark the exact formulations that ship.
from ..ops.pallas_gemm import _expand_nibble, _expand_shift_raw, _expand_sign


def _body_sign(a_ref, b_ref, o_ref, *, w, k, p):
    tile = b_ref.shape[-1]
    planes = _expand_sign(b_ref[:], w, k, tile)
    acc = jnp.dot(a_ref[:], planes, preferred_element_type=jnp.int32)
    bits = acc & 1
    out_shifts = jax.lax.broadcasted_iota(jnp.int32, (1, w, 1), 1)
    o_ref[:] = jnp.sum(bits.reshape(p, w, tile) << out_shifts, axis=1).astype(
        o_ref.dtype
    )


def _body_signc(a_ref, b_ref, o_ref, *, w, k, p):
    """Constant-shift unrolled variant of sign (no variable vector shift)."""
    tile = b_ref.shape[-1]
    bts = jax.lax.bitcast_convert_type(b_ref[:], jnp.int8)
    planes = jnp.stack(
        [(bts << jnp.int8(7 - s)) >> jnp.int8(7) for s in range(w)], axis=1
    ).reshape(k * w, tile)
    acc = jnp.dot(a_ref[:], planes, preferred_element_type=jnp.int32)
    bits = acc & 1
    out_shifts = jax.lax.broadcasted_iota(jnp.int32, (1, w, 1), 1)
    o_ref[:] = jnp.sum(bits.reshape(p, w, tile) << out_shifts, axis=1).astype(
        o_ref.dtype
    )


def _body_signf(a_ref, b_ref, o_ref, *, w, k, p):
    """sign expansion + MXU refold: out = F . (acc & 1) with F the (p, p*w)
    block-diagonal [1,2,...,128] weight — removes the VPU shift/sum fold."""
    tile = b_ref.shape[-1]
    planes = _expand_sign(b_ref[:], w, k, tile)
    acc = jnp.dot(a_ref[:], planes, preferred_element_type=jnp.int32)
    bits = (acc & 1).astype(jnp.int8)
    pow2 = (2 ** jnp.arange(w, dtype=jnp.int32)).astype(jnp.float32)
    fold = jnp.kron(jnp.eye(p, dtype=jnp.float32), pow2.reshape(1, w))
    out = jnp.dot(fold, bits.astype(jnp.float32), preferred_element_type=jnp.float32)
    o_ref[:] = out.astype(o_ref.dtype)


def _body_raw_dot(a_ref, b_ref, o_ref, *, w, k, p):
    """The round-4 production formulation (pallas_gemm defaults since
    2026-07-31): mask-free shift_raw expansion + MXU dot refold.  The
    (p, p*w) fold operator is built from iota ops in-kernel (Pallas
    kernels may not capture array constants; the production kernel passes
    it as an operand instead) and the output takes the f32 -> int32 ->
    uint8 chain Mosaic lowers (a direct f32 -> uint8 cast is refused)."""
    tile = b_ref.shape[-1]
    planes = _expand_shift_raw(b_ref[:], w, k, tile)
    acc = jnp.dot(
        a_ref[:], planes.astype(jnp.int8), preferred_element_type=jnp.int32
    )
    bits = (acc & 1).astype(jnp.bfloat16)
    r = jax.lax.broadcasted_iota(jnp.int32, (p, p * w), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (p, p * w), 1)
    F = jnp.where(
        c // w == r, jnp.left_shift(1, c % w), 0
    ).astype(jnp.bfloat16)
    out = jnp.dot(F, bits, preferred_element_type=jnp.float32)
    o_ref[:] = out.astype(jnp.int32).astype(o_ref.dtype)


def _body_nibble(a_ref, b_ref, o_ref, *, w, k, p):
    """One-hot nibble expansion against the (p*w, k*32) operator — the MXU
    analog of the reference's GF(16) nibble-table kernel (design.tex:485)."""
    tile = b_ref.shape[-1]
    planes = _expand_nibble(b_ref[:], w, k, tile)
    acc = jnp.dot(
        a_ref[:], planes.astype(jnp.int8), preferred_element_type=jnp.int32
    )
    bits = acc & 1
    out_shifts = jax.lax.broadcasted_iota(jnp.int32, (1, w, 1), 1)
    o_ref[:] = jnp.sum(bits.reshape(p, w, tile) << out_shifts, axis=1).astype(
        o_ref.dtype
    )


BODIES = {
    "base": _body_base,
    "cmp": _body_cmp,
    "dma": _body_dma,
    "sign": _body_sign,
    "signc": _body_signc,
    "signf": _body_signf,
    "nibble": _body_nibble,
    "raw_dot": _body_raw_dot,
}

# Bodies whose coefficient operator is the (p*w, k*32) one-hot-nibble form
# instead of the (p*w, k*w) bit operator.
NIBBLE_BODIES = {"nibble"}


def make_fn(name, A_bits, B, tile, pinned_input=False):
    p, k, w = P, K, W
    m = B.shape[1]
    tile = min(tile, m)
    body = functools.partial(BODIES[name], w=w, k=k, p=p)
    b_map = (lambda i: (0, 0)) if pinned_input else (lambda i: (0, i))
    a_cols = k * 32 if name in NIBBLE_BODIES else k * w

    @jax.jit
    def run(A_bits, B):
        return pl.pallas_call(
            body,
            out_shape=jax.ShapeDtypeStruct((p, m), jnp.uint8),
            grid=(pl.cdiv(m, tile),),
            in_specs=[
                pl.BlockSpec((p * w, a_cols), lambda i: (0, 0)),
                pl.BlockSpec((k, tile), b_map),
            ],
            out_specs=pl.BlockSpec((p, tile), lambda i: (0, i)),
        )(A_bits, B)

    return lambda: run(A_bits, B)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mb", type=int, default=64, help="stripe data MB")
    ap.add_argument("--trials", type=int, default=2)
    ap.add_argument(
        "--tiles", type=str, default="8192,16384,32768,65536"
    )
    ap.add_argument(
        "--bodies", type=str, default="base,cmp,sign,signc,signf,nibble,raw_dot",
        help="comma-separated subset of kernel bodies to sweep",
    )
    args = ap.parse_args()

    from ..obs.runlog import capture_header

    print(json.dumps(capture_header("kernel_sweep")), flush=True)
    bodies = [b.strip() for b in args.bodies.split(",") if b.strip()]
    unknown = [b for b in bodies if b not in BODIES]
    if unknown:
        ap.error(f"unknown --bodies {unknown}; choose from {sorted(BODIES)}")

    from ..utils.backend import tpu_devices_present

    assert tpu_devices_present(), "sweep is for real hardware"
    m = args.mb * 1024 * 1024 // K
    m = (m // 512) * 512
    A = vandermonde_matrix(P, K)
    rng = np.random.default_rng(0)
    B_host = rng.integers(0, 256, size=(K, m), dtype=np.uint8)
    A_bits = jax.device_put(
        np.asarray(expand_bitmatrix_jnp(jnp.asarray(A), W)).astype(np.int8)
    )
    A_nib = jax.device_put(
        np.asarray(expand_nibblematrix_jnp(jnp.asarray(A), W)).astype(np.int8)
    )
    Bd = jax.device_put(B_host)
    oracle = native.gemm(A, B_host[:, :4096])
    data_bytes = K * m

    tiles = [int(t) for t in args.tiles.split(",")]
    results = {}
    for name in bodies:
        for tile in tiles:
            fn = make_fn(name, A_nib if name in NIBBLE_BODIES else A_bits, Bd, tile)
            try:
                got = np.asarray(fn()[:, :4096])
                if np.array_equal(got, oracle):
                    dt = _time(fn, trials=args.trials)
                    results[f"{name}@{tile}"] = round(data_bytes / dt / 1e9, 2)
                else:
                    results[f"{name}@{tile}"] = "MISMATCH"
            except Exception as e:  # noqa: BLE001 — sweep must survive variants
                results[f"{name}@{tile}"] = f"fail:{type(e).__name__}"
            print(json.dumps({f"{name}@{tile}": results[f"{name}@{tile}"]}))

    # floors at the best measured tile across whatever bodies ran (not just
    # "base" — a --bodies subset without it must not silently pick tiles[0])
    def _tile_best(t):
        vals = [
            results.get(f"{b}@{t}")
            for b in bodies
            if isinstance(results.get(f"{b}@{t}"), float)
        ]
        return max(vals, default=0.0)

    best_tile = max(tiles, key=_tile_best)
    # The compute-only ceiling is measured on the production body when the
    # sweep includes it (raw_dot since round 4), else on "base".
    # Key naming: r1-r3 captures (kernel_floors_tpu_20260730T*) used plain
    # "compute_only" for what is now "compute_only[base]"; readers comparing
    # against old captures must map the legacy key to the [base] body.
    ceiling_body = "raw_dot" if "raw_dot" in bodies else "base"
    for name, pinned in (("dma", False), (ceiling_body, True)):
        key = "dma_floor" if name == "dma" else f"compute_only[{name}]"
        try:
            fn = make_fn(name, A_bits, Bd, best_tile, pinned_input=pinned)
            dt = _time(fn, trials=args.trials)
            results[key] = round(data_bytes / dt / 1e9, 2)
        except Exception as e:  # noqa: BLE001
            results[key] = f"fail:{type(e).__name__}"
        print(json.dumps({key: results[key]}))

    print(json.dumps({"mb": args.mb, "results": results}))


if __name__ == "__main__":
    main()
