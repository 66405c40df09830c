"""Fused Pallas TPU kernel for the GF(2^w) GEMM — the production hot loop.

Role parity: the reference's tiled shared-memory GF-GEMM kernel
(``matrix_mul``, matrix.cu:232-407) — the single kernel both encode and
decode dispatch.  TPU-first design, not a translation:

The XLA bitplane path (:mod:`.gemm`) materialises the (k*w, m) bit-plane
expansion of the data in HBM — 8x (int8) / 16x (bf16) the input bytes of
HBM traffic.  This kernel fuses the whole chain per column tile in VMEM:

    HBM uint8 (k, TILE) --DMA--> VMEM
      -> bit-expand on the VPU          (k, TILE)   -> (k*w, TILE)
      -> one MXU matmul with the (p*w, k*w) bit operator
      -> parity + refold on the VPU     (p*w, TILE) -> (p, TILE)
    VMEM uint8 (p, TILE) --DMA--> HBM

so HBM sees exactly 1x the input + 1x the output bytes — the kernel is
bandwidth-optimal.  The coefficient operator (a few KB) stays resident in
VMEM across the whole grid (the analog of the reference staging its GF
tables into __shared__, matrix.cu:36-39, except here it's the *matrix* that
is staged and the tables have been compiled away entirely).

Grid: 1-D over column tiles, the embarrassingly-parallel axis (the
reference's grid-stride column sweep, matrix.cu:265-322).  Out-of-range
columns in the last tile compute garbage on garbage and are dropped by the
masked output write Pallas performs automatically.

Three bit-expansion formulations (``expand``), all bit-verified in
interpret mode.  The kernel is compute-bound in every measured era: the
2026-07-30 captures had it at ~99 % of a 64.9 GB/s compute-only ceiling,
and after the round-4/5 algebraic reductions the post-flip floors
(kernel_floors_postflip_tpu_20260801T*) put it at ~97 % of a ~110 GB/s
ceiling, with the DMA floor far above (>= 170; readings scatter 125-333
across sessions, dma_floor_recheck_*):

* ``"shift"`` — plane s = (b >> s) & 1 in int32 lanes (proven default).
* ``"sign"``  — plane s = (int_w)(b << (w-1-s)) >> (w-1), i.e. {0, -1},
  staying in w-bit lanes (4x VPU packing for w=8).  -1 === 1 (mod 2), so
  the parity of the integer accumulator — all the refold reads — is
  unchanged.
* ``"nibble"`` (w=8) — one-hot of the high/low nibbles (32 rows per data
  byte) against the (p*w, k*32) one-hot-nibble operator (gf.nibble_mats):
  compare-based VPU expansion, 4x the MXU contraction depth.  The MXU
  analog of the reference's fastest kernel — the GF(16) nibble-table
  branch (design.tex:485 9.12 ms vs 160.5 ms; gf16.h:1-22).

Hardware verdict (2026-07-31, real v5e, committed captures
bench_captures/expand_r4b_* / expand_r4c_*): the production default is
``expand="shift_raw"`` plus, at w=8, ``refold="dot"`` — the mask-free
expansion beat ``shift`` at every probed shape, and moving the parity
refold onto the MXU beat the VPU shift-sum at every probed w=8 shape.
Headline (k=10, p=4): 105.5 GB/s end-to-end encode / 105.6 decode with
3.18 ms 4-erasure recovery (bench_tpu_20260801T000810Z — was 64.7/64.7
under shift+sum); raw GEMM 109.8 @ k=10, 152.5 @ k=32, 159.8 @ k=64,
167.4 @ k=128 (post-flip k-sweep).  w=16 measured 101.9 under
shift_raw (was 90.3 under shift) with the "sum" refold.  The r4c
w16+dot timeout was not a hang (both small-shape re-probes returned
rc=0, w16_small_*_tpu_20260801T*) —
but the r5c crossover sweep showed w16+dot is BIMODAL at fixed shape
(mb=128: 84.8 / 82.3 / 147.6 across three runs; mb=64: 142.3; mb=320:
147.0; mb=32: 8.2) where sum is stable (101.7-102.6 at every probed
size, w16_cross_*_tpu_20260801T*).  The r5e tile map pinned the cause
as NOT tile-dependent: at mb=128, tile 8192 read 136.9 then 52.4 and
tile 16384 read 144.4 / 132.0 in-session against 84.8 / 82.3 / 147.6
for the same shape in the prior session (w16_bimodal_t*_tpu_20260801T*)
— every slow reading was a best-of-trials WITHIN one process, so the
mode is fixed at (re)compile time, i.e. compile nondeterminism, not a
per-dispatch or per-tile effect.  A default that
regresses below sum on a coin-flip compile is not shippable, so w=16
keeps "sum"; RS_PALLAS_REFOLD=dot opts into the 132-147 GB/s fast mode
for callers who can tolerate the variance, and RS_PALLAS_REFOLD=autotune
times both variants once per compiled shape class and ships whichever
mode THIS process's compile produced (fast-dot when the coin lands
right, sum otherwise — the operational answer to nondeterminism a
static default cannot give).  ``"sign"`` and ``"nibble"``
do NOT
lower on the current Mosaic toolchain (sign: ``arith.subi`` on int8
vectors fails to legalize; nibble: 8-bit iota unsupported; reworked
int32-iota formulations crash the compile helper) — see
bench_captures/tile_pick_tpu_*.jsonl and expand_probe_tpu_*.jsonl.  They
remain available for interpret mode (bit-verified in CI) and future
toolchains.  Probed and rejected on measurement: a packed uint8
mask-compare variant (40.7 vs 64.4 shift), and ``pack2`` — correct only
under ``Precision.HIGHEST`` (packed lanes reach 257, which the default
bf16 MXU pass rounds to 256) whose multi-pass cost sinks it to 2.4 GB/s
(expand_r4b_decode capture).
"""

from __future__ import annotations

import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs import metrics as _obs_metrics
from .gf import get_field

DEFAULT_TILE = 2048      # interpret / CPU-mesh default
# Measured best on real v5e, production path, 320 MB per timed call
# (bench_captures/tile_pick_tpu_20260730T050344Z.jsonl: 64.33 @ 16384, 64.63 @
# 32768 — a tie within run-to-run jitter; 47.11 @ 8192, 56.91 @ 65536).
TPU_TILE = 16384
# Mosaic's scoped-VMEM limit on v5e.  A kernel whose blocks and live
# values exceed it is refused at compile time ("Ran out of memory in
# memory space vmem ... limit 16.00M"), so the default tile shrinks until
# the estimate in _vmem_bytes fits (see fit_tile).
SCOPED_VMEM_BYTES = 16 << 20
# Depth-split history: the 2026-07-31 PRE-flip k-sweep
# (k_sweep_tpu_20260731T010808Z.jsonl) had bf16@32768 winning at
# contraction depth k*w >= 256, so rounds 4-5 shipped a deep-config
# split.  The POST-flip re-sweep under the production shift_raw+dot
# kernel (k_sweep_postflip_tpu_20260801T002730Z.jsonl) RETIRED it:
# int8 wins at every k (k=32: 152.5 vs bf16's 119.0; k=64: 159.8 vs
# 136.7; k=128: 167.4 vs 140.2), tile 16384 is within ~5 % of the best
# tile at every depth, and int8@32768 at depth 1024 fails to compile
# (compile helper HTTP 500) — so int8@TPU_TILE is the one hardware
# default at w=8.  Unlike the reference, which degrades for k >= 32
# (design.tex:462-466), throughput GROWS with k: the p*w-row output
# refold amortizes over more input rows.


def _vmem_bytes(k, p, w, tile, a_cols, fold):
    """Scoped VMEM one grid step of the fused kernel needs at ``tile``.

    Double-buffered in/out blocks and operator blocks, plus the live
    values of the kernel body: the int8 bit planes (a_cols, tile) and
    the int32 accumulator (p*w, tile).  The pre-parity form's output
    block is that int32 accumulator.  Checked against the v5e compiler
    (tests/test_tpu_compile.py): it reported 20.74M at (k=128, p=16,
    tile 16384, dot refold), 18.36M at (32, 32, 16384) and 20.00M for the
    pre-parity (128, 16, 16384) block set — each at or under this sum."""
    sym = w // 8
    out_rows, out_bytes = (p, sym) if fold else (p * w, 4)
    io = 2 * tile * (k * sym + out_rows * out_bytes)
    ops = 2 * (p * w * a_cols + p * p * w * 2)
    live = tile * (a_cols + p * w * 4)
    return io + ops + live


def fit_tile(k, p, w, tile, a_cols=None, fold=True):
    """Largest 128-multiple tile <= ``tile`` whose kernel fits the
    scoped VMEM (k=10, p=4 keeps 16384; k=128, p=16 drops below 9000)."""
    a_cols = k * w if a_cols is None else a_cols
    while tile > 128 and _vmem_bytes(k, p, w, tile, a_cols, fold) > (
        SCOPED_VMEM_BYTES
    ):
        tile -= 128
    return tile


def _expand_shift(b, w, k, tile):
    b = b.astype(jnp.int32)
    in_shifts = jax.lax.broadcasted_iota(jnp.int32, (1, w, 1), 1)
    return ((b[:, None, :] >> in_shifts) & 1).reshape(k * w, tile)


def _expand_shift_raw(b, w, k, tile):
    # ``shift`` without the ``& 1`` — the round-4 algebraic shortcut.  The
    # matmul's accumulator is only ever read modulo 2 (XOR == parity), and
    # (b >> s) === bit_s (mod 2): every higher bit of the unmasked plane
    # contributes an even term (2^(t-s) for t > s), invisible to parity.
    # The int8 MXU cast wraps plane values mod 256 (even — parity-safe, and
    # two's-complement v-256 === v mod 2), products are exact in int32
    # (|sum| <= k*w*128 << 2^31), and the f32 path is exact below 2^24.
    # Net effect: w fewer VPU mask ops per input byte on the kernel's
    # bottleneck (the r3 floors capture pinned the kernel compute-bound on
    # expansion at ~65 of 286 GB/s DMA floor).
    b = b.astype(jnp.int32)
    in_shifts = jax.lax.broadcasted_iota(jnp.int32, (1, w, 1), 1)
    return (b[:, None, :] >> in_shifts).reshape(k * w, tile)


def _expand_sign(b, w, k, tile):
    sdt = jnp.int8 if w == 8 else jnp.int16
    bts = jax.lax.bitcast_convert_type(b, sdt)
    shifts = jax.lax.broadcasted_iota(jnp.int32, (1, w, 1), 1).astype(sdt)
    lsh = sdt(w - 1) - shifts
    return ((bts[:, None, :] << lsh) >> sdt(w - 1)).reshape(k * w, tile)


def _expand_nibble(b, w, k, tile):
    # One-hot of the high and low nibbles, int8 lanes throughout: 32 rows
    # per data byte, selecting columns of the (p*w, k*32) nibble operator
    # (gf.nibble_mats).  Trades 4x MXU work (affordable: the kernel runs at
    # a small fraction of int8 peak) for compare-based expansion on the VPU.
    v = jax.lax.broadcasted_iota(jnp.uint8, (1, 16, 1), 1)
    hi = (b >> 4)[:, None, :]
    lo = (b & 0xF)[:, None, :]
    planes = jnp.concatenate([hi == v, lo == v], axis=1)  # (k, 32, tile)
    return planes.reshape(k * 32, tile)


# ---- round-4 probe formulations (VERDICT r3 item 2 / 8) -------------------
# The 2026-07 Mosaic verdicts pinned the sign/nibble failures to 8-bit iota
# and int8 arith.subi; every formulation below avoids BOTH (shift amounts
# are numpy CONSTANTS, no iota op; no int8 subtraction).  All bit-verified
# in interpret mode; hardware verdicts land in
# bench_captures/expand_probe_* via tools/expand_probe.py.
# Hardware verdicts 2026-07-31 (a pre-PR-1 capture, deleted in PR 21):
# packed32 hits an unimplemented Mosaic bitcast; sign16/shift_u8/
# nibble_const crash the compile helper — no narrow-lane VPU
# formulation lowers on this toolchain.  The follow-ups that stay in the
# lowerable int32-lane family are ``shift_raw`` (above) and ``pack2``
# (``_kernel_pack2``): two bytes per int32 lane via an XLA-level uint16
# bitcast OUTSIDE the kernel, f32 MXU contraction with 8-bit parity
# fields (exact below depth 256), and a packed refold whose lane value is
# already the two output bytes — half the VPU work per byte at both ends.


def _expand_packed32(b, w, k, tile):
    # VERDICT r3 candidate (b): int32 lane packing.  Bitcast 4 data bytes
    # into one int32 lane, extract a bit-plane of all 4 with ONE shift-mask
    # pass (mask 0x01010101), bitcast back to int8 — 4x fewer VPU lane-ops
    # per plane than per-byte int32 shifts; the MXU sees ordinary int8
    # planes.  w=8 only (byte-granular packing).  Shift amounts are
    # python-unrolled scalar immediates (Pallas kernels may not capture
    # array constants, and vector shift-amounts would need the iota Mosaic
    # refuses in narrow types).
    p32 = jax.lax.bitcast_convert_type(
        b.reshape(k, tile // 4, 4), jnp.int32
    )  # (k, tile/4)
    planes32 = jnp.stack(
        [(p32 >> jnp.int32(s)) & jnp.int32(0x01010101) for s in range(w)],
        axis=1,
    )  # (k, w, tile/4)
    planes8 = jax.lax.bitcast_convert_type(planes32, jnp.int8)
    return planes8.reshape(k * w, tile)  # (k, w, tile/4, 4) -> rows of bits


def _expand_sign16(b, w, k, tile):
    # VERDICT r3 candidate (d): sign-replication in int16-only lanes (2x
    # VPU packing vs int32) — bit s to the sign position, arithmetic shift
    # back to {0, -1}; -1 === 1 (mod 2) so the parity refold is unchanged.
    # Scalar-immediate shifts, unrolled: no int8 ops, no iota.
    bts = b.astype(jnp.int16)
    planes = jnp.stack(
        [(bts << jnp.int16(15 - s)) >> jnp.int16(15) for s in range(w)],
        axis=1,
    )
    return planes.reshape(k * w, tile)


def _expand_shift_u8(b, w, k, tile):
    # Python-unrolled CONSTANT shifts in uint8 lanes: no iota, no subi,
    # 4x lane packing vs int32, w compiled-in copies of one shift-mask op.
    planes = [(b >> np.uint8(s)) & np.uint8(1) for s in range(w)]
    return jnp.stack(planes, axis=1).reshape(k * w, tile)


def _expand_nibble32(b, w, k, tile):
    # The nibble one-hot (the reference's fastest-kernel idea, gf16.h:1-22)
    # carried entirely in int32 lanes — the only lane width the Mosaic
    # toolchain has lowered for this kernel (r3/r4 verdicts: every 8/16-bit
    # formulation fails legalization or crashes the compile helper).
    # 32 compares per input byte on the VPU buy a 4x-deeper MXU
    # contraction against the (p*w, k*32) one-hot operator; the k-sweep
    # capture shows deep contractions RAISE throughput, so the trade is
    # plausible where compares are cheaper than shifts.  Compare constants
    # are python-unrolled scalar immediates (no iota).
    v = b.astype(jnp.int32)
    hi = v[:, None, :] >> np.int32(4)
    lo = v[:, None, :] & np.int32(15)
    planes = jnp.concatenate(
        [hi == np.int32(c) for c in range(16)]
        + [lo == np.int32(c) for c in range(16)],
        axis=1,
    )  # (k, 32, tile) bool
    return planes.reshape(k * 32, tile)


def _expand_nibble_const(b, w, k, tile):
    # The nibble one-hot (reference's fastest-kernel idea, gf16.h:1-22)
    # with the 16 compare values python-unrolled as scalar immediates
    # instead of the 8-bit iota Mosaic refuses.
    hi = b >> np.uint8(4)
    lo = b & np.uint8(0xF)
    planes = jnp.stack(
        [hi == np.uint8(v) for v in range(16)]
        + [lo == np.uint8(v) for v in range(16)],
        axis=1,
    )  # (k, 32, tile)
    return planes.reshape(k * 32, tile)


def _kernel_pack2(a_ref, b_ref, o_ref, *, w: int, k: int, p: int):
    # Two data bytes per int32 lane (VERDICT r3 candidate (b), realized
    # without the in-kernel bitcast Mosaic refuses: the uint16 view is an
    # XLA-level bitcast OUTSIDE the kernel).  Each plane row holds bit s of
    # BOTH bytes at int32 bit positions 0 and 8 (mask 0x0101); the f32
    # matmul accumulates the two parity fields independently — field sums
    # are bounded by the contraction depth k*w < 256, so no cross-field
    # carry, and every value is far below 2^24 (f32-exact on the MXU).
    # The packed shift-sum refold then produces, per lane, exactly
    # lo_out + 256*hi_out — i.e. the uint16 of the two output bytes in the
    # same byte order the input bitcast used (the algebra is symmetric
    # under endianness, so the pair of bitcasts cancels either way).
    # Net: HALF the VPU lane-ops per byte in BOTH expansion and refold.
    tile2 = b_ref.shape[-1]
    v = b_ref[:].astype(jnp.int32)
    planes = jnp.stack(
        [(v >> np.int32(s)) & np.int32(0x0101) for s in range(w)], axis=1
    ).reshape(k * w, tile2)
    # Precision.HIGHEST is load-bearing on hardware: packed lanes take the
    # value 257 (both fields set), which needs 9 significand bits — the
    # MXU's default bf16 pass rounds it to 256, corrupting the low field
    # (observed OracleMismatch, expand_r4b_k10_tpu_20260731T031556Z.jsonl).
    # HIGHEST runs the multi-pass bf16 decomposition, exact for f32 inputs.
    acc = jnp.dot(
        a_ref[:], planes.astype(jnp.float32),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    bits = acc.astype(jnp.int32) & 0x0101
    out_shifts = jax.lax.broadcasted_iota(jnp.int32, (1, w, 1), 1)
    o_ref[:] = (
        jnp.sum(bits.reshape(p, w, tile2) << out_shifts, axis=1)
        .astype(jnp.uint16)
    )


def _kernel(
    a_ref, b_ref, o_ref, *, w: int, k: int, p: int, acc_dtype, expand, fold
):
    _kernel_body(a_ref, b_ref, None, o_ref, w=w, k=k, p=p,
                 acc_dtype=acc_dtype, expand=expand, fold=fold)


def _kernel_dotfold(
    a_ref, b_ref, f_ref, o_ref, *, w: int, k: int, p: int, acc_dtype, expand,
    fold,
):
    _kernel_body(a_ref, b_ref, f_ref, o_ref, w=w, k=k, p=p,
                 acc_dtype=acc_dtype, expand=expand, fold=fold)


def _kernel_body(
    a_ref, b_ref, f_ref, o_ref, *, w, k, p, acc_dtype, expand, fold
):
    tile = b_ref.shape[-1]
    expander = {
        "sign": _expand_sign,
        "nibble": _expand_nibble,
        "shift": _expand_shift,
        "shift_raw": _expand_shift_raw,
        "packed32": _expand_packed32,
        "sign16": _expand_sign16,
        "shift_u8": _expand_shift_u8,
        "nibble_const": _expand_nibble_const,
        "nibble32": _expand_nibble32,
    }[expand]
    planes = expander(b_ref[:], w, k, tile)
    acc = jnp.dot(
        a_ref[:].astype(acc_dtype),
        planes.astype(acc_dtype),
        preferred_element_type=jnp.float32 if acc_dtype != jnp.int8 else jnp.int32,
    )
    if not fold:
        # Pre-parity mode: emit the raw (p*w, tile) integer bit-plane
        # accumulators so a cross-device psum can extend the XOR-as-sum
        # before parity is taken (stripe-sharded GEMM, parallel/sharded.py).
        o_ref[:] = acc.astype(jnp.int32)
        return
    # Parity: XOR == sum mod 2.  Holds for the sign formulation too:
    # two's-complement (-n) & 1 == n & 1, and f32->int32 truncation is exact
    # for these small integers.
    bits = acc.astype(jnp.int32) & 1
    if f_ref is not None:
        # MXU refold: out = F . bits with F (p, p*w) the constant
        # bit-weight operator (2^s on the diagonal blocks, passed as an
        # operand — Pallas kernels may not capture array constants).  The
        # VPU's per-output shift + w-way sum becomes one tiny bf16 matmul;
        # exact in f32 (values <= 2^w - 1 < 2^24).
        # f32 -> int32 -> uint8/16: Mosaic refuses a direct f32 -> uint8
        # cast (expand_r4b_k10_dot_tpu_20260731T031850Z.log); the int32 hop
        # is the same cast chain the sum refold lowers with.
        o_ref[:] = jnp.dot(
            f_ref[:], bits.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        ).astype(jnp.int32).astype(o_ref.dtype)
        return
    out_shifts = jax.lax.broadcasted_iota(jnp.int32, (1, w, 1), 1)
    o_ref[:] = (
        jnp.sum(bits.reshape(p, w, tile) << out_shifts, axis=1)
        .astype(o_ref.dtype)
    )


@functools.partial(jax.jit, static_argnames=("w", "tile", "interpret"))
def _pallas_matmul_pack2(A, B, w, tile, interpret):
    from .gemm import expand_bitmatrix_jnp

    p, k = A.shape
    _, m = B.shape
    a_op = expand_bitmatrix_jnp(A, w).astype(jnp.float32)
    pad = m % 2
    if pad:
        B = jnp.pad(B, ((0, 0), (0, 1)))
    m2 = (m + pad) // 2
    B16 = jax.lax.bitcast_convert_type(B.reshape(k, m2, 2), jnp.uint16)
    # Same alignment rule as _pallas_matmul: the halved tile must stay
    # lane-aligned (tile//2 of an odd-128-multiple tile is not).
    tile2 = min(tile // 2, ((m2 + 127) // 128) * 128)
    tile2 = ((tile2 + 127) // 128) * 128
    grid = (pl.cdiv(m2, tile2),)
    out16 = pl.pallas_call(
        functools.partial(_kernel_pack2, w=w, k=k, p=p),
        out_shape=jax.ShapeDtypeStruct((p, m2), jnp.uint16),
        grid=grid,
        in_specs=[
            pl.BlockSpec((p * w, k * w), lambda i: (0, 0)),
            pl.BlockSpec((k, tile2), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((p, tile2), lambda i: (0, i)),
        interpret=interpret,
        name="gf_matmul_pack2",
    )(a_op, B16)
    out = jax.lax.bitcast_convert_type(out16, jnp.uint8).reshape(p, 2 * m2)
    return out[:, :m] if pad else out


@functools.partial(
    jax.jit,
    static_argnames=(
        "w", "tile", "acc_dtype", "interpret", "expand", "fold", "refold",
    ),
)
def _pallas_matmul(
    A, B, w, tile, acc_dtype, interpret, expand, fold=True, refold="sum"
):
    gf = get_field(w)
    p, k = A.shape
    _, m = B.shape
    # Expand the coefficient matrix to its GF(2) operator on the host side of
    # the graph (tiny; XLA folds it when A is a constant).  The bit-plane
    # expansions pair with the (p*w, k*w) bit operator; the nibble expansion
    # pairs with the (p*w, k*32) one-hot-nibble operator (the MXU analog of
    # the reference's GF(16) nibble-table strategy, gf16.h:1-22,
    # cpu-rs-double.c:52-55).
    from .gemm import expand_bitmatrix_jnp, expand_nibblematrix_jnp

    if expand in ("nibble", "nibble_const", "nibble32"):
        a_op = expand_nibblematrix_jnp(A, w)
        a_cols = k * 32
    else:
        a_op = expand_bitmatrix_jnp(A, w)
        a_cols = k * w
    a_bits = a_op.astype(jnp.int8 if acc_dtype == jnp.int8 else acc_dtype)
    out_dtype = jnp.uint8 if gf.dtype == np.uint8 else jnp.uint16
    # Clamp to m rounded up to the lane width, then round the tile itself
    # up to the lane width, so the block shape stays 128-aligned for ANY
    # tile origin (defaults, RS_PALLAS_TILE, explicit arguments, pack2's
    # halving); the last tile's overhang is masked by Pallas.  A
    # misaligned block would fail Mosaic lowering on hardware.
    tile = min(tile, ((m + 127) // 128) * 128)
    tile = ((tile + 127) // 128) * 128
    grid = (pl.cdiv(m, tile),)
    out_rows = p if fold else p * w
    in_specs = [
        pl.BlockSpec((p * w, a_cols), lambda i: (0, 0)),
        pl.BlockSpec((k, tile), lambda i: (0, i)),
    ]
    operands = [a_bits, B]
    if fold and refold == "dot":
        # (p, p*w) bit-weight fold operator: F[i, i*w + s] = 2^s.
        F = jnp.asarray(
            np.kron(np.eye(p), (1 << np.arange(w))[None, :]), jnp.bfloat16
        )
        kernel = functools.partial(
            _kernel_dotfold, w=w, k=k, p=p, acc_dtype=acc_dtype,
            expand=expand, fold=fold,
        )
        in_specs.append(pl.BlockSpec((p, p * w), lambda i: (0, 0)))
        operands.append(F)
    else:
        kernel = functools.partial(
            _kernel, w=w, k=k, p=p, acc_dtype=acc_dtype, expand=expand,
            fold=fold,
        )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(
            (out_rows, m), out_dtype if fold else jnp.int32
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((out_rows, tile), lambda i: (0, i)),
        interpret=interpret,
        # A stable kernel name for the profiler's device ops.
        name="gf_matmul",
    )(*operands)


# refold="autotune" decisions, keyed by the full dispatch configuration
# (shapes + dtypes + kernel config).  The w16 bimodality evidence
# (w16_bimodal_t*_tpu_20260801T*) shows the dot refold's fast/slow mode is
# fixed at (re)compile time — every slow reading was a best-of-trials
# WITHIN one process — so one timed calibration per compiled shape class
# is sound for the process lifetime: XLA's jit cache keeps that exact
# compilation alive, and a new shape class gets its own calibration.
# Writes go through _AUTOTUNE_LOCK (concurrent codec threads can race the
# same cold key; the worst pre-lock case was a benign duplicate
# calibration — the lock also makes the read accessors consistent).
# External readers use autotune_decisions(), never the dict itself.
_AUTOTUNE_CACHE: dict = {}
_AUTOTUNE_LOCK = threading.Lock()


def autotune_decisions() -> dict:
    """Snapshot of the refold='autotune' calibration results, keyed by
    dispatch configuration (shapes, dtypes, w, tile, acc_dtype, expand,
    interpret) with values "sum"/"dot".  The supported read surface for
    tools and benches (tools/w16_bench.py) — the backing dict is private
    and lock-guarded."""
    with _AUTOTUNE_LOCK:
        return dict(_AUTOTUNE_CACHE)


def clear_autotune_cache() -> None:
    """Drop every calibration decision.  Pair with ``jax.clear_caches()``:
    a decision is only sound while the executable it timed stays alive in
    XLA's jit cache — after an eviction the next compile re-flips the w16
    fast/slow coin while a stale pinned "dot" would silently re-expose the
    slow mode (ADVICE r5 finding 2).  Also invoked by the execution-plan
    cache's clear() (plan.PLAN_CACHE), which pins refold choices into AOT
    executables the same way."""
    with _AUTOTUNE_LOCK:
        _AUTOTUNE_CACHE.clear()

# Require a real win before preferring the variable mode: ties and noise
# go to the stable "sum".  The measured gap is wide on both sides (dot
# fast 132-147 vs sum ~102 vs dot slow 52-85 GB/s at w=16), so any
# margin in (0.7, 1.0) separates the modes; 0.9 leaves room for
# dispatch jitter.
_AUTOTUNE_MARGIN = 0.9


def _time_refold(run) -> float:
    """Best-of-2 wall time of ``run()`` after a compile/warm-up call.

    Separated out so tests can monkeypatch deterministic timings.
    """
    import time

    jax.block_until_ready(run())
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        jax.block_until_ready(run())
        best = min(best, time.perf_counter() - t0)
    return best


def _autotune_refold(A, B, w, tile, acc_dtype, interpret, expand) -> str:
    """Resolve ``refold="autotune"`` to "sum" or "dot" by timing both
    compiled kernels once on the actual operands.

    Motivated by w=16, where the dot refold is bimodal ACROSS compiles
    (compile nondeterminism, not tile- or dispatch-
    dependent — see the module docstring) so no static default can ship
    its 132-147 GB/s fast mode safely; a per-process calibration can:
    whichever mode this process compiled is the mode every subsequent
    same-shape dispatch reuses.  A slow-mode compile falls back to the
    stable "sum"; a variant that fails to compile raises.
    """
    key = (
        tuple(A.shape), str(A.dtype), tuple(B.shape), str(B.dtype), w, tile,
        str(acc_dtype), expand, interpret,
    )
    with _AUTOTUNE_LOCK:
        hit = _AUTOTUNE_CACHE.get(key)
    if hit is not None:
        return hit
    times = {
        cand: _time_refold(
            lambda: _pallas_matmul(
                A, B, w, tile, acc_dtype, interpret, expand,
                fold=True, refold=cand,
            )
        )
        for cand in ("sum", "dot")
    }
    choice = (
        "dot"
        if times["dot"] < _AUTOTUNE_MARGIN * times["sum"]
        else "sum"
    )
    _obs_metrics.counter(
        "rs_pallas_autotune_total",
        "refold autotune calibrations by winning candidate",
    ).labels(choice=choice, mode="eager", w=w).inc()
    with _AUTOTUNE_LOCK:
        # First writer wins: a thread that raced the same cold key already
        # proved its (identical) choice; keep the cache write-once per key.
        return _AUTOTUNE_CACHE.setdefault(key, choice)


def _default_refold(w: int) -> str:
    """The static per-width refold default: "dot" at w=8 (wins every
    probed shape — expand_r4b/r4c captures), "sum" elsewhere (at w=16
    dot is a compile-time coin flip; see the module docstring).  One
    definition shared by the env-fallback, pre-parity and tracer-guard
    resolution paths."""
    return "dot" if w == 8 else "sum"


def static_refold(w: int) -> str | None:
    """RS_PALLAS_REFOLD resolved to a static "sum"/"dot" with NO warning:
    "autotune" (and unknown values) map to the per-width default.  For
    dispatch sites that always run under a jit/shard_map trace — the mesh
    cols-sharded path — where calibration is impossible by construction
    and the tracer-guard's 'cannot calibrate' warning would fire on every
    trace, false-alarming the verify skill's warning check on perfectly
    healthy mesh runs (ADVICE r5 finding 3).  Returns ``None`` when the
    expand env resolves to pack2 (its fixed packed-refold pipeline REJECTS
    an explicit refold; the pack2 path returns before any refold env read,
    so ``None`` is both required and warning-safe there).  An UNKNOWN env
    value keeps the module's warn-and-fall-back hygiene — only the
    documented "autotune"→default mapping is silent."""
    import os

    if os.environ.get("RS_PALLAS_EXPAND") == "pack2" and w == 8:
        return None
    env = os.environ.get("RS_PALLAS_REFOLD")
    if env in ("sum", "dot"):
        return env
    if env and env != "autotune":
        return _env_fallback(
            f"RS_PALLAS_REFOLD={env!r} is unknown", _default_refold(w)
        )
    return _default_refold(w)


def plan_refold_resolution(w: int) -> str | None:
    """The refold an AOT execution plan (plan.ExecutionPlan) should bake:
    every non-calibrating case delegates to :func:`static_refold` (env
    pass-through, typo fallback, pack2's ``None`` — the mesh path and AOT
    plans must never bake DIFFERENT resolutions of the same env), while
    ``"autotune"`` is returned AS the string ``"autotune"`` so the plan
    calibrates against its OWN executables via
    :func:`calibrate_aot_refold` — the eager path's cached decision
    described a different compile, and dot speed at w=16 is per-compile
    bimodal (see the module docstring), so inheriting it would silently
    re-expose the slow mode the calibration exists to avoid."""
    import os

    # Derive, don't duplicate, static_refold's pack2 gate: a None static
    # resolution means pack2 applies and refold must stay unset — only a
    # refold-bearing pipeline may escalate to per-plan calibration.
    s = static_refold(w)
    if s is not None and os.environ.get("RS_PALLAS_REFOLD") == "autotune":
        return "autotune"
    return s


def calibrate_aot_refold(A, B, w, compile_variant):
    """Resolve ``refold="autotune"`` for one AOT plan build by timing the
    two candidates AS COMPILED BY THE CALLER on the actual operands —
    ``compile_variant(refold)`` must return the plan's own compiled
    executable for that refold.  Returns ``(choice, executable)`` so the
    winner's compile is not repeated.  The eager decision cache is
    deliberately NOT consulted or written: each decision is only sound
    for the executable it timed."""
    times, exes = {}, {}
    for cand in ("sum", "dot"):
        exe = exes[cand] = compile_variant(cand)
        times[cand] = _time_refold(lambda: exe(A, B))
    choice = (
        "dot"
        if times["dot"] < _AUTOTUNE_MARGIN * times["sum"]
        else "sum"
    )
    _obs_metrics.counter(
        "rs_pallas_autotune_total",
        "refold autotune calibrations by winning candidate",
    ).labels(choice=choice, mode="aot", w=w).inc()
    return choice, exes[choice]


def _default_expand(w: int, acc_dtype) -> str:
    """The production default that APPLIES at this (w, acc_dtype):
    shift_raw (faster at every probed shape — expand_r4b_*/expand_r4c_*
    captures), except w=16 with an explicitly non-int8 accumulator, where
    shift_raw's unmasked 16-bit planes would exceed bf16's exact-integer
    range and the masked shift formulation is the production choice."""
    if w == 16 and acc_dtype is not None and acc_dtype != jnp.int8:
        return "shift"
    return "shift_raw"


def _env_fallback(reason: str, to, label: str | None = None):
    """Warn-and-fall-back hygiene shared by every RS_PALLAS_* env knob
    (EXPAND / REFOLD / TILE): an env value that is unknown or inapplicable
    must neither crash production nor silently record a capture under a
    non-default configuration — the fallback target is the production
    default that applies, named in one uniformly-worded warning."""
    import warnings

    warnings.warn(f"{reason}; using {label or repr(to)}", stacklevel=3)
    return to


def gf_matmul_pallas(
    A,
    B,
    w: int = 8,
    tile: int | None = None,
    acc_dtype=None,
    interpret: bool | None = None,
    expand: str | None = None,
    fold_parity: bool = True,
    refold: str | None = None,
):
    """``C = A . B`` over GF(2^w) via the fused Pallas kernel.

    ``fold_parity=False`` returns the raw (p*w, m) int32 bit-plane
    accumulators instead of folded GF elements — the pre-parity form a
    stripe-sharded caller psums across devices before folding with
    :func:`..gemm.from_bitplanes` (XOR == total sum mod 2 must be taken
    AFTER the cross-device reduction).

    ``acc_dtype``: matmul input dtype — ``int8`` (int32 accumulation, exact
    for contraction depth < 2^31; 2x MXU rate on v5e) or ``bfloat16`` (f32
    accumulation, exact for depth < 2^24).  Both bit-verified; the TPU
    default is int8 @ tile 16384 at EVERY depth — the post-flip k-sweep
    (k_sweep_postflip_tpu_20260801T002730Z.jsonl) retired the old
    bf16-at-depth>=256 split: under shift_raw+dot, int8 wins at every k
    (152.5-167.4 GB/s at k=32-128 vs bf16's 119-140) and int8@32768
    fails to compile at depth 1024.
    ``expand``: data-expansion formulation — "shift_raw" (default; any
    width, but w=16 needs acc_dtype=int8 — unmasked planes exceed bf16's
    exact-integer range, so a w=16 call with an explicit non-int8
    acc_dtype defaults to "shift" instead), "shift" (any width), "sign"
    (w=8/16), or the
    byte-granular set "nibble"/"nibble_const"/"nibble32"/"packed32"/
    "sign16"/"shift_u8"/"pack2" (w=8 only; the nibble family one-hots
    against the (p*w, k*32) operator; see module docstring).  "pack2" additionally
    requires fold_parity=True and runs a fixed f32/packed-refold pipeline
    (passing acc_dtype or refold with it raises); contractions deeper than
    k*w < 256 split into carry-free depth slices XORed together.  On the
    current TPU toolchain only "shift"/"shift_raw"/"pack2" lower to
    hardware — pack2 correctly only under Precision.HIGHEST, whose cost
    sinks it to 2.4 GB/s (rejected; see module docstring).  "nibble32"
    (the nibble one-hot in int32 lanes, the lowerable lane width) is
    hardware-REFUSED too: it crashes the tpu_compile_helper
    (HTTP 500; a pre-PR-1 capture, deleted in PR 21), the same wall
    as every r4 narrow-lane candidate; it and the remaining modes fail
    on hardware (bench_captures/expand_probe_*) and serve interpret
    mode only.
    ``refold``: how the kernel folds accumulator parities back into GF
    elements — "dot" (MXU: one tiny bf16 matmul against the (p, p*w)
    bit-weight operator; exact in f32 for any supported w) or "sum"
    (VPU: bits << s summed over w), or "autotune" — time both compiled
    variants once on the actual operands and cache the winner per shape
    class (ties/noise go to "sum"; intended for w=16, where the dot
    refold's speed is a compile-time coin flip — see _autotune_refold).
    Default: "dot" at w=8 (the width the captures validate), "sum"
    elsewhere until a width-specific capture lands.  Env-overridable via
    RS_PALLAS_REFOLD.
    ``interpret`` defaults to True off-TPU so the same code path runs under
    the CPU test mesh.
    """
    _BYTE_ONLY = (
        "nibble", "nibble_const", "nibble32", "packed32", "sign16",
        "shift_u8", "pack2",
    )
    _ANY_W = ("shift", "shift_raw")
    from_env = False
    if expand is None:
        # Production default, overridable for whole-pipeline hardware
        # experiments (e.g. RS_PALLAS_EXPAND=packed32 python bench.py)
        # without touching call sites; the literal default only changes
        # with a committed capture justifying it.  An env value that is
        # unknown or inapplicable at this width falls back WITH a warning
        # to the production default that applies (_default_expand) — an
        # env typo must neither crash production nor silently record a
        # capture under a non-default formulation.
        import os

        env = os.environ.get("RS_PALLAS_EXPAND")
        from_env = bool(env)
        if from_env:
            expand = env
            applies = expand in _ANY_W + ("sign",) + _BYTE_ONLY and (
                expand in _ANY_W or w == 8 or (w == 16 and expand == "sign")
            )
            if not applies:
                expand = _env_fallback(
                    f"RS_PALLAS_EXPAND={expand!r} is unknown or does not "
                    f"apply at w={w}",
                    _default_expand(w, acc_dtype),
                )
        else:
            # The measured production default (shift_raw beat shift at
            # every probed shape — expand_r4b_*/expand_r4c_* captures,
            # 2026-07-31: k10 60.0 vs 44.1, k64 119.4 vs 100.5, p=k=10
            # 48.4 vs 45.6, +dot k10 102.5 vs 82.8); at w=16 with an
            # explicit non-int8 acc_dtype this silently selects "shift"
            # rather than raise over a parameter the caller never passed.
            expand = _default_expand(w, acc_dtype)
    if expand not in _ANY_W + ("sign",) + _BYTE_ONLY:
        raise ValueError(f"unknown expand {expand!r}")
    if expand == "sign" and w not in (8, 16):
        raise ValueError(
            f"expand='sign' needs a lane-width field (w=8 or 16), got w={w}; "
            "use expand='shift' for other widths"
        )
    if expand in _BYTE_ONLY and w != 8:
        raise ValueError(
            f"expand={expand!r} is a GF(2^8) (byte-granular) strategy, "
            f"got w={w}"
        )
    A = jnp.asarray(A)
    B = jnp.asarray(B)
    # Python-level entries: eager dispatches plus one per jit/AOT trace
    # (inside a trace this runs once per compile, so the counter reads as
    # "kernel builds + eager dispatches", labeled by call context).
    _obs_metrics.counter(
        "rs_pallas_gemm_calls_total",
        "gf_matmul_pallas entries (eager dispatches + compile traces)",
    ).labels(
        w=w,
        traced=isinstance(A, jax.core.Tracer) or isinstance(B, jax.core.Tracer),
    ).inc()
    if expand == "pack2" and not fold_parity:
        # The pre-parity (stripe-psum) form cannot be emitted: the
        # accumulator lanes hold two packed 8-bit parity fields, not the
        # per-column bit-plane accumulators from_bitplanes expects.
        why = "pack2 cannot emit pre-parity accumulators"
        if from_env:
            expand = _env_fallback(
                f"RS_PALLAS_EXPAND=pack2 does not apply here ({why})",
                _default_expand(w, acc_dtype),
            )
        else:
            raise ValueError(why)
    if interpret is None:
        from ..utils.backend import tpu_devices_present

        interpret = not tpu_devices_present()
    if tile is None:
        # RS_PALLAS_TILE: whole-pipeline tile experiments without touching
        # call sites (the CLI's -p cannot reach the kernel tile — it sizes
        # segments; this knob is the actual gridDim.x-cap analog of the
        # reference's -p, encode.cu:348-355).  Same warn-and-fall-back
        # hygiene as RS_PALLAS_EXPAND/REFOLD; an explicit argument wins.
        import os

        env = os.environ.get("RS_PALLAS_TILE")
        if env:
            try:
                tile = int(env)
                if tile <= 0:
                    raise ValueError(env)
            except ValueError:
                tile = _env_fallback(
                    f"RS_PALLAS_TILE={env!r} is not a positive integer",
                    None, label="the measured default",
                )
            if tile is not None and tile % 128:
                # TPU blocks must be lane-aligned; a misaligned tile
                # would fail Mosaic lowering.  Round up, warn — the
                # same warn-and-continue hygiene as the other env knobs.
                aligned = ((tile + 127) // 128) * 128
                import warnings

                warnings.warn(
                    f"RS_PALLAS_TILE={tile} is not a multiple of the "
                    f"128-lane width; rounding up to {aligned}",
                    stacklevel=2,
                )
                tile = aligned
    if tile is None:
        tile = fit_tile(
            A.shape[1], A.shape[0], w,
            DEFAULT_TILE if interpret else TPU_TILE,
            a_cols=A.shape[1] * (
                32 if expand in ("nibble", "nibble_const", "nibble32") else w
            ),
            fold=fold_parity,
        )
    acc_explicit = acc_dtype is not None
    if acc_dtype is None:
        if expand == "shift_raw" and w == 16:
            acc_dtype = jnp.int8
        else:
            acc_dtype = jnp.bfloat16 if interpret else jnp.int8
    if expand == "shift_raw" and w == 16 and acc_dtype != jnp.int8:
        # Unmasked 16-bit planes reach 65535; bf16 represents integers
        # exactly only up to 2^8, so rounding would corrupt the parity.
        # (int8 wraps mod 256 — even, parity-safe; w<=8 planes are <=255
        # and exact in bf16.)  Env-selected modes keep the warn-and-fall-
        # back guarantee instead of crashing production.
        if from_env:
            expand = _env_fallback(
                "RS_PALLAS_EXPAND=shift_raw needs acc_dtype=int8 at w=16",
                _default_expand(w, acc_dtype),
            )
        else:
            raise ValueError(
                "expand='shift_raw' at w=16 requires acc_dtype=int8"
            )
    if expand == "pack2":
        # Self-contained path: f32 accumulation (exact; fields < 256) and
        # the packed shift-sum refold.  Explicit acc_dtype/refold must not
        # be silently ignored — a probe capture would be labeled with a
        # configuration that never ran.
        if acc_explicit or refold is not None:
            raise ValueError(
                "pack2 has a fixed f32/packed-refold pipeline; "
                "acc_dtype and refold do not apply"
            )
        k_all = A.shape[1]
        k_c = (256 // w) - 1  # per-slice depth k_c*w <= 248 < 256
        if k_all <= k_c:
            return _pallas_matmul_pack2(A, B, w, tile, interpret)
        # Split-k: the packed parity fields are only carry-free below
        # depth 256, so deeper contractions run as ceil(k/k_c) carry-free
        # slices XORed together (XOR is the field addition, so slicing the
        # contraction is exact).  Each slice reads only its own k rows —
        # total input traffic is unchanged; the extra cost is the (p, m)
        # slice outputs and their XORs, cheap while p << k and affordable
        # even at p = k (HBM has 4x headroom over the measured kernel).
        out = None
        for c0 in range(0, k_all, k_c):
            part = _pallas_matmul_pack2(
                A[:, c0:c0 + k_c], B[c0:c0 + k_c], w, tile, interpret
            )
            out = part if out is None else out ^ part
        return out
    if refold is None:
        # Env override for whole-pipeline hardware experiments, mirroring
        # RS_PALLAS_EXPAND; an explicit refold argument always wins.
        import os

        # "dot" (MXU parity refold) is the measured production default at
        # w=8: it lowers after the int32 cast-chain fix and wins at every
        # probed w=8 shape — k64 132.0 vs 119.4, decode p=k=10 80.5 vs
        # 48.4, headline k10 102.5 vs 60.0 (expand_r4b_*dot/
        # expand_r4c_*dot captures, 2026-07-31).  w=16 stays on "sum":
        # dot there is BIMODAL at fixed shape (82-148 GB/s across runs
        # at mb=128) where sum is stable at ~102
        # (w16_cross_*_tpu_20260801T*); a default that can regress
        # below the stable alternative on half its dispatches does not
        # ship.  RS_PALLAS_REFOLD=dot opts in.
        default_refold = _default_refold(w)
        refold = os.environ.get("RS_PALLAS_REFOLD") or default_refold
        if refold not in ("sum", "dot", "autotune"):
            refold = _env_fallback(
                f"RS_PALLAS_REFOLD={refold!r} is unknown", default_refold
            )
    if refold not in ("sum", "dot", "autotune"):
        raise ValueError(f"unknown refold {refold!r}")
    if refold == "autotune":
        if not fold_parity:
            # The pre-parity (stripe-psum) form has no refold stage to
            # tune — the fold happens host-side after the collective.
            refold = _default_refold(w)
        elif isinstance(A, jax.core.Tracer) or isinstance(B, jax.core.Tracer):
            # Inside a caller's jit trace the operands are tracers:
            # block_until_ready is a no-op there, so "timing" would
            # measure per-variant TRACE overhead and cache that garbage
            # decision for every later eager call of the same shape.
            # Calibration needs concrete arrays — fall back to the
            # static per-width default with the module's usual warning.
            refold = _env_fallback(
                "refold='autotune' cannot calibrate under a jit trace "
                "(operands are tracers); call the pallas path eagerly "
                "to calibrate",
                _default_refold(w),
            )
        else:
            refold = _autotune_refold(
                A, B, w, tile, acc_dtype, interpret, expand
            )
    _obs_metrics.counter(
        "rs_pallas_refold_total",
        "resolved refold choices at kernel dispatch/trace time",
    ).labels(refold=refold, expand=expand, w=w).inc()
    return _pallas_matmul(
        A, B, w, tile, acc_dtype, interpret, expand, fold=fold_parity,
        refold=refold,
    )
