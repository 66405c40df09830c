"""Sharded GF-GEMM over a device mesh — the scale-out compute path.

Capability parity with the reference's multi-GPU fan-out (one pthread per
device over disjoint byte ranges, encode.cu:240-292,357-408), redesigned as
SPMD ``shard_map`` over a ``(stripe, cols)`` mesh:

* **cols sharding** (reference's chunk-split): each device runs the
  identical fused GEMM on its column slice; zero communication, linear
  scaling.  This is the default and matches the reference's model where
  PCIe/pthreads never exchange data.
* **stripe sharding** (wide-stripe k=128 class, BASELINE config 4): the
  contraction axis k itself is sharded.  GF XOR-accumulation across devices
  cannot ride ``psum`` directly (psum adds integers), but the bit-plane
  formulation makes it exact: each device computes integer bit-plane
  partial products over its local k-slice, ``psum`` sums them over ICI
  (XOR == sum mod 2 taken AFTER the reduction), then parity-folds.  One
  collective per segment, bandwidth p*w*m bytes — the partials ride int8
  (mod-256 wrap is parity-exact, 4x less ICI than the int32 form) — the
  TPU-native equivalent the reference never had (it had no cross-device
  reduction at all; this is what unlocks stripes wider than one device's
  memory).

All functions take the GLOBAL (k, m) array; shardings are expressed with
``jax.sharding.PartitionSpec`` so the same code runs on 1 device, a v5e-8
slice, or multi-host DCN meshes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..obs import metrics as _metrics, tracing as _tracing
from ..ops import gemm as _gemm
from ..ops.gf import get_field
from .mesh import COLS, STRIPE

# The varying-axes checker is off: it cannot type ``pallas_call`` outputs
# or scan carries initialised inside the body; correctness is covered by
# the oracle-equality tests on the virtual mesh.
shard_map = functools.partial(jax.shard_map, check_vma=False)


def sharded_gf_matmul(A, B, *, mesh, w=8, strategy="bitplane",
                      stripe_sharded=False):
    """``C = A . B`` over GF(2^w), B sharded over the mesh.

    ``A``: (p, k) coefficient matrix (replicated; sharded along k when
    ``stripe_sharded``).  ``B``: (k, m) global data.  Returns (p, m) sharded
    along ``cols`` (replicated along ``stripe``).

    This wrapper is the mesh path's accounting boundary (the compute
    lives in the jitted ``_sharded_gf_matmul_jit``): each eager dispatch
    records a ``mesh_dispatch`` span and counts the collective payload in
    ``rs_mesh_collective_bytes_total{op}`` — stripe mode's psum moves
    ``p * w * m`` int8 pre-parity plane bytes per segment (the logical
    reduce volume; the ring transfer is ~2x that on real links), cols
    mode moves nothing — so ``rs analyze`` can attribute mesh-path cost
    next to the staged-byte counters.  Skipped under an outer trace
    (tracers have no concrete byte counts to account).
    """
    if not isinstance(B, jax.core.Tracer):
        m = int(B.shape[1])
        if stripe_sharded:
            p_rows = int(A.shape[0])
            _metrics.counter(
                "rs_mesh_collective_bytes_total",
                "logical bytes through mesh collectives per dispatch",
            ).labels(op="psum_stripe").inc(p_rows * w * m)
        with _tracing.span(
            "mesh_dispatch", lane="dispatch", strategy=str(strategy),
            stripe=bool(stripe_sharded), cols=m,
        ):
            return _sharded_gf_matmul_jit(
                A, B, mesh=mesh, w=w, strategy=strategy,
                stripe_sharded=stripe_sharded,
            )
    return _sharded_gf_matmul_jit(
        A, B, mesh=mesh, w=w, strategy=strategy,
        stripe_sharded=stripe_sharded,
    )


@functools.partial(
    jax.jit, static_argnames=("mesh", "w", "strategy", "stripe_sharded")
)
def _sharded_gf_matmul_jit(A, B, *, mesh, w=8, strategy="bitplane",
                           stripe_sharded=False):
    gf = get_field(w)
    out_dtype = jnp.uint8 if gf.dtype == np.uint8 else jnp.uint16

    if not stripe_sharded:
        if strategy == "pallas":
            # This dispatch always runs under the shard_map/jit trace,
            # where refold='autotune' cannot calibrate (the operands are
            # tracers).  Resolve the env knob to a static value HERE —
            # env "sum"/"dot" pass through, "autotune" takes the per-width
            # static default, pack2 expand yields None (its fixed pipeline
            # rejects an explicit refold) — instead of letting the
            # kernel's tracer guard warn 'cannot calibrate under a jit
            # trace' on every mesh trace: that warning is a real
            # regression signal on the eager path and must not cry wolf
            # here (ADVICE r5 finding 3).
            from ..ops.pallas_gemm import gf_matmul_pallas, static_refold

            refold = static_refold(w)

            def body(a_loc, b_loc):
                return gf_matmul_pallas(
                    a_loc, b_loc, w=w, refold=refold
                ).astype(out_dtype)

        else:

            def body(a_loc, b_loc):
                return _gemm.gf_matmul(
                    a_loc, b_loc, w=w, strategy=strategy
                ).astype(out_dtype)

        return shard_map(
            body,
            mesh=mesh,
            in_specs=(P(), P(None, COLS)),
            out_specs=P(None, COLS),
        )(A, B)

    # Wide stripe: contraction axis sharded.  Integer partials + psum + parity.
    # The partial products MUST stay integer (pre-parity) so psum can carry
    # the XOR as a sum; both the XLA bitplane path and the fused Pallas
    # kernel (fold_parity=False) can emit that form.  The table path folds
    # XOR per element and cannot.
    if strategy not in ("bitplane", "pallas"):
        import warnings

        warnings.warn(
            "stripe-sharded GEMM needs a pre-parity form (bitplane/pallas); "
            f"ignoring strategy={strategy!r}",
            stacklevel=2,
        )
        strategy = "bitplane"

    use_pallas = strategy == "pallas"

    def body(a_loc, b_loc):
        if use_pallas:
            from ..ops.pallas_gemm import gf_matmul_pallas

            # int32 bit-plane partials straight from VMEM (no refold).
            acc = gf_matmul_pallas(a_loc, b_loc, w=w, fold_parity=False)
        else:
            a_bits = _gemm.expand_bitmatrix_jnp(a_loc, w)  # (p*w, k_loc*w)
            b_bits = _gemm.to_bitplanes(b_loc, w)  # (k_loc*w, m_loc)
            acc = _gemm._dot_bits(a_bits, b_bits, jnp.int8)  # int32 partials
        # The collective rides int8, not int32: each accumulator is only
        # ever read mod 2 (XOR == sum mod 2), and both the int32->int8
        # narrowing and the int8 psum wrap mod 256 — an even modulus, so
        # parity is preserved exactly (the same algebra that lets
        # shift_raw drop the plane mask).  This cuts the per-segment ICI
        # payload 4x (STATUS pins it as stripe mode's entire cost:
        # ~107 MB/device per 32 MB segment as int32, ~27 MB as int8); the
        # cast itself is XLA-level, outside the Pallas kernel, so nothing
        # new has to lower through Mosaic.  from_bitplanes upcasts to
        # int32 before its shifts, so the int8 planes fold exactly.
        acc = jax.lax.psum(acc.astype(jnp.int8), STRIPE)
        return _gemm.from_bitplanes(acc, w, dtype=out_dtype)

    return shard_map(
        body,
        mesh=mesh,
        in_specs=(P(None, STRIPE), P(STRIPE, COLS)),
        out_specs=P(None, COLS),
    )(A, B)


def put_sharded(B, mesh, stripe_sharded: bool = False):
    """Place a host (k, m) array on the mesh with the encode sharding.

    Single-process: ``B`` is the GLOBAL array, device_put scatters it.
    Multi-process (mesh spans hosts): ``B`` must be this process's LOCAL
    portion of the global array (each host stages the byte range it owns —
    the natural layout for multi-host file encode); the global array is
    assembled from the per-process pieces.
    """
    spec = P(STRIPE if stripe_sharded else None, COLS)
    sharding = NamedSharding(mesh, spec)
    _metrics.counter(
        "rs_mesh_segments_staged_total",
        "segments placed onto a device mesh (put_sharded)",
    ).labels(stripe=stripe_sharded, procs=jax.process_count()).inc()
    # Byte volume alongside the segment count: per-process in a multi-host
    # job (each host stages only its local portion), so the aggregate sum
    # (obs/aggregate.py) is the fleet's true staged-traffic total.
    _metrics.counter(
        "rs_mesh_staged_bytes_total",
        "bytes placed onto a device mesh (process-local portion)",
    ).labels(stripe=stripe_sharded).inc(int(B.nbytes))
    with _tracing.span(
        "mesh_stage", lane="stage", cols=int(B.shape[1]),
        stripe=bool(stripe_sharded),
    ):
        if jax.process_count() > 1:
            return jax.make_array_from_process_local_data(sharding, B)
        return jax.device_put(B, sharding)
