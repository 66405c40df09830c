"""RSCodec — the stripe-level coding engine (compute only, no file IO).

This is the L3 equivalent of the reference's host-callable coding math
(``gen_encoding_matrix`` / ``encode_chunk`` / ``decode_chunk`` /
``CPU_invert_matrix``, matrix.h:63-102 + cpu-decode.h:27), re-packaged the
JAX way: a stateless object holding the (tiny) generator matrix as host
NumPy, whose encode/decode methods dispatch one jitted GF-GEMM over a
(rows, chunk_bytes) stripe.  The k x k decode inversion runs on host (same
host/device split the reference production path uses — decode.cu:333) but an
on-device inverter is available (:func:`..ops.inverse.invert_matrix_jax`).
"""

from __future__ import annotations

import jax
import numpy as np

from .models.vandermonde import generator_matrix
from .obs import metrics as _obs_metrics, profiler as _prof
from .ops.gemm import Strategy, gf_matmul_jit
from .ops.gf import get_field
from .ops.inverse import invert_matrix


# The monkeypatch seam tests use to steer strategy selection (tune.py
# reads it through this module).
from .utils.backend import tpu_devices_present as _tpu_devices_present


class FusedKernelError(RuntimeError):
    """The fused Pallas GEMM failed for one dispatch shape.  Nothing runs
    in its place: a file operation that hits it fails and leaves no
    output behind."""


def _gf_matmul_pallas_eager(A, B, w):
    """Single-device fused-kernel dispatch, called EAGERLY (the inner
    _pallas_matmul is itself jitted, so compute is identical to routing
    through gf_matmul_jit): the RS_PALLAS_* env knobs then resolve on
    concrete arrays, which is what lets RS_PALLAS_REFOLD=autotune time
    real kernels — under an outer jit it would see tracers and fall back
    to the static default (see pallas_gemm._autotune_refold).  Module-
    level hook so tests can inject kernel failures here."""
    from .ops.pallas_gemm import gf_matmul_pallas

    return gf_matmul_pallas(A, B, w)


class RSCodec:
    """(n, k) Reed-Solomon codec over GF(2^w).

    ``native_num`` = k data chunks, ``parity_num`` = n - k parity chunks.
    ``generator``: "vandermonde" (reference-compatible: the exact matrix the
    reference generates and stores in .METADATA) or "cauchy" (any-k-subset
    decodable).  ``strategy``: GEMM strategy — "auto" (default at the file
    layer) resolves through the per-backend autotuner (:mod:`.tune`:
    pallas on real TPU hardware / bitplane elsewhere unless a measured
    decision says otherwise; ``RS_STRATEGY_AUTOTUNE=measure`` lets
    table/bitplane/pallas/xor/ring/native compete on real timings);
    explicit values: "pallas", "bitplane" (MXU), "table" (VPU), "xor"
    (XOR-lowered bitsliced planes, docs/XOR.md), "ring" (polynomial-
    ring lowering, docs/XOR.md "Ring lowering"), "cpu" (native host
    codec).
    """

    def __init__(
        self,
        native_num: int,
        parity_num: int,
        w: int = 8,
        generator: str = "vandermonde",
        strategy: Strategy = "bitplane",
        mesh=None,
        stripe_sharded: bool = False,
    ):
        if native_num < 1 or parity_num < 0:
            raise ValueError(f"bad (k={native_num}, p={parity_num})")
        from .tune import VALID_STRATEGIES, resolve_auto

        if strategy not in VALID_STRATEGIES:
            raise ValueError(
                f"unknown strategy {strategy!r}: valid strategies are "
                f"{', '.join(VALID_STRATEGIES)} — 'auto' resolves per "
                "backend via the autotuner (docs/XOR.md, docs/PLAN.md)"
            )
        if strategy == "auto":
            # Resolved through the strategy autotuner (tune.py): the
            # static prior keeps the old behaviour — fused kernel on
            # real TPU hardware (the reference's multi-GPU mode runs its
            # fast kernel unconditionally, decode.cu:335-378), bitplane
            # elsewhere — and RS_STRATEGY_AUTOTUNE=measure lets xor and
            # the native codec compete on real timings.
            strategy = resolve_auto(
                native_num, parity_num, w, mesh=mesh, generator=generator
            )
        self.gf = get_field(w)
        self.w = w
        self.native_num = native_num
        self.parity_num = parity_num
        self.strategy: Strategy = strategy
        self.generator = generator
        self.mesh = mesh
        self.stripe_sharded = stripe_sharded
        self._pallas_checked = False
        if strategy == "cpu":
            # Validate up front: failing mid-stream would leave partial
            # output files behind.
            if w != 8:
                raise ValueError("strategy='cpu' supports GF(2^8) only")
            if mesh is not None:
                raise ValueError(
                    "strategy='cpu' is host-only; it cannot run on a device mesh"
                )
        if strategy in ("xor", "ring"):
            if w not in (8, 16):
                raise ValueError(
                    f"strategy={strategy!r} supports GF(2^8) and "
                    "GF(2^16) only"
                )
            if mesh is not None:
                raise ValueError(
                    f"strategy={strategy!r} is single-device (its "
                    "schedule is baked from concrete coefficients, "
                    "which the jitted mesh collective cannot trace); "
                    "use bitplane/table/pallas on a mesh"
                )
        if mesh is not None:
            from .parallel.mesh import COLS, STRIPE

            self._cols_size = mesh.shape[COLS]
            if stripe_sharded and native_num % mesh.shape[STRIPE]:
                raise ValueError(
                    f"k={native_num} not divisible by stripe axis "
                    f"({mesh.shape[STRIPE]} devices)"
                )
        gen = generator_matrix(generator, parity_num, native_num, self.gf)
        eye = np.eye(native_num, dtype=self.gf.dtype)
        self.total_matrix = np.concatenate([eye, gen], axis=0)  # (n, k)

    @property
    def n(self) -> int:
        return self.native_num + self.parity_num

    @property
    def parity_block(self) -> np.ndarray:
        return self.total_matrix[self.native_num :]

    # ----- stripe ops (device) ----------------------------------------------

    def _count_segment(self, op: str, data) -> None:
        """Registry accounting for one stripe dispatch (no-op unless
        RS_METRICS).  Skipped under a caller's jit trace — a Python-level
        increment there would count TRACES, not dispatches."""
        if isinstance(data, jax.core.Tracer):
            return
        # Profiler seam (obs/profiler.py): name the file-level op for the
        # dispatch this call precedes, so a sampled `rs_perf` event says
        # "decode", not "matmul".  One env read when RS_PROF is off.
        _prof.note_op(op)
        _obs_metrics.counter(
            "segments_dispatched",
            "stripe GEMM dispatches by operation and strategy",
        ).labels(op=op, strategy=self.strategy, w=self.w).inc()
        # Payload volume next to the dispatch count: the per-strategy
        # byte stream `rs analyze` divides by measured wall for achieved
        # GB/s.  True (pre-pad) columns for pipeline-staged segments —
        # bucket pad is compute, not payload.
        from . import plan as _plan
        from .ops.xor_gemm import PackedOperand

        if isinstance(data, PackedOperand):
            # True-column payload of the symbols the planes encode —
            # the pack pad is compute, not payload (same contract as
            # the staged branch below).
            nbytes = data.rows * data.cols_true * data.dtype.itemsize
        elif isinstance(data, _plan.StagedSegment):
            nbytes = (
                data.array.shape[0] * data.cols * data.array.dtype.itemsize
            )
        else:
            nbytes = getattr(data, "nbytes", 0)
        if nbytes:
            _obs_metrics.counter(
                "rs_codec_bytes_total",
                "payload bytes entering stripe GEMM dispatches",
            ).labels(op=op, strategy=self.strategy, w=self.w).inc(
                int(nbytes)
            )

    def encode(self, data):
        """(k, m) natives -> (p, m) parity.  Systematic: natives pass through
        unchanged, only parity is computed (the reference's encode kernel has
        the same shape: (n-k) x k coefficient block, matrix.cu:767-776).
        ``data`` may be a host array or a :class:`..plan.StagedSegment` the
        pipeline pre-placed on the device (see :meth:`stage_segment`)."""
        self._count_segment("encode", data)
        return self._matmul(self.parity_block, data)

    def decode(self, decode_mat, chunks):
        """(k, k) recovery matrix x (k, m) surviving chunks -> (k, m) natives."""
        self._count_segment("decode", chunks)
        return self._matmul(decode_mat, chunks)

    def update(self, parity_mat, delta):
        """(p, k) parity coefficient block x (k, m) native-symbol delta
        -> (p, m) parity delta (``parity' = parity ⊕ E·Δ``).

        The partial-stripe update kernel (update/engine.py): RS linearity
        makes the parity patch a GEMM over just the TOUCHED columns.
        Same plan-cached ``_matmul`` as encode — identical
        ``A`` shape means an update rides the very executable the encode
        path (or ``warm_plan``) already compiled — under its own ``op``
        label so dispatch counts and payload bytes attribute separately
        (docs/PLAN.md)."""
        self._count_segment("update", delta)
        return self._matmul(parity_mat, delta)

    def syndrome(self, check_mat, chunks):
        """(r, s) parity-check block x (s, m) stacked chunk rows -> (r, m)
        syndromes (zero columns == consistent codeword columns).

        The error-locating decode path's batched syndrome kernel
        (gf_decode/syndrome.py): same GF-GEMM machinery as encode/decode —
        plan-cached, strategy-aware — under its own ``op``
        label so dispatch counts and payload bytes attribute separately."""
        self._count_segment("syndrome", chunks)
        return self._matmul(check_mat, chunks)

    def stage_segment(self, seg, *, cap=None, sym: int = 1):
        """Stage one segment for the next encode/decode dispatch.

        The H2D stage of the 3-stage pipeline (DeviceStagingRing): pads the
        (k, cols) host segment to its plan bucket and issues the async
        ``device_put``, returning a :class:`..plan.StagedSegment` whose
        buffer the dispatch may DONATE.  ``sym`` > 1 reinterprets the raw
        bytes as little-endian symbols first (the w=16 wide-symbol view).
        Where planning does not apply — layer disabled, host-only codec,
        or a mesh (whose placement happens in ``_matmul`` via
        ``put_sharded``) — the (viewed) host array is returned unchanged
        and the dispatch behaves exactly as before.
        """
        if sym > 1:
            seg = seg.view(np.uint16)
        from . import plan as _plan

        if self.mesh is not None or self.strategy == "cpu" or not _plan.enabled():
            # Mesh placement happens in _matmul (put_sharded), host codec
            # never leaves the host: both count as passthrough stages so
            # the rs_io_* balance (docs/IO.md) still sees the segment.
            _obs_metrics.counter(
                "rs_io_h2d_bytes_total",
                "segment bytes entering the H2D stage of the pipeline",
            ).labels(path="passthrough").inc(seg.nbytes)
            return seg
        _obs_metrics.counter(
            "rs_io_h2d_bytes_total",
            "segment bytes entering the H2D stage of the pipeline",
        ).labels(path="plan").inc(seg.nbytes)
        return _plan.stage_segment(seg, cap)

    def pack_operand(self, data):
        """Pack a staged segment's bit-planes ONCE for reuse across the
        chained xor dispatches that consume the same ``B`` operand
        (docs/XOR.md "Packed-operand reuse"): the returned
        :class:`..ops.xor_gemm.PackedOperand` feeds
        :meth:`syndrome`/:meth:`decode` in place of the segment, and its
        :meth:`~..ops.xor_gemm.PackedOperand.select` hands a row subset
        to a follow-up dispatch with no second pack.  Returns ``None``
        whenever the reuse does not apply — non-xor strategy, mesh
        codec, plan layer off, ``RS_XOR_PACK_REUSE=0``, or a traced
        operand — so callers can fall back to the classic path with one
        ``is None`` check."""
        from . import plan as _plan
        from .ops import xor_gemm as _xg

        if (
            self.strategy not in ("xor", "ring")
            or self.mesh is not None
            or not _xg.pack_reuse_enabled()
            or not _plan.enabled()
        ):
            return None
        seg = data if isinstance(data, _plan.StagedSegment) else None
        arr = seg.array if seg is not None else data
        if isinstance(arr, jax.core.Tracer):
            return None
        cols_true = seg.cols if seg is not None else arr.shape[1]
        cap = seg.cap if seg is not None else None
        cols32 = _xg.padded_cols(arr.shape[1])
        if arr.shape[1] != cols32:
            # Ragged staged width (cap smaller than the pack alignment):
            # pad exactly as plan.dispatch would before the pipeline.
            import jax.numpy as jnp

            arr = jnp.pad(
                jnp.asarray(arr), ((0, 0), (0, cols32 - arr.shape[1]))
            )
        return _xg.pack_operand(arr, self.w, cols_true=cols_true, cap=cap)

    def _matmul(self, A, B):
        from . import plan as _plan
        from .ops.xor_gemm import PackedOperand

        if isinstance(B, PackedOperand):
            # A pre-packed plane handle (see pack_operand): only the xor
            # single-device plan path can consume it, and it is already
            # bucket-padded — dispatch directly, trimming to true cols.
            if self.strategy not in ("xor", "ring") or self.mesh is not None:
                raise ValueError(
                    "packed operands require strategy='xor' or 'ring' "
                    "on a single-device codec"
                )
            return _plan.dispatch(
                A, B, w=self.w, strategy=self.strategy, cap=B.cap,
                cols=B.cols_true,
            )
        seg = B if isinstance(B, _plan.StagedSegment) else None
        staged = seg is not None
        b_cols = seg.cols if staged else None
        plan_cap = seg.cap if staged else None
        if staged:
            B = seg.array
        if self.strategy == "cpu":
            # Native host codec (the CPU-RS oracle role, cpu-rs.c) — no
            # device involved; useful as differential baseline and fallback.
            from . import native

            return native.gemm(np.asarray(A), np.asarray(B))
        if self.mesh is None:
            # A StagedSegment is already bucket-padded: it must go through
            # the plan layer (which knows to trim) even if RS_PLAN was
            # flipped off between staging and dispatch.
            use_plan = (_plan.enabled() or staged) and not isinstance(
                B, jax.core.Tracer
            )
            if self.strategy == "pallas":
                # The first dispatch runs EAGERLY through the module hook
                # (RS_PALLAS_REFOLD=autotune needs concrete arrays to
                # calibrate, and tests inject failures there) and is
                # materialised, so a kernel that cannot compile fails
                # before any caller writes output.  After it, the plan's
                # AOT executable (calibrated refold baked in) takes over
                # and may donate pipeline-staged buffers.
                try:
                    if use_plan:
                        out = _plan.dispatch(
                            A, B, w=self.w, strategy="pallas",
                            cap=plan_cap, cols=b_cols,
                            donate=staged and self._pallas_checked,
                            eager_fn=(
                                None if self._pallas_checked else
                                lambda a, b: _gf_matmul_pallas_eager(
                                    a, b, self.w
                                )
                            ),
                        )
                    else:
                        out = _gf_matmul_pallas_eager(A, B, self.w)
                    if not self._pallas_checked:
                        jax.block_until_ready(out)
                        self._pallas_checked = True
                    return out
                except Exception as e:
                    raise self._kernel_error(A, B, e) from e
            if use_plan:
                return _plan.dispatch(
                    A, B, w=self.w, strategy=self.strategy,
                    cap=plan_cap, cols=b_cols, donate=staged,
                )
            if self.strategy in ("xor", "ring"):
                # Value-dependent schedule: the coefficients must stay
                # concrete, so this path never rides gf_matmul_jit
                # (which would trace A).  Works under a caller's jit
                # too — only the DATA may be traced.
                if self.strategy == "ring":
                    from .ops.ring_gemm import gf_matmul_ring

                    return gf_matmul_ring(A, B, self.w)
                from .ops.xor_gemm import gf_matmul_xor

                return gf_matmul_xor(A, B, self.w)
            return gf_matmul_jit(A, B, w=self.w, strategy=self.strategy)
        from .parallel.sharded import put_sharded, sharded_gf_matmul

        m = B.shape[1]
        pad = (-m) % self._cols_size
        if pad:
            B = np.pad(np.asarray(B), ((0, 0), (0, pad)))
        Bd = put_sharded(B, self.mesh, self.stripe_sharded)

        def _sharded(A_, B_, strategy):
            # Mesh dispatches register in the same plan cache (keyed by the
            # mesh fingerprint) so compile classes are counted uniformly;
            # the executable itself stays pinned by the jitted collective.
            run = lambda a, b: sharded_gf_matmul(  # noqa: E731
                a, b, mesh=self.mesh, w=self.w, strategy=strategy,
                stripe_sharded=self.stripe_sharded,
            )
            if not _plan.enabled():
                return run(A_, B_)
            return _plan.dispatch_mesh(
                A_, B_, w=self.w, strategy=strategy, mesh=self.mesh,
                stripe_sharded=self.stripe_sharded, fn=run,
            )

        try:
            out = _sharded(np.asarray(A), Bd, self.strategy)
            if self.strategy == "pallas" and not self._pallas_checked:
                # Materialise the first fused dispatch, as on one device.
                jax.block_until_ready(out)
                self._pallas_checked = True
        except Exception as e:
            if self.strategy != "pallas":
                raise
            raise self._kernel_error(A, Bd, e) from e
        return out[:, :m] if pad else out

    def _kernel_error(self, A, B, e: Exception) -> FusedKernelError:
        where = "one device" if self.mesh is None else (
            f"mesh {dict(self.mesh.shape)}, stripe_sharded="
            f"{self.stripe_sharded}"
        )
        return FusedKernelError(
            f"fused pallas GEMM failed for A{tuple(A.shape)} x "
            f"B{tuple(B.shape)} at w={self.w} on {where}: "
            f"{type(e).__name__}: {e}"
        )

    # ----- decode-matrix construction (host) --------------------------------

    def decode_matrix(self, survivor_rows) -> np.ndarray:
        """Inverse of the k x k submatrix of the total matrix selected by the
        k ``survivor_rows`` (chunk indices of the survivors, in the order
        their chunks will be stacked).  Raises SingularMatrixError if the
        survivor set is not decodable."""
        rows = list(survivor_rows)
        if len(rows) != self.native_num:
            raise ValueError(
                f"need exactly k={self.native_num} survivors, got {len(rows)}"
            )
        if any(r < 0 or r >= self.n for r in rows):
            raise ValueError(f"survivor index out of range in {rows}")
        sub = self.total_matrix[rows]
        return invert_matrix(sub, self.gf)

    def decode_matrix_from(self, total_mat: np.ndarray, survivor_rows) -> np.ndarray:
        """Same, but against an externally supplied total matrix (the one
        parsed from .METADATA — the authoritative copy for decode, matching
        the reference which trusts the file over regeneration)."""
        rows = list(survivor_rows)
        total_mat = np.asarray(total_mat)
        if any(r < 0 or r >= total_mat.shape[0] for r in rows):
            raise ValueError(
                f"survivor chunk index out of range for n={total_mat.shape[0]}: {rows}"
            )
        return invert_matrix(total_mat[rows], self.gf)
