"""Shape-bucketed execution plans — the dispatch layer of the hot loop.

The reference keeps its GPU busy by overlapping PCIe copies with kernels
(encode.cu:165-218); the JAX port's equivalent tax is *dispatch overhead*:
every distinct segment width (tail segments, small files, per-k sweeps)
costs a fresh XLA trace+compile, and every segment round-trips an unpinned
host buffer.  This module removes both from the segment loop:

* **Shape bucketing** — segment column counts are rounded up a small
  geometric ladder (powers of two of a 128-lane-aligned floor, capped at
  the full segment width), so any file's segment loop compiles at most
  O(log(seg_cols/128)) executables per (k, n, w, strategy) instead of one
  per distinct tail width.  The pad columns are zeros; GF linearity makes
  their output columns zeros too, and the caller-visible result is trimmed
  back to the true width.
* **A bounded, thread-safe plan cache** keyed on (bucket, matrix shape,
  dtypes, w, strategy, mesh fingerprint) holding AOT-lowered/compiled
  callables (``jax.jit(...).lower(...).compile()``), with hit/miss/eviction
  counters and an explicit :meth:`PlanCache.clear` that also invalidates
  the Pallas refold-autotune cache (the two caches go stale together: see
  ADVICE r5 finding 2 and docs/PLAN.md on ``jax.clear_caches()``).
* **Buffer donation** — plans compile a ``donate_argnums`` variant for the
  data operand, used for segments the pipeline itself staged
  (:class:`StagedSegment` marks ownership transfer) whose output can
  actually alias the donated buffer (XLA needs equal sizes: full-k
  decode/repair, not encode's p < k), so XLA reuses the segment's device
  buffer across the loop instead of allocating a fresh output every
  dispatch.  Caller-owned arrays (a bench timing the same device buffer
  repeatedly) are never donated.

Dispatch strategy per plan:

* ``bitplane`` / ``table`` — true AOT: the GEMM is lowered and compiled
  once per plan; later dispatches skip jit's signature machinery entirely.
* ``pallas`` — the FIRST dispatch of each codec runs eagerly through
  ``codec._gf_matmul_pallas_eager`` (preserving the documented contracts:
  failure injection for tests, and RS_PALLAS_REFOLD=autotune calibration
  on concrete arrays); subsequent dispatches run the AOT executable.
  Under autotune the plan times its OWN compiled refold candidates
  (``pallas_gemm.calibrate_aot_refold``) — the eager decision described a
  different compile, and dot speed at w=16 is per-compile bimodal.
* ``xor`` — the plan key additionally carries the COEFFICIENT MATRIX
  DIGEST (the XOR schedule is a function of the matrix values, not just
  its shape), and the cached callable is a composite of three stage
  executables (pack / xor-chain / unpack, ops/xor_gemm.py) — XLA fuses
  a monolithic emission ~2x slower than the staged one.  One schedule
  per digest, never one per dispatch; schedule term counts surface in
  ``describe()`` and ``rs doctor``.  Donation is skipped (the stage
  split owns its intermediates).
* mesh plans — counted and fingerprinted, but the callable is the
  existing jitted ``sharded_gf_matmul`` (XLA's jit cache pins the
  executable; donation is skipped — sharded inputs may be caller-held).
* update-op plans (``codec.update``, docs/UPDATE.md) — the delta-parity
  GEMM ``E·Δ`` dispatches with the SAME (p, k) coefficient shape as
  encode, so its plan key aliases the encode bucket class on purpose: a
  warm encode executable (or ``warm_plan``) serves update traffic with
  zero extra compiles, and the bucket ladder absorbs the small ragged
  widths partial-stripe edits produce.  The ``op`` split lives in the
  metrics (``segments_dispatched{op="update"}``, ``rs_codec_bytes_total``)
  rather than the cache key — compile classes stay shape-pure.

Env knobs (all read per call, so tests can monkeypatch):

* ``RS_PLAN=0`` — disable the whole layer (legacy per-shape jit dispatch).
* ``RS_PLAN_MIN_BUCKET`` — ladder floor, default 128 (the TPU lane width).
* ``RS_PLAN_CACHE_SIZE`` — LRU bound on cached plans, default 64.
* ``RS_PLAN_DONATE`` — ``1`` force donation on, ``0`` off; unset = donate
  on accelerator backends only (CPU XLA rejects donation with a warning).
"""

from __future__ import annotations

import os
import threading
import time
from collections import OrderedDict

import numpy as np

from .obs import (attrib as _attrib, metrics as _metrics,
                  profiler as _prof, tracing as _tracing)


def enabled() -> bool:
    """Whether the plan layer is on (RS_PLAN=0/false/off/no disables)."""
    return os.environ.get("RS_PLAN", "1").lower() not in (
        "0", "false", "off", "no"
    )


def _min_bucket() -> int:
    try:
        v = int(os.environ.get("RS_PLAN_MIN_BUCKET", "128"))
        return v if v > 0 else 128
    except ValueError:
        return 128


def _donation_allowed() -> bool:
    env = os.environ.get("RS_PLAN_DONATE")
    if env is not None:
        return env.lower() not in ("0", "false", "off", "no")
    # CPU XLA refuses donation ("Some donated buffers were not usable")
    # with a UserWarning per compile; accelerators honour it.  Checked on
    # the REAL device platform (not the tpu_devices_present helper, which
    # tests fake to steer strategy selection): donation must follow what
    # the executing backend actually supports.
    import jax

    try:
        plat = jax.devices()[0].platform.lower()
    except Exception:
        return False
    return plat in ("tpu", "gpu", "cuda", "rocm")


def bucket_cols(m: int, cap: int | None = None) -> int:
    """Round a segment column count up the bucket ladder.

    ``cap`` is the plan's maximum width (the full segment width): the
    ladder is min_bucket * 2^j capped there, so a segment loop emits at
    most the full width plus O(log) tail buckets.  ``cap=None`` means "no
    ladder" — direct eager callers (benches, tests) keep their exact shape
    and never pay pad compute.  Widths at or above the cap (including
    chunks smaller than one bucket, where cap == chunk) pass through
    unchanged.
    """
    if cap is None or m >= cap or m <= 0:
        return m
    b = _min_bucket()
    while b < m:
        b <<= 1
    return min(b, cap)


def mesh_fingerprint(mesh) -> tuple:
    """Hashable identity of the dispatch target: which devices, in which
    mesh layout, on which platform.  Part of every plan key so a rebuilt
    mesh (new axis order, different device set) cannot alias a stale
    executable."""
    import jax

    if mesh is None:
        return ("local", jax.default_backend())
    devs = tuple(int(d.id) for d in mesh.devices.flat)
    plat = next(iter(mesh.devices.flat)).platform
    return (
        tuple(mesh.axis_names),
        tuple(mesh.devices.shape),
        devs,
        plat,
    )


class StagedSegment:
    """A segment the pipeline staged onto the device ahead of dispatch.

    Marks ownership transfer: the wrapped array was created by
    :func:`stage_segment` for exactly one GEMM dispatch, so the plan layer
    may DONATE its device buffer.  ``cols`` is the true (pre-pad) column
    count; ``cap`` the plan cap it was bucketed under.
    """

    __slots__ = ("array", "cols", "cap")

    def __init__(self, array, cols: int, cap: int | None):
        self.array = array
        self.cols = cols
        self.cap = cap

    @property
    def shape(self):
        return self.array.shape


def stage_segment(B, cap: int | None):
    """Pad a host segment to its bucket and issue its (async) H2D transfer.

    This is the H2D stage of the 3-stage pipeline (see
    ``parallel.pipeline.DeviceStagingRing``): ``jax.device_put`` returns
    immediately, so the transfer of segment i+1 overlaps segment i's
    compute.  The zero pad is written host-side (one bounded memcpy) so the
    staged buffer is exactly the plan's compiled shape.
    """
    import jax

    padded = _pad_to(B, bucket_cols(B.shape[1], cap))
    prof_on = _prof.enabled()
    t0 = time.monotonic() if prof_on else 0.0
    with _tracing.span("h2d_stage", lane="stage", cols=int(B.shape[1]),
                       bucket=int(padded.shape[1])):
        staged = jax.device_put(padded)
    if prof_on:
        # Staging wall as the host observes it (device_put returns once
        # the transfer is SCHEDULED on async backends — no block here,
        # staging exists to overlap); folded into the next dispatch's
        # profile as its h2d field (obs/profiler.py note_staging).
        _prof.note_staging(time.monotonic() - t0, int(padded.nbytes))
    _metrics.counter(
        "rs_segments_staged_total",
        "segments bucket-padded and staged onto the device (H2D issued)",
    ).inc()
    return StagedSegment(staged, B.shape[1], cap)


class ExecutionPlan:
    """One cached executable class: a (bucket, shapes, strategy, target)
    combination, with its AOT-compiled donate/no-donate variants."""

    __slots__ = (
        "key", "strategy", "w", "bucket", "refold", "calls", "donated_calls",
        "compile_seconds", "cost_analysis", "xor_stats", "last_stages",
        "_compiled", "_lock",
    )

    def __init__(self, key, strategy, w, bucket):
        self.key = key
        self.strategy = strategy
        self.w = w
        self.bucket = bucket
        self.refold = None          # pallas plans: resolved at first compile
        self.calls = 0
        self.donated_calls = 0
        self.compile_seconds = 0.0  # lower+compile wall across all variants
        self.cost_analysis = None   # XLA cost model of one dispatch, or None
        self.xor_stats = None       # xor plans: schedule term counts
        self.last_stages = None     # newest RS_PROF stage breakdown, or None
        self._compiled: dict = {}   # donate(bool) -> jax Compiled
        self._lock = threading.Lock()   # serializes this plan's builds

    # -- builders ------------------------------------------------------------

    def _compile(self, A, B, fn, donate: bool):
        import jax

        jitted = jax.jit(fn, donate_argnums=(1,) if donate else ())
        t0 = time.perf_counter()
        with _tracing.span(
            "plan_compile", lane="compile", strategy=self.strategy,
            bucket=int(self.bucket), donate=donate,
        ):
            exe = jitted.lower(
                jax.ShapeDtypeStruct(A.shape, A.dtype),
                jax.ShapeDtypeStruct(B.shape, B.dtype),
            ).compile()
        dt = time.perf_counter() - t0
        self.compile_seconds += dt  # under the plan's own lock (see run())
        if self.cost_analysis is None:
            # Roofline accounting (obs/attrib.py): the XLA cost model of
            # one dispatch — FLOPs, bytes accessed, transcendentals.
            # Variants share compute (donate only changes aliasing), so
            # the first variant's analysis stands for the plan; backends
            # that return None/partial leave it None and `rs analyze`
            # falls back to the analytic model.
            self.cost_analysis = _attrib.extract_cost_analysis(exe)
        _metrics.histogram(
            "rs_plan_compile_seconds",
            "wall seconds spent in AOT lower+compile per plan variant",
        ).labels(strategy=self.strategy).observe(dt)
        return exe

    def _build(self, A, B, donate: bool):
        """Lower + compile this plan's executable for concrete operands.
        Runs under the plan's own lock (see :meth:`run`); compile errors
        propagate to the dispatch site."""
        w, strategy = self.w, self.strategy
        if strategy == "ring":
            # Same contract as the xor branch below, with the ring
            # three-stage pipeline (ops/ring_gemm.py) as the composite.
            from .ops import ring_gemm as _rg

            t0 = time.perf_counter()
            pipe = _rg.get_ring_pipeline(np.asarray(A), B.shape, B.dtype, w)
            dt = time.perf_counter() - t0
            self.compile_seconds += dt
            if self.cost_analysis is None:
                self.cost_analysis = pipe.cost_analysis
            self.xor_stats = pipe.describe()
            _metrics.histogram(
                "rs_plan_compile_seconds",
                "wall seconds spent in AOT lower+compile per plan variant",
            ).labels(strategy=strategy).observe(dt)
            return pipe
        if strategy == "xor":
            # Digest-keyed composite pipeline (ops/xor_gemm.py): three
            # stage executables whose XOR schedule is baked from the
            # CONCRETE coefficients (the plan key carries the matrix
            # digest, so one schedule serves every dispatch of this
            # matrix — never one per dispatch).  The pipeline cache is
            # shared with the eager path and cleared with this cache.
            # Donation is not applicable: the stage split owns its
            # intermediates, and dispatch() never requests it for xor.
            from .ops import xor_gemm as _xg

            t0 = time.perf_counter()
            pipe = _xg.get_pipeline(np.asarray(A), B.shape, B.dtype, w)
            dt = time.perf_counter() - t0  # ~0 on a pipeline-cache hit
            self.compile_seconds += dt
            if self.cost_analysis is None:
                self.cost_analysis = pipe.cost_analysis
            self.xor_stats = pipe.describe()
            _metrics.histogram(
                "rs_plan_compile_seconds",
                "wall seconds spent in AOT lower+compile per plan variant",
            ).labels(strategy=strategy).observe(dt)
            return pipe
        if strategy == "pallas":
            from .ops import pallas_gemm as _pg

            if self.refold is None:
                self.refold = _pg.plan_refold_resolution(w)
            if self.refold == "autotune":
                # Calibrate against THIS plan's own executables: the eager
                # path's cached decision timed a DIFFERENT compile, and
                # dot speed at w=16 is per-compile bimodal.  Candidates
                # are timed non-donating (a donating warm-up would delete
                # the operand); the winner's plain executable is kept.
                def plain_variant(refold):
                    return self._compile(
                        A, B,
                        lambda a, b: _pg.gf_matmul_pallas(
                            a, b, w=w, refold=refold
                        ),
                        donate=False,
                    )

                self.refold, exe = _pg.calibrate_aot_refold(
                    A, B, w, plain_variant
                )
                self._compiled.setdefault(False, exe)
                if not donate:
                    return exe
            refold = self.refold

            def fn(a, b):
                return _pg.gf_matmul_pallas(a, b, w=w, refold=refold)

        else:
            from .ops.gemm import gf_matmul

            def fn(a, b):
                return gf_matmul(a, b, w=w, strategy=strategy)

        return self._compile(A, B, fn, donate)

    # -- dispatch ------------------------------------------------------------

    def run(self, A, B, donate: bool):
        # The plan's own lock covers check AND build: two threads racing
        # the same cold variant compile once, not twice (the compile is
        # seconds; the serialization is the point).  The dispatch itself
        # runs outside the lock so warm callers never serialize.
        with self._lock:
            exe = self._compiled.get(donate)
            if exe is None:
                t0 = time.perf_counter()
                exe = self._compiled[donate] = self._build(A, B, donate)
                # Cold-dispatch attribution (obs/profiler.py): the build
                # wall is part of THIS dispatch's wall, named `compile`.
                _prof.add_compile(time.perf_counter() - t0)
            self.calls += 1
            if donate:
                self.donated_calls += 1
        _metrics.counter(
            "rs_plan_dispatch_total",
            "GEMM dispatches through cached plan executables",
        ).labels(strategy=self.strategy, donated=donate).inc()
        if self.strategy not in ("xor", "ring") and \
                _prof.active() is not None:
            # Monolithic strategies have one device stage; the xor/ring
            # pipelines attribute their own pack/chain/unpack inside.
            return _prof.run_stage("chain", exe, A, B)
        return exe(A, B)

    def describe(self) -> dict:
        with self._lock:  # a concurrent _build may be inserting a variant
            variants = list(self._compiled)
        out = {
            "strategy": self.strategy,
            "w": self.w,
            "bucket": self.bucket,
            "a_shape": list(self.key[2]),
            "b_dtype": self.key[5],
            "mesh": self.key[6][0] != "local",
            "refold": self.refold,
            "variants": sorted(
                ("donate" if d else "plain") for d in variants
            ) or (["jit"] if self.key[6][0] != "local" else []),
            "calls": self.calls,
            "donated_calls": self.donated_calls,
            "compile_seconds": self.compile_seconds,
            "cost_analysis": self.cost_analysis,
        }
        if self.xor_stats is not None:
            # Schedule economy for `rs doctor`: terms before/after CSE
            # and the matrix digest this plan is keyed by (keyed by the
            # lowering that produced it — "xor" or "ring").
            out[self.strategy] = self.xor_stats
        if self.last_stages is not None:
            # Newest profiled dispatch's stage walls (obs/profiler.py):
            # where this plan's dispatch wall went, in the same stage
            # vocabulary as `rs perf` and the xor_ab captures.
            out["stages"] = self.last_stages
        return out


class PlanCache:
    """Bounded, thread-safe LRU of :class:`ExecutionPlan`.

    The cache lock covers lookup/eviction; each plan's OWN lock covers its
    builds (see :meth:`ExecutionPlan.run`), so a slow compile on one shape
    class never blocks dispatches of another.  ``clear()`` also drops the
    Pallas refold-autotune decisions — both caches pin choices to
    executables XLA may since have evicted, so they are invalidated
    together (pair with ``jax.clear_caches()``).
    """

    def __init__(self, max_size: int | None = None, name: str = "local"):
        self._lock = threading.RLock()
        self._plans: OrderedDict = OrderedDict()
        self._max_size = max_size
        self.name = name
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def _count_event(self, event: str) -> None:
        # The plain int attributes stay authoritative (always counted —
        # they are plan-layer contract surface, metrics on or off); the
        # registry mirror makes them part of the unified snapshot's
        # metric families when RS_METRICS is on.
        _metrics.counter(
            "rs_plan_cache_events_total",
            "plan cache lookups by outcome",
        ).labels(cache=self.name, event=event).inc()

    def _bound(self) -> int:
        if self._max_size is not None:
            return self._max_size
        try:
            v = int(os.environ.get("RS_PLAN_CACHE_SIZE", "64"))
            return v if v > 0 else 64
        except ValueError:
            return 64

    def lookup(self, key, strategy, w, bucket) -> "ExecutionPlan":
        with self._lock:
            plan = self._plans.get(key)
            if plan is not None:
                self.hits += 1
                self._plans.move_to_end(key)
                self._count_event("hit")
                return plan
            self.misses += 1
            self._count_event("miss")
            plan = ExecutionPlan(key, strategy, w, bucket)
            self._plans[key] = plan
            while len(self._plans) > self._bound():
                # Eviction needs no autotune invalidation: AOT plans
                # calibrate against their OWN executables (never the
                # eager decision cache), so a rebuilt plan re-measures
                # rather than inheriting a decision about a dead compile.
                self._plans.popitem(last=False)
                self.evictions += 1
                self._count_event("eviction")
            return plan

    def clear(self) -> None:
        with self._lock:
            self._plans.clear()
            self.hits = self.misses = self.evictions = 0
        from .ops.pallas_gemm import clear_autotune_cache
        from .ops.xor_gemm import clear_pipeline_cache

        clear_autotune_cache()
        clear_pipeline_cache()
        # The generation-keyed survivor-subset cache (api.py) memoizes
        # inverses whose xor schedules live in the caches just dropped —
        # clear it too so a post-clear decode re-derives rather than
        # assuming a warm schedule that no longer exists.  (Persistent
        # STORE entries survive by design: they are pure data, re-read
        # and re-validated on the next build — see clear_pipeline_cache.)
        from .api import clear_subset_cache

        clear_subset_cache()

    def stats(self) -> dict:
        # Snapshot under the cache lock, describe() OUTSIDE it: describe
        # takes each plan's own lock, which a multi-second _build may hold
        # — holding the cache lock across that would stall every lookup.
        with self._lock:
            plans = list(self._plans.values())
            out = {
                "enabled": enabled(),
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
                "executables": len(plans),
                "max_size": self._bound(),
            }
        out["plans"] = [p.describe() for p in plans]
        out["compile_seconds"] = sum(
            p["compile_seconds"] for p in out["plans"]
        )
        return out

PLAN_CACHE = PlanCache(name="local")
# Mesh dispatches are counter-only entries (the executable lives in the
# jitted collective's own cache, keyed by EXACT shapes — so they are
# counted by exact width, which reflects real mesh compiles).  They live
# in their own cache so unbounded mesh width churn can never evict local
# plans that hold real AOT executables.
MESH_PLAN_CACHE = PlanCache(name="mesh")


def _pad_to(B, bucket: int):
    m = B.shape[1]
    if m == bucket:
        return B
    if isinstance(B, np.ndarray):
        padded = np.zeros((B.shape[0], bucket), dtype=B.dtype)
        padded[:, :m] = B
        return padded
    import jax.numpy as jnp

    return jnp.pad(B, ((0, 0), (0, bucket - m)))


def dispatch(
    A,
    B,
    *,
    w: int,
    strategy: str,
    cap: int | None = None,
    cols: int | None = None,
    donate: bool = False,
    eager_fn=None,
):
    """Plan-cached single-device GEMM dispatch.

    ``A`` (p, k) coefficients, ``B`` (k, m) data — possibly already padded
    to its bucket by :func:`stage_segment`, in which case ``cols`` is the
    true width.  Pads to the bucket, runs the cached executable (or
    ``eager_fn(A, B)`` when given — the codec's first-pallas-dispatch
    contract), and trims the result back to the true width.  ``donate``
    requests the donating variant; it is honoured only for ownership-
    transferred buffers and when the backend supports donation.
    """
    m = cols if cols is not None else B.shape[1]
    bucket = max(bucket_cols(m, cap), B.shape[1])
    key = (
        strategy,
        w,
        tuple(A.shape),
        str(np.dtype(A.dtype)),
        bucket,
        str(np.dtype(B.dtype)),
        mesh_fingerprint(None),
    )
    if strategy in ("xor", "ring"):
        # The XOR/ring schedule is a function of the coefficient VALUES,
        # so the plan key carries the matrix digest (one compiled schedule
        # per matrix, shared by every dispatch — docs/XOR.md); the
        # bucket additionally rounds up to the pipeline's 32-symbol
        # pack alignment (ragged caps only — ladder buckets are already
        # 128-aligned).  ``B`` may be a PackedOperand — a bit-plane
        # handle an earlier chained dispatch packed (docs/XOR.md
        # "Packed-operand reuse"); the pipeline skips its pack stage.
        from .ops.xor_gemm import PackedOperand, matrix_digest, padded_cols

        bucket = max(bucket, padded_cols(bucket))
        key = key[:4] + (bucket,) + key[5:] + (matrix_digest(A, w),)
        if isinstance(B, PackedOperand) and B.shape[1] != bucket:
            raise ValueError(
                f"packed operand cols {B.shape[1]} does not match the "
                f"plan bucket {bucket} — pack after staging, with the "
                "same cap"
            )
    prof = None
    if _prof.enabled():
        nb = getattr(B, "nbytes", None)
        if nb is None and hasattr(B, "cols_true"):  # PackedOperand
            nb = B.rows * B.cols_true * np.dtype(B.dtype).itemsize
        prof = _prof.begin(strategy=strategy, w=w, bucket=int(bucket),
                           bytes_in=int(nb) if nb else None)
    if prof is not None:
        misses_before = PLAN_CACHE.misses
    plan = PLAN_CACHE.lookup(key, strategy, w, bucket)
    if prof is not None:
        _prof.attr(plan_bucket="miss" if PLAN_CACHE.misses > misses_before
                   else "hit")
    try:
        B = _pad_to(B, bucket)
        if eager_fn is not None:
            with plan._lock:
                plan.calls += 1
            out = eager_fn(A, B)
        else:
            # XLA input-output aliasing needs equal buffer sizes: the
            # (rows, m) output can only reuse B's (k, m) buffer when
            # rows == k (full-k decode/repair).  Encode's p < k dispatch
            # would just compile a donate variant that warns 'donated
            # buffers were not usable' and aliases nothing — drop the
            # request instead.  The xor pipeline never donates: its stage
            # split owns the intermediate planes (nor does ring, which
            # shares the split).
            can_alias = A.shape[0] == B.shape[0] and strategy not in (
                "xor", "ring"
            )
            out = plan.run(
                A, B, donate and can_alias and _donation_allowed()
            )
        out = out if bucket == m else out[:, :m]
    except BaseException:
        _prof.discard(prof)
        raise
    if prof is not None:
        event = _prof.finish(prof, out)
        if event is not None and event.get("stages"):
            plan.last_stages = event["stages"]
    return out


def dispatch_mesh(A, B, *, w: int, strategy: str, mesh, stripe_sharded, fn):
    """Mesh-path plan accounting: the executable is pinned by the jitted
    collective's own cache (``fn`` is a ``sharded_gf_matmul`` partial and
    is called directly — caching it here would only pin the caller's codec
    and mesh in a process-global), but the dispatch is registered in
    MESH_PLAN_CACHE so compile classes are counted and fingerprinted per
    mesh.  No donation: sharded inputs may be caller-held."""
    key = (
        strategy,
        w,
        tuple(np.asarray(A).shape),
        str(np.dtype(A.dtype)),
        B.shape[1],
        str(np.dtype(B.dtype)),
        mesh_fingerprint(mesh),
        bool(stripe_sharded),
    )
    plan = MESH_PLAN_CACHE.lookup(key, strategy, w, B.shape[1])
    with plan._lock:
        plan.calls += 1
    return fn(A, B)
