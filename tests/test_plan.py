"""Execution-plan layer: bucket ladder, plan cache, donation, compile guard.

Covers ADVICE r5 finding 2's cache-coherence contract (plan clear() also
invalidates the pallas autotune cache) and the ISSUE's acceptance bound:
a segment loop that previously produced >= 4 distinct trace shapes holds
<= 2 plan executables per (k, n, strategy).
"""

import json
import os
import warnings

import numpy as np
import pytest

from gpu_rscode_tpu import api, plan
from gpu_rscode_tpu.codec import RSCodec
from gpu_rscode_tpu.tools.make_conf import make_conf


def _mkfile(tmp_path, size, seed=0, name="f.bin"):
    path = str(tmp_path / name)
    rng = np.random.default_rng(seed)
    open(path, "wb").write(
        rng.integers(0, 256, size=size, dtype=np.uint8).tobytes()
    )
    return path


# ----- bucket ladder --------------------------------------------------------


def test_bucket_ladder():
    """Powers of two of the 128-lane floor, capped at the full segment
    width; exact pass-through without a cap (direct eager callers must
    never pay pad compute)."""
    assert plan.bucket_cols(1, 1024) == 128
    assert plan.bucket_cols(100, 1024) == 128
    assert plan.bucket_cols(128, 1024) == 128
    assert plan.bucket_cols(129, 1024) == 256
    assert plan.bucket_cols(512, 1024) == 512
    assert plan.bucket_cols(513, 1024) == 1024   # ladder caps at seg width
    assert plan.bucket_cols(1000, 1024) == 1024
    assert plan.bucket_cols(1024, 1024) == 1024  # full segment: unchanged
    # Chunk smaller than one bucket: cap == chunk wins, no pad past it.
    assert plan.bucket_cols(50, 50) == 50
    assert plan.bucket_cols(700, None) == 700    # no cap -> exact shape
    # The whole ladder under a cap is O(log) wide.
    buckets = {plan.bucket_cols(m, 4096) for m in range(1, 4097)}
    assert buckets == {128, 256, 512, 1024, 2048, 4096}


def test_bucketed_dispatch_trims_back(tmp_path):
    """A tail narrower than its bucket round-trips bit-exactly — the zero
    pad's parity columns are trimmed before any caller sees them —
    including widths smaller than one bucket (tiny files)."""
    for size in (257, 4 * 100 + 3, 4 * 1500 + 1):  # chunk 65 / 101 / 1501
        path = _mkfile(tmp_path, size, seed=size, name=f"f{size}.bin")
        data = open(path, "rb").read()
        api.encode_file(path, 4, 2, segment_bytes=4096)
        conf = make_conf(6, 4, path)
        out = str(tmp_path / f"o{size}")
        api.decode_file(path, conf, out)
        assert open(out, "rb").read() == data


# ----- plan cache -----------------------------------------------------------


def test_cache_hits_and_misses():
    """Two widths in the same bucket share one executable: the first
    dispatch is the miss that compiles, the second is a pure hit."""
    plan.PLAN_CACHE.clear()
    c = RSCodec(4, 2, strategy="bitplane")
    rng = np.random.default_rng(5)
    for i, m in enumerate((700, 600)):  # both bucket to 1024 under cap 1024
        B = rng.integers(0, 256, size=(4, m), dtype=np.uint8)
        out = np.asarray(c.encode(c.stage_segment(B, cap=1024)))
        np.testing.assert_array_equal(out, c.gf.matmul(c.parity_block, B))
        assert out.shape == (2, m)  # trimmed to the true width
    s = plan.PLAN_CACHE.stats()
    assert s["misses"] == 1 and s["hits"] == 1 and s["executables"] == 1
    assert s["plans"][0]["bucket"] == 1024


def test_cache_is_bounded():
    """The LRU bound holds: more shape classes than RS_PLAN_CACHE_SIZE
    evict the oldest instead of growing without limit."""
    cache = plan.PlanCache(max_size=2)
    for b in (128, 256, 512):
        cache.lookup(("k", b), "bitplane", 8, b)
    assert len(cache._plans) == 2 and cache.evictions == 1


def test_plan_autotune_calibrates_its_own_executables(monkeypatch):
    """Under RS_PALLAS_REFOLD=autotune the AOT plan build must time ITS
    OWN compiled refold candidates, not inherit the eager dispatch's
    cached decision: a decision is only sound for the executable it
    timed, and the w16 dot mode is per-compile bimodal."""
    from gpu_rscode_tpu.ops import pallas_gemm as pg

    monkeypatch.setenv("RS_PALLAS_REFOLD", "autotune")
    plan.PLAN_CACHE.clear()
    pg.clear_autotune_cache()
    timed = []
    real = pg._time_refold
    monkeypatch.setattr(
        pg, "_time_refold", lambda run: timed.append(1) or real(run)
    )
    c = RSCodec(4, 2, strategy="pallas")
    rng = np.random.default_rng(31)
    B = rng.integers(0, 256, size=(4, 256), dtype=np.uint8)
    want = c.gf.matmul(c.parity_block, B)
    np.testing.assert_array_equal(np.asarray(c.encode(B)), want)
    n_eager = len(timed)  # eager dispatch calibrated (2 candidates)
    assert n_eager == 2
    np.testing.assert_array_equal(np.asarray(c.encode(B)), want)
    # the AOT build re-measured both candidates on its own compiles
    assert len(timed) == n_eager + 2
    plans = [
        p for p in plan.PLAN_CACHE.stats()["plans"]
        if p["strategy"] == "pallas"
    ]
    assert plans and plans[0]["refold"] in ("sum", "dot")
    pg.clear_autotune_cache()


def test_clear_also_clears_autotune_cache():
    """plan.PLAN_CACHE.clear() invalidates the pallas refold-autotune
    decisions with it: both caches pin choices to compiled executables, so
    they go stale together (ADVICE r5 finding 2; pair with
    jax.clear_caches())."""
    from gpu_rscode_tpu.ops import pallas_gemm as pg

    c = RSCodec(4, 2, strategy="bitplane")
    c.encode(np.zeros((4, 256), dtype=np.uint8))
    with pg._AUTOTUNE_LOCK:
        pg._AUTOTUNE_CACHE[("sentinel",)] = "dot"
    plan.PLAN_CACHE.clear()
    assert pg.autotune_decisions() == {}
    s = plan.PLAN_CACHE.stats()
    assert s["executables"] == 0 and s["hits"] == 0 and s["misses"] == 0


def test_plan_disable_env(monkeypatch, tmp_path):
    """RS_PLAN=0 falls back to the legacy per-shape jit dispatch — same
    bytes, no cache activity."""
    monkeypatch.setenv("RS_PLAN", "0")
    plan.PLAN_CACHE.clear()
    path = _mkfile(tmp_path, 10_001, seed=9)
    data = open(path, "rb").read()
    api.encode_file(path, 4, 2, segment_bytes=4096)
    conf = make_conf(6, 4, path)
    out = str(tmp_path / "o")
    api.decode_file(path, conf, out)
    assert open(out, "rb").read() == data
    s = plan.PLAN_CACHE.stats()
    assert not s["enabled"] and s["misses"] == 0 and s["executables"] == 0


def test_staged_dispatch_matches_host_dispatch():
    """A pipeline-staged (bucket-padded, device-resident) segment and the
    same host array produce identical output, for both symbol widths."""
    rng = np.random.default_rng(11)
    for w in (8, 16):
        sym = w // 8
        c = RSCodec(4, 2, w=w, strategy="bitplane")
        raw = rng.integers(0, 256, size=(4, 600 * sym), dtype=np.uint8)
        host_view = raw.view(np.uint16) if sym > 1 else raw
        want = np.asarray(c.encode(host_view))
        staged = c.stage_segment(raw.copy(), cap=1024, sym=sym)
        assert isinstance(staged, plan.StagedSegment)
        assert staged.array.shape == (4, 1024)  # padded to the bucket
        got = np.asarray(c.encode(staged))
        np.testing.assert_array_equal(got, want)


# ----- donation -------------------------------------------------------------


def test_donation_does_not_corrupt_retained_host_arrays(monkeypatch):
    """With donation forced on, dispatching a staged segment must leave the
    caller's host array intact (donation may only recycle the DEVICE
    buffer), and repeated dispatches of fresh stages stay bit-exact.
    Decode's (k, k) dispatch is the aliasable case — the output matches
    the donated buffer's size; encode's (p < k, k) can never alias, so
    its donate request is dropped (no donate variant, no XLA warning)."""
    monkeypatch.setenv("RS_PLAN_DONATE", "1")
    plan.PLAN_CACHE.clear()
    c = RSCodec(4, 2, strategy="bitplane")
    dec = np.eye(4, dtype=np.uint8)  # GF identity: recovery == input
    rng = np.random.default_rng(13)
    B = rng.integers(0, 256, size=(4, 700), dtype=np.uint8)
    keep = B.copy()
    with warnings.catch_warnings():
        # CPU XLA rejects donation with a UserWarning at compile; the
        # donation *request* path is what this test exercises.
        warnings.simplefilter("ignore")
        for _ in range(3):
            out = np.asarray(
                c.decode(dec, c.stage_segment(B.copy(), cap=1024))
            )
            np.testing.assert_array_equal(out, B)
        enc = np.asarray(c.encode(c.stage_segment(B.copy(), cap=1024)))
    np.testing.assert_array_equal(B, keep)
    np.testing.assert_array_equal(enc, c.gf.matmul(c.parity_block, B))
    plans = plan.PLAN_CACHE.stats()["plans"]
    assert any(
        p["donated_calls"] >= 1 for p in plans if p["a_shape"] == [4, 4]
    )
    # encode's output is smaller than the staged buffer: never donated
    assert all(
        p["donated_calls"] == 0 for p in plans if p["a_shape"] == [2, 4]
    )


def test_caller_owned_device_arrays_are_never_donated():
    """A device array the caller placed (a bench timing the same buffer
    repeatedly) must stay valid across dispatches — only
    pipeline-staged StagedSegment buffers are donation candidates."""
    import jax

    plan.PLAN_CACHE.clear()
    c = RSCodec(4, 2, strategy="bitplane")
    rng = np.random.default_rng(17)
    B = rng.integers(0, 256, size=(4, 512), dtype=np.uint8)
    Bd = jax.device_put(B)
    want = c.gf.matmul(c.parity_block, B)
    for _ in range(3):  # donation would kill the second iteration
        np.testing.assert_array_equal(np.asarray(c.encode(Bd)), want)
    assert all(
        p["donated_calls"] == 0 for p in plan.PLAN_CACHE.stats()["plans"]
    )


# ----- pallas strategy under the plan layer ---------------------------------


def test_pallas_first_dispatch_eager_then_aot(monkeypatch):
    """The pallas strategy keeps its documented first-dispatch contract
    under the plan layer: dispatch #1 runs eagerly through the
    codec._gf_matmul_pallas_eager hook (failure injection + autotune
    calibration on concrete arrays), later same-shape dispatches run the
    AOT plan executable — bit-exact either way."""
    from gpu_rscode_tpu import codec as codec_mod

    plan.PLAN_CACHE.clear()
    calls = []
    real = codec_mod._gf_matmul_pallas_eager

    def spy(A, B, w=8):
        calls.append(B.shape)
        return real(A, B, w)

    monkeypatch.setattr(codec_mod, "_gf_matmul_pallas_eager", spy)
    c = RSCodec(4, 2, strategy="pallas")
    rng = np.random.default_rng(19)
    B = rng.integers(0, 256, size=(4, 512), dtype=np.uint8)
    want = c.gf.matmul(c.parity_block, B)
    np.testing.assert_array_equal(np.asarray(c.encode(B)), want)
    np.testing.assert_array_equal(np.asarray(c.encode(B)), want)
    assert len(calls) == 1  # only the first dispatch took the eager hook
    plans = plan.PLAN_CACHE.stats()["plans"]
    assert [p for p in plans if p["strategy"] == "pallas"]


def test_pack2_expand_survives_plan_aot_rebuild(monkeypatch):
    """RS_PALLAS_EXPAND=pack2 has a fixed packed-refold pipeline that
    REJECTS an explicit refold: the plan's AOT rebuild (dispatch #2) must
    leave refold unset rather than bake in a static 'sum'/'dot' — the
    eager path accepted pack2 before the plan layer and must keep doing
    so after it (no demote, no ValueError)."""
    monkeypatch.setenv("RS_PALLAS_EXPAND", "pack2")
    plan.PLAN_CACHE.clear()
    c = RSCodec(4, 2, strategy="pallas")
    rng = np.random.default_rng(29)
    B = rng.integers(0, 256, size=(4, 256), dtype=np.uint8)
    want = c.gf.matmul(c.parity_block, B)
    for _ in range(3):  # 1: eager+proof, 2: AOT build, 3: AOT run
        np.testing.assert_array_equal(np.asarray(c.encode(B)), want)
    assert c.strategy == "pallas"  # never demoted


def test_pallas_failure_raises_under_plan(monkeypatch):
    """A fused-kernel failure on the first (eager) dispatch of a staged
    segment raises, naming the shapes; nothing runs in its place."""
    import jax

    from gpu_rscode_tpu import codec as codec_mod

    plan.PLAN_CACHE.clear()

    def boom(A, B, w=8):
        raise jax.errors.JaxRuntimeError("MOSAIC: no")

    monkeypatch.setattr(codec_mod, "_gf_matmul_pallas_eager", boom)
    c = RSCodec(4, 2, strategy="pallas")
    rng = np.random.default_rng(23)
    B = rng.integers(0, 256, size=(4, 512), dtype=np.uint8)
    with pytest.raises(codec_mod.FusedKernelError, match=r"B\(4, 512\)"):
        c.encode(c.stage_segment(B.copy(), cap=512))
    assert c.strategy == "pallas"


# ----- compile-count guard (tier-1, ISSUE acceptance) -----------------------


def test_segment_loop_compile_count_bounded(tmp_path):
    """THE bucket-ladder guard: a (k, p, strategy) whose segment loop sees
    >= 4 distinct raw trace shapes (four different tail widths plus the
    full segment width) must hold <= 2 plan executables — the bound that
    keeps tail segments from paying a fresh XLA compile each."""
    plan.PLAN_CACHE.clear()
    k, p, seg_bytes = 4, 2, 4096            # seg_cols = 1024
    tails = (520, 652, 776, 1000)           # all in (512, 1024]
    widths = set()
    for i, tail in enumerate(tails):
        chunk = 2 * 1024 + tail
        path = _mkfile(tmp_path, k * chunk, seed=i, name=f"t{tail}.bin")
        api.encode_file(path, k, p, segment_bytes=seg_bytes)
        widths.update(
            cols for _, cols in api._segment_spans(chunk, 1024)
        )
    assert len(widths) >= 4, widths  # the loop really saw >= 4 raw shapes
    encode_plans = [
        pl for pl in plan.PLAN_CACHE.stats()["plans"]
        if pl["a_shape"] == [p, k] and pl["strategy"] != "cpu"
    ]
    assert 1 <= len(encode_plans) <= 2, encode_plans


def test_plan_stats_tool_smoke(capsys):
    """tools/plan_stats.py runs a synthetic multi-tail workload and emits
    one machine-readable JSON line whose executable count respects the
    ladder bound."""
    from gpu_rscode_tpu.tools.plan_stats import main

    assert main(["--seg-kb", "4", "--tails", "520", "1000"]) == 0
    line = capsys.readouterr().out.strip().splitlines()[-1]
    out = json.loads(line)
    assert out["metric"] == "plan_cache_stats"
    assert out["stats"]["executables"] >= 1
    assert out["ladder_bound"] >= out["encode_executables"]
