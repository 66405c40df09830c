"""Warm-path amortization (docs/XOR.md "The persistent store" /
"Packed-operand reuse", docs/PLAN.md "Generation-keyed schedule
entries"): persistent schedule + autotune store round trips and
corruption fallbacks, ledger-vs-measure autotune precedence, cache-clear
coherence, packed-domain reuse byte-equivalence, the generation-keyed
survivor-subset cache, and the cross-process warm start."""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

from gpu_rscode_tpu import api, plan, tune
from gpu_rscode_tpu.obs import runlog
from gpu_rscode_tpu.ops import xor_gemm as xg
from gpu_rscode_tpu.ops.gf import get_field

GF8 = get_field(8)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def store(tmp_path, monkeypatch):
    """A dedicated schedule/autotune store file, with every warm-path
    cache reset around the test so nothing leaks across tests."""
    p = str(tmp_path / "store.jsonl")
    monkeypatch.setenv("RS_SCHEDULE_STORE", p)
    plan.PLAN_CACHE.clear()
    tune.clear_decisions()
    yield p
    plan.PLAN_CACHE.clear()
    tune.clear_decisions()


def _delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before[k]
            for k in ("hits", "misses", "stored", "corrupt", "built")}


def _mat(rows=4, cols=6, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(1, 256, size=(rows, cols)).astype(GF8.dtype)


# ----- persistent schedule store ---------------------------------------------


def test_schedule_store_roundtrip(store):
    A = _mat(seed=1)
    before = xg.store_stats()
    s1 = xg.build_schedule(A, 8)
    d = _delta(xg.store_stats(), before)
    assert d["built"] == 1 and d["stored"] == 1 and d["misses"] == 1
    # a second process is modelled by clearing the in-process caches:
    # the rebuild must LOAD, not re-run Paar.
    plan.PLAN_CACHE.clear()
    before = xg.store_stats()
    s2 = xg.build_schedule(A, 8)
    d = _delta(xg.store_stats(), before)
    assert d["hits"] == 1 and d["built"] == 0 and d["stored"] == 0
    assert (s2.digest, s2.pair_ops, s2.rows) == (
        s1.digest, s1.pair_ops, s1.rows
    )
    assert (s2.terms_naive, s2.terms_cse) == (s1.terms_naive, s1.terms_cse)
    # the store file holds exactly one schedule record for this digest
    recs = [r for r in runlog.read_records(store)
            if r.get("kind") == "rs_xor_schedule"]
    assert len(recs) == 1 and recs[0]["digest"] == s1.digest


def test_schedule_store_disabled_without_path(tmp_path, monkeypatch):
    monkeypatch.setenv("RS_SCHEDULE_STORE", "0")
    monkeypatch.setenv("RS_RUNLOG", str(tmp_path / "ledger.jsonl"))
    assert runlog.store_path() is None
    monkeypatch.setenv("RS_SCHEDULE_STORE", "1")
    assert runlog.store_path() == str(tmp_path / "ledger.jsonl")
    monkeypatch.delenv("RS_SCHEDULE_STORE")
    assert runlog.store_path() == str(tmp_path / "ledger.jsonl")
    monkeypatch.setenv("RS_SCHEDULE_STORE", str(tmp_path / "own.jsonl"))
    assert runlog.store_path() == str(tmp_path / "own.jsonl")


@pytest.mark.parametrize("tamper", ["out_of_range", "payload", "truncated"])
def test_corrupt_store_entry_recomputes_never_crashes(store, tamper):
    A = _mat(seed=2)
    s1 = xg.build_schedule(A, 8)
    if tamper == "truncated":
        # a torn tail line (crashed writer) plus a re-pointed build
        with open(store, "w") as fp:
            fp.write('{"kind": "rs_xor_schedule", "digest": "')
    else:
        recs = runlog.read_records(store)
        rec = next(r for r in recs if r.get("kind") == "rs_xor_schedule")
        if tamper == "out_of_range":
            rec["rows"] = [[999999]] + rec["rows"][1:]
        else:  # valid-looking terms, wrong checksum
            rec["rows"] = [sorted(set(rec["rows"][0]) ^ {0, 1})] \
                + rec["rows"][1:]
        with open(store, "w") as fp:
            for r in recs:
                fp.write(json.dumps(r) + "\n")
    plan.PLAN_CACHE.clear()
    before = xg.store_stats()
    s2 = xg.build_schedule(A, 8)  # must not crash, must not trust the rec
    d = _delta(xg.store_stats(), before)
    assert d["built"] == 1
    if tamper != "truncated":
        assert d["corrupt"] == 1
    assert (s2.pair_ops, s2.rows) == (s1.pair_ops, s1.rows)
    # the recompute re-stored a good record: a third build loads clean
    plan.PLAN_CACHE.clear()
    before = xg.store_stats()
    s3 = xg.build_schedule(A, 8)
    d = _delta(xg.store_stats(), before)
    assert d["hits"] == 1 and d["built"] == 0
    assert s3.rows == s1.rows


def test_cache_clear_does_not_resurrect_but_revalidates(store):
    """The clear-coherence contract: PLAN_CACHE.clear() drops every
    in-process schedule/pipeline/stage cache AND the store's in-memory
    index; the store FILE survives, and post-clear loads re-read and
    re-validate it from disk."""
    A = _mat(seed=3)
    xg.build_schedule(A, 8)
    assert xg.schedule_stats()
    plan.PLAN_CACHE.clear()
    assert xg.schedule_stats() == []          # in-process state gone
    assert xg.pipeline_stats() == []
    assert os.path.exists(store)              # persistent state kept
    # wiping the store file after a clear means the next build computes:
    # nothing cached in RAM can resurrect a schedule the store lost.
    os.unlink(store)
    plan.PLAN_CACHE.clear()
    before = xg.store_stats()
    xg.build_schedule(A, 8)
    assert _delta(xg.store_stats(), before)["built"] == 1


def test_store_stats_shape(store):
    st = xg.store_stats(load=True)
    assert st["path"] == store and st["enabled"] is True
    assert {"entries", "hits", "misses", "stored", "corrupt",
            "built"} <= set(st)


def test_pre_optimizer_store_record_recomputed_not_served(store):
    """Algo-version regression (ISSUE 16): a store populated BEFORE the
    schedule-optimizer landed carries ``algo: 1`` records without the
    explicit ``algo_version`` field, and their payload digests still
    validate (the digest never covered the algo fields).  Such a record
    must take the corrupt-style drop + recompute + re-store path — never
    be served on the strength of its checksum."""
    A = _mat(seed=14)
    s1 = xg.build_schedule(A, 8)
    recs = runlog.read_records(store)
    rec = next(r for r in recs if r.get("kind") == "rs_xor_schedule")
    # Rewrite to the exact pre-PR record shape: old algo value, no
    # algo_version field, payload digest untouched (it still validates).
    rec["algo"] = 1
    del rec["algo_version"]
    with open(store, "w") as fp:
        for r in recs:
            fp.write(json.dumps(r) + "\n")
    plan.PLAN_CACHE.clear()
    before = xg.store_stats()
    s2 = xg.build_schedule(A, 8)
    d = _delta(xg.store_stats(), before)
    assert d["corrupt"] == 1 and d["built"] == 1 and d["hits"] == 0
    assert (s2.pair_ops, s2.rows) == (s1.pair_ops, s1.rows)
    # The recompute re-stored a current-version record: next build loads.
    plan.PLAN_CACHE.clear()
    before = xg.store_stats()
    xg.build_schedule(A, 8)
    d = _delta(xg.store_stats(), before)
    assert d["hits"] == 1 and d["built"] == 0
    newest = [r for r in runlog.read_records(store)
              if r.get("kind") == "rs_xor_schedule"]
    assert newest[-1]["algo_version"] == xg._STORE_ALGO


# ----- autotune ledger precedence --------------------------------------------


def _seed_autotune(store, k, p, w, strategy, ts=1.0):
    runlog.append({
        "kind": "rs_autotune", "host": socket.gethostname(),
        "backend": "other", "k": k, "p": p, "w": w,
        "strategy": strategy, "gbps": {strategy: 1.0}, "ts": ts,
    }, store)


def test_resolve_auto_prefers_ledger_in_prior_mode(store, monkeypatch):
    monkeypatch.delenv("RS_STRATEGY_AUTOTUNE", raising=False)
    _seed_autotune(store, 6, 3, 8, "table")
    tune.clear_decisions()
    assert tune.resolve_auto(6, 3, 8) == "table"
    (decision,) = tune.decisions().values()
    assert decision["source"] == "ledger"
    # an unseeded class still takes the static prior
    assert tune.resolve_auto(7, 3, 8) == tune.static_choice(8)


def test_ledger_ignores_other_hosts_and_junk(store, monkeypatch):
    monkeypatch.delenv("RS_STRATEGY_AUTOTUNE", raising=False)
    runlog.append({
        "kind": "rs_autotune", "host": "someone-else", "backend": "other",
        "k": 6, "p": 3, "w": 8, "strategy": "table", "ts": 1.0,
    }, store)
    runlog.append({"kind": "rs_autotune", "host": socket.gethostname(),
                   "backend": "other", "k": "junk"}, store)
    tune.clear_decisions()
    assert tune.resolve_auto(6, 3, 8) == tune.static_choice(8)


def test_measure_mode_reprobes_over_ledger(store, monkeypatch):
    """RS_STRATEGY_AUTOTUNE=measure must ignore a ledger-sourced entry,
    re-probe, and overwrite — the documented precedence."""
    monkeypatch.delenv("RS_STRATEGY_AUTOTUNE", raising=False)
    _seed_autotune(store, 6, 3, 8, "table")
    tune.clear_decisions()
    assert tune.resolve_auto(6, 3, 8) == "table"  # ledger cached in-proc
    monkeypatch.setenv("RS_STRATEGY_AUTOTUNE", "measure")
    monkeypatch.setattr(
        tune, "_measure_one",
        lambda strategy, A, B, w: 0.001 if strategy == "bitplane" else 1.0,
    )
    assert tune.resolve_auto(6, 3, 8) == "bitplane"
    (decision,) = tune.decisions().values()
    assert decision["source"] == "measured"
    # ...and the re-probe PERSISTED, superseding the seeded record: a
    # fresh process in prior mode now resolves the measured winner.
    tune.clear_decisions()
    monkeypatch.setenv("RS_STRATEGY_AUTOTUNE", "prior")
    assert tune.resolve_auto(6, 3, 8) == "bitplane"
    (decision,) = tune.decisions().values()
    assert decision["source"] == "ledger"


def test_rotation_carries_store_records_forward(store, monkeypatch):
    """High-volume rs_run traffic must not rotate the persistent store
    away: rotation carries calibration/cache kinds into the fresh file
    (two rotations without the carry would lose them entirely)."""
    A = _mat(2, 3, seed=9)
    s1 = xg.build_schedule(A, 8)
    monkeypatch.setenv("RS_RUNLOG", store)
    monkeypatch.setenv("RS_RUNLOG_MAX_BYTES", "16384")
    filler = {"op": "encode", "outcome": "ok", "pad": "x" * 512}
    for _ in range(60):  # several rotations worth of measurements
        runlog.record(dict(filler))
    kinds = [r.get("kind")
             for r in runlog.read_records(store, include_rotated=False)]
    assert "rs_xor_schedule" in kinds, (
        "rotation dropped the schedule store records from the live file"
    )
    plan.PLAN_CACHE.clear()
    before = xg.store_stats()
    s2 = xg.build_schedule(A, 8)
    assert _delta(xg.store_stats(), before)["hits"] == 1
    assert s2.rows == s1.rows


def test_rotation_carry_keeps_newest_superseding_record(store, monkeypatch):
    """Dedup-by-identity, latest wins: a re-measured verdict must never
    lose its carry slot to its own stale predecessor."""
    _seed_autotune(store, 6, 3, 8, "table")     # stale
    _seed_autotune(store, 6, 3, 8, "bitplane")  # superseding re-measure
    monkeypatch.setenv("RS_RUNLOG", store)
    monkeypatch.setenv("RS_RUNLOG_MAX_BYTES", "4096")
    for _ in range(30):
        runlog.record({"op": "encode", "outcome": "ok", "pad": "x" * 256})
    live = [r for r in runlog.read_records(store, include_rotated=False)
            if r.get("kind") == "rs_autotune"]
    assert len(live) == 1 and live[0]["strategy"] == "bitplane", live
    tune.clear_decisions()
    monkeypatch.delenv("RS_STRATEGY_AUTOTUNE", raising=False)
    assert tune.resolve_auto(6, 3, 8) == "bitplane"


def test_ledger_resolves_by_timestamp_not_file_order(store, monkeypatch):
    """Rotation carry can interleave an old record AFTER a concurrent
    fresh append — recency must come from the ts field, never from
    position in the file."""
    monkeypatch.delenv("RS_STRATEGY_AUTOTUNE", raising=False)
    _seed_autotune(store, 6, 3, 8, "bitplane", ts=200.0)  # newer, first
    _seed_autotune(store, 6, 3, 8, "table", ts=100.0)     # stale, later
    tune.clear_decisions()
    assert tune.resolve_auto(6, 3, 8) == "bitplane"


def test_ledger_verdict_revalidated_against_candidates(store, monkeypatch):
    """A persisted winner that is no longer runnable here (native codec
    removed, TPU host now CPU-only) must fall back to the static prior,
    not silently route onto a fallback path."""
    monkeypatch.delenv("RS_STRATEGY_AUTOTUNE", raising=False)
    _seed_autotune(store, 6, 3, 8, "pallas")  # never a CPU candidate
    tune.clear_decisions()
    assert tune.resolve_auto(6, 3, 8) == tune.static_choice(8)
    from gpu_rscode_tpu import native

    _seed_autotune(store, 9, 3, 8, "cpu")
    tune.clear_decisions()
    monkeypatch.setattr(native, "available", lambda: False)
    assert tune.resolve_auto(9, 3, 8) == tune.static_choice(8)
    monkeypatch.setattr(native, "available", lambda: True)
    tune.clear_decisions()
    assert tune.resolve_auto(9, 3, 8) == "cpu"


def test_pack_timing_is_opt_in(monkeypatch):
    """RS_METRICS alone must NOT enable the blocking pack timer (it
    would sync the hot pipeline on every xor dispatch); the quantile
    records only with RS_XOR_PACK_TIMING=1 on top."""
    from gpu_rscode_tpu.obs import metrics

    was_forced = metrics.forced()
    metrics.force_enable(True)
    try:
        import jax

        B = jax.device_put(np.zeros((2, 64), dtype=np.uint8))
        monkeypatch.delenv("RS_XOR_PACK_TIMING", raising=False)
        assert not xg.pack_timing_enabled()

        def pack_count():
            snap = metrics.REGISTRY.snapshot().get(
                "rs_xor_pack_seconds", {}
            )
            return snap.get("values", {}).get("", {}).get("count", 0)

        c0 = pack_count()
        xg.pack_operand(B, 8)
        assert pack_count() == c0  # metrics on, timing off: no sample
        monkeypatch.setenv("RS_XOR_PACK_TIMING", "1")
        assert xg.pack_timing_enabled()
        xg.pack_operand(B, 8)
        assert pack_count() == c0 + 1
    finally:
        metrics.force_enable(was_forced)


def test_store_records_hidden_from_history(store):
    xg.build_schedule(_mat(seed=4), 8)
    _seed_autotune(store, 6, 3, 8, "table")
    runlog.append({"kind": "rs_run", "op": "encode", "outcome": "ok",
                   "bytes": 10, "wall_s": 1.0, "config": {}}, store)
    recs = runlog.read_records(store)
    assert any(r.get("kind") == "rs_xor_schedule" for r in recs)
    filtered = runlog.filter_records(recs)
    assert [r.get("kind") for r in filtered] == ["rs_run"]


# ----- packed-domain reuse ----------------------------------------------------


def _encode_archive(tmp_path, name, k, p, w, generator, nbytes=200_000):
    src = str(tmp_path / name)
    rng = np.random.default_rng(11)
    with open(src, "wb") as fp:
        fp.write(rng.integers(0, 256, nbytes, dtype=np.uint8).tobytes())
    api.encode_file(src, k, p, w=w, generator=generator, strategy="xor")
    return src


@pytest.mark.parametrize("w,generator", [
    (8, "vandermonde"), (8, "cauchy"), (16, "vandermonde"), (16, "cauchy"),
])
def test_packed_reuse_byte_equivalent(tmp_path, monkeypatch, w, generator):
    """locate decode with packed-domain reuse must produce the same
    bytes as the unshared path — with a native erasure (recovery GEMM
    consumes the reused planes) and with silent bitrot (the in-place
    patch invalidates the planes; the fallback re-stages)."""
    src = _encode_archive(tmp_path, "f.bin", 6, 3, w, generator)
    original = open(src, "rb").read()
    os.unlink(api.chunk_file_name(src, 2))
    # flip two bytes in a surviving parity chunk: silent bitrot the
    # syndrome locate must patch before recovery.
    victim = api.chunk_file_name(src, 7)
    buf = bytearray(open(victim, "rb").read())
    buf[40] ^= 0x5A
    buf[41] ^= 0x0F
    with open(victim, "wb") as fp:
        fp.write(bytes(buf))
    outs = {}
    for arm, env in (("reuse", "1"), ("noreuse", "0")):
        monkeypatch.setenv("RS_XOR_PACK_REUSE", env)
        out = str(tmp_path / f"out_{arm}.bin")
        api.locate_decode_file(src, out, strategy="xor")
        outs[arm] = open(out, "rb").read()
    assert outs["reuse"] == original
    assert outs["noreuse"] == original


def test_packed_operand_select_and_validation():
    rng = np.random.default_rng(5)
    B = rng.integers(0, 256, size=(5, 64), dtype=np.uint8)
    import jax

    packed = xg.pack_operand(jax.device_put(B), 8)
    assert packed.shape == (5, 64)
    sub = packed.select([3, 0])
    assert sub.rows == 2 and sub.cols == 64
    assert sub.planes == packed.planes[24:32] + packed.planes[0:8]
    with pytest.raises(ValueError, match="out of range"):
        packed.select([5])
    with pytest.raises(ValueError, match="32-aligned"):
        xg.pack_operand(np.zeros((2, 33), dtype=np.uint8), 8)


def test_packed_operand_gemm_equivalence():
    """A GEMM fed a PackedOperand (full and row-subset) must equal the
    host GF oracle."""
    import jax

    rng = np.random.default_rng(6)
    A = rng.integers(0, 256, size=(3, 4), dtype=np.uint8)
    B = rng.integers(0, 256, size=(6, 96), dtype=np.uint8)
    packed = xg.pack_operand(jax.device_put(B), 8)
    sub_rows = [5, 1, 3, 0]
    got = np.asarray(plan.dispatch(
        A, packed.select(sub_rows), w=8, strategy="xor",
        cap=packed.cap, cols=packed.cols_true,
    ))
    np.testing.assert_array_equal(got, GF8.matmul(A, B[sub_rows]))


def test_pack_reuse_knob(monkeypatch):
    monkeypatch.setenv("RS_XOR_PACK_REUSE", "0")
    assert not xg.pack_reuse_enabled()
    from gpu_rscode_tpu.codec import RSCodec

    codec = RSCodec(4, 2, strategy="xor")
    staged = codec.stage_segment(
        np.zeros((4, 64), dtype=np.uint8), cap=64
    )
    assert codec.pack_operand(staged) is None
    monkeypatch.delenv("RS_XOR_PACK_REUSE")
    assert xg.pack_reuse_enabled()
    packed = codec.pack_operand(staged)
    assert packed is not None and packed.rows == 4


# ----- generation-keyed survivor-subset cache --------------------------------


def _subset_delta(after, before):
    return {k: after[k] - before[k] for k in ("hits", "misses", "stale")}


def test_subset_cache_hit_and_generation_bump(tmp_path):
    src = str(tmp_path / "g.bin")
    rng = np.random.default_rng(12)
    with open(src, "wb") as fp:
        fp.write(rng.integers(0, 256, 100_000, dtype=np.uint8).tobytes())
    api.encode_file(src, 4, 2)
    api.clear_subset_cache()
    before = api.subset_cache_stats()
    assert api.scan_file(src)["decodable"] is True
    d = _subset_delta(api.subset_cache_stats(), before)
    assert d["misses"] == 1 and d["hits"] == 0
    before = api.subset_cache_stats()
    assert api.scan_file(src)["decodable"] is True
    d = _subset_delta(api.subset_cache_stats(), before)
    assert d["hits"] == 1 and d["misses"] == 0
    # an update bumps the metadata generation -> the entry is stale and
    # the next scan re-selects (the docs/PLAN.md invalidation contract)
    api.update_file(src, 10, data=b"\xff" * 16)
    before = api.subset_cache_stats()
    assert api.scan_file(src)["decodable"] is True
    d = _subset_delta(api.subset_cache_stats(), before)
    assert d["stale"] == 1 and d["misses"] == 1 and d["hits"] == 0


def test_subset_cache_rejects_foreign_matrix(tmp_path):
    """Re-encoding the same path with a different generator must not
    serve the old inverse (the matrix digest guards the entry)."""
    src = str(tmp_path / "h.bin")
    rng = np.random.default_rng(13)
    payload = rng.integers(0, 256, 50_000, dtype=np.uint8).tobytes()
    with open(src, "wb") as fp:
        fp.write(payload)
    api.encode_file(src, 4, 2, generator="vandermonde")
    api.clear_subset_cache()
    api.scan_file(src)
    api.encode_file(src, 4, 2, generator="cauchy")
    before = api.subset_cache_stats()
    assert api.scan_file(src)["decodable"] is True
    d = _subset_delta(api.subset_cache_stats(), before)
    assert d["stale"] == 1 and d["misses"] == 1
    # and the decode is still byte-correct
    os.unlink(api.chunk_file_name(src, 1))
    out = str(tmp_path / "h.out")
    api.auto_decode_file(src, out)
    assert open(out, "rb").read() == payload


def test_subset_churn_compiles_one_inverse_schedule(tmp_path):
    """The acceptance scenario: >= 5 DISTINCT survivor sets at one
    generation resolve to the pinned subset, so exactly ONE xor inverse
    schedule is compiled across the whole churn loop — visible in the
    doctor schedule-cache stats."""
    k, p = 5, 3
    src = str(tmp_path / "churn.bin")
    rng = np.random.default_rng(11)
    with open(src, "wb") as fp:
        fp.write(rng.integers(0, 256, 200_000, dtype=np.uint8).tobytes())
    # CRC lines keep auto-decode on the erasure ladder (locate-first
    # engages only on CRC-less archives), so every round runs the
    # subset selection this test is pinning.
    api.encode_file(src, k, p, strategy="xor", checksums=True)
    original = open(src, "rb").read()
    chunks = {i: open(api.chunk_file_name(src, i), "rb").read()
              for i in range(k + p)}
    plan.PLAN_CACHE.clear()  # also clears schedules + subset cache
    os.unlink(api.chunk_file_name(src, 0))  # native 0 gone for good
    out = str(tmp_path / "churn.out")

    def survivors() -> tuple:
        return tuple(sorted(
            i for i in range(k + p)
            if os.path.exists(api.chunk_file_name(src, i))
        ))

    seen = set()
    # Five distinct survivor pools, same generation.  The pinned subset
    # from round 1 is natives 1-4 + parity 5; later rounds delete
    # parities 6/7 (alone and together) and finally RESTORE native 0 —
    # the round where an unpinned natives-first search would switch
    # subsets and compile a second inverse schedule.
    variants = [(), (6,), (7,), (6, 7), ("restore0",)]
    for variant in variants:
        removed = []
        if variant == ("restore0",):
            with open(api.chunk_file_name(src, 0), "wb") as fp:
                fp.write(chunks[0])
        else:
            for i in variant:
                os.unlink(api.chunk_file_name(src, i))
                removed.append(i)
        seen.add(survivors())
        api.auto_decode_file(src, out, strategy="xor")
        assert open(out, "rb").read() == original
        for i in removed:
            with open(api.chunk_file_name(src, i), "wb") as fp:
                fp.write(chunks[i])
        if variant == ("restore0",):
            os.unlink(api.chunk_file_name(src, 0))
    assert len(seen) >= 5
    # exactly one k-column recovery schedule (the encode matrix's p x k
    # schedule is a different shape class and doesn't count)
    inverse_scheds = [
        s for s in xg.schedule_stats() if s["k"] == k and s["rows_out"] < k
    ]
    assert len(inverse_scheds) == 1, inverse_scheds
    stats = api.subset_cache_stats()
    assert stats["hits"] >= 4 and stats["misses"] == 1


# ----- doctor surface ---------------------------------------------------------


def test_doctor_strategies_store_section(store, capsys):
    from gpu_rscode_tpu import cli

    xg.build_schedule(_mat(seed=7), 8)
    assert cli.main(["doctor", "--json", "--no-probe"]) == 0
    report = json.loads(capsys.readouterr().out)
    sec = report["strategies"]
    assert sec["error"] is None
    st = sec["store"]
    assert st["path"] == store and st["enabled"] is True
    assert st["entries"] >= 1
    assert {"hits", "misses", "stored", "corrupt", "built",
            "ledger_autotune"} <= set(st)
    assert {"entries", "hits", "misses", "stale"} <= set(
        sec["inverse_cache"]
    )


# ----- cross-process warm start ----------------------------------------------


_CHILD = r"""
import json, os, sys
sys.path.insert(0, sys.argv[1])
import numpy as np
from gpu_rscode_tpu import api
from gpu_rscode_tpu.ops import xor_gemm
payload = sys.argv[2] + ".payload"
if not os.path.exists(payload):
    with open(payload, "wb") as fp:
        fp.write(np.random.default_rng(3).integers(
            0, 256, 65536, dtype=np.uint8).tobytes())
api.encode_file(payload, 4, 2, strategy="xor")
print(json.dumps(xor_gemm.store_stats()))
"""


def test_cross_process_warm_start(tmp_path):
    """Process one encodes against a fresh store; process two must build
    ZERO schedules — every build is served by the store the first
    process populated (the CI warm-start leg's in-tree twin)."""
    store_file = str(tmp_path / "xstore.jsonl")
    env = dict(os.environ)
    env["RS_SCHEDULE_STORE"] = store_file
    env.pop("RS_RUNLOG", None)

    def run() -> dict:
        proc = subprocess.run(
            [sys.executable, "-c", _CHILD, REPO_ROOT,
             str(tmp_path / "w")],
            capture_output=True, text=True, env=env, timeout=300,
        )
        assert proc.returncode == 0, proc.stderr
        return json.loads(proc.stdout.strip().splitlines()[-1])

    first = run()
    assert first["built"] >= 1 and first["stored"] >= 1
    second = run()
    assert second["built"] == 0, (
        f"second process compiled {second['built']} schedules; the "
        "persistent store must serve them"
    )
    assert second["hits"] >= 1


# ----- tool surfaces ----------------------------------------------------------


def test_locate_ab_tool_capture_schema(tmp_path, capsys):
    from gpu_rscode_tpu.tools import xor_ab

    cap = str(tmp_path / "locate_ab.jsonl")
    rc = xor_ab.main([
        "--locate-ab", "--size-mb", "0.5", "--trials", "1",
        "--capture", cap, "--json",
    ])
    assert rc == 0
    lines = open(cap).read().splitlines()
    head = json.loads(lines[0])
    assert head["kind"] == "capture_header"
    assert head["tool"] == "xor_locate_ab"
    assert head["host_cpus"] >= 1 and head["intra_op_threads"] >= 1
    row = json.loads(lines[1])
    assert row["kind"] == "xor_locate_ab"
    assert row["op"] == "locate_decode"
    assert row["best_pack_s"]["reuse"] >= 0
    assert row["best_pack_s"]["noreuse"] > 0
    assert row["wall_speedup"] > 0
