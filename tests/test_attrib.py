"""Performance-attribution layer (obs/attrib.py, obs/doctor.py).

Covers the ISSUE 6 contract: cost_analysis absence/partial-key fallback
(CPU backends vary), the roofline ledger cache, the `rs analyze --json`
schema the CI analyze-smoke step validates, `rs doctor --json` schema
stability, and the tier-1 guard that the disabled-attribution path
registers nothing (mirroring test_disabled_fault_plane_is_noop).
"""

import json
import os
import time

import numpy as np
import pytest

from gpu_rscode_tpu import api, cli, plan
from gpu_rscode_tpu.obs import attrib, doctor, metrics, percentile


@pytest.fixture
def clean_registry(monkeypatch):
    metrics.REGISTRY.reset()
    yield
    metrics.force_enable(False)
    metrics.REGISTRY.reset()


def _mkfile(tmp_path, size, name="f.bin"):
    p = str(tmp_path / name)
    rng = np.random.default_rng(7)
    with open(p, "wb") as fp:
        fp.write(rng.integers(0, 256, size=size, dtype=np.uint8).tobytes())
    return p


# ----- cost_analysis extraction (backend-variance tolerance) ----------------


class _Compiled:
    def __init__(self, result=None, raises=None):
        self._result = result
        self._raises = raises

    def cost_analysis(self):
        if self._raises is not None:
            raise self._raises
        return self._result


def test_cost_analysis_none_and_raising_backends():
    assert attrib.extract_cost_analysis(_Compiled(None)) is None
    assert attrib.extract_cost_analysis(
        _Compiled(raises=NotImplementedError("no cost model"))
    ) is None
    assert attrib.extract_cost_analysis(_Compiled([])) is None
    assert attrib.extract_cost_analysis(_Compiled("bogus")) is None


def test_cost_analysis_partial_keys_and_list_form():
    # Partial key set (CPU backends omit keys TPU builds report).
    got = attrib.extract_cost_analysis(_Compiled({"flops": 42.0}))
    assert got == {"flops": 42.0}
    # Old-style list-of-dicts form, plus keys that must not leak through.
    got = attrib.extract_cost_analysis(_Compiled([{
        "flops": 10, "bytes accessed": 20.5, "transcendentals": 0,
        "utilization operand 0 {}": 9.9,
    }]))
    assert got == {"flops": 10.0, "bytes_accessed": 20.5,
                   "transcendentals": 0.0}
    # All-unusable values degrade to None, not {}.
    assert attrib.extract_cost_analysis(
        _Compiled({"flops": "NaNish", "bytes accessed": None})
    ) is None


def test_plan_compile_tolerates_cost_analysis_failure(monkeypatch,
                                                      clean_registry):
    """A backend whose cost_analysis() raises must not fail the plan
    build — the plan stats then carry cost_analysis: None and `rs
    analyze` falls back to the analytic model."""
    original = attrib.extract_cost_analysis
    monkeypatch.setattr(
        attrib, "extract_cost_analysis",
        lambda compiled: original(
            _Compiled(raises=RuntimeError("backend variance"))
        ),
    )
    plan.PLAN_CACHE.clear()
    A = np.random.randint(0, 256, (2, 4), dtype=np.uint8)
    B = np.random.randint(0, 256, (4, 512), dtype=np.uint8)
    out = plan.dispatch(A, B, w=8, strategy="table", cap=512)
    assert out.shape == (2, 512)
    stats = plan.PLAN_CACHE.stats()
    assert stats["plans"] and all(
        p["cost_analysis"] is None for p in stats["plans"]
    )
    plan.PLAN_CACHE.clear()


def test_plan_stats_carry_cost_analysis(clean_registry):
    plan.PLAN_CACHE.clear()
    A = np.random.randint(0, 256, (2, 4), dtype=np.uint8)
    B = np.random.randint(0, 256, (4, 512), dtype=np.uint8)
    plan.dispatch(A, B, w=8, strategy="table", cap=512)
    plans = plan.PLAN_CACHE.stats()["plans"]
    assert len(plans) == 1
    ca = plans[0]["cost_analysis"]
    # CPU XLA reports these; a backend returning None is covered above.
    if ca is not None:
        assert set(ca) <= {"flops", "bytes_accessed", "transcendentals"}
        assert all(isinstance(v, float) for v in ca.values())
    plan.PLAN_CACHE.clear()


# ----- roofline probe + ledger cache ----------------------------------------


def test_roofline_probe_and_ledger_cache(tmp_path, monkeypatch):
    ledger = str(tmp_path / "ledger.jsonl")
    monkeypatch.setenv("RS_RUNLOG", ledger)
    monkeypatch.setattr(
        attrib, "measure_roofline",
        lambda reps=3: {"triad_gbps": 12.5, "gemm_gflops": 99.0,
                        "ts": time.time(), "host": __import__(
                            "socket").gethostname()},
    )
    first = attrib.get_roofline(ledger)
    assert first["source"] == "probe"
    # Second call reads the ledger record back instead of re-probing.
    monkeypatch.setattr(attrib, "measure_roofline",
                        lambda reps=3: pytest.fail("re-probed a fresh "
                                                   "calibration"))
    second = attrib.get_roofline(ledger)
    assert second["source"] == "ledger"
    assert second["triad_gbps"] == 12.5
    # A stale record re-probes.
    monkeypatch.setenv("RS_ROOFLINE_MAX_AGE_S", "0")
    monkeypatch.setattr(
        attrib, "measure_roofline",
        lambda reps=3: {"triad_gbps": 1.0, "gemm_gflops": 2.0,
                        "ts": time.time(), "host": __import__(
                            "socket").gethostname()},
    )
    third = attrib.get_roofline(ledger)
    assert third["source"] == "probe" and third["triad_gbps"] == 1.0


def test_roofline_records_do_not_pollute_history(tmp_path, monkeypatch):
    """Calibration records are not runs: filter_records must drop them
    (else repeated analyze runs displace real measurements from the
    --regress window)."""
    from gpu_rscode_tpu.obs import runlog

    ledger = str(tmp_path / "ledger.jsonl")
    monkeypatch.setenv("RS_RUNLOG", ledger)
    runlog.record({"op": "encode", "bytes": 1000, "wall_s": 0.5,
                   "outcome": "ok", "config": {}})
    runlog.append({"kind": "rs_roofline", "host": "h", "ts": time.time(),
                   "triad_gbps": 5.0, "gemm_gflops": 50.0}, ledger)
    recs = runlog.filter_records(runlog.read_records(ledger))
    assert len(recs) == 1 and recs[0]["op"] == "encode"


def test_classify_bound():
    assert attrib.classify_bound(0.8, 0.1) == "memory"
    assert attrib.classify_bound(0.1, 0.8) == "compute"
    assert attrib.classify_bound(0.05, 0.08) == "dispatch"


# ----- rs analyze -----------------------------------------------------------


@pytest.fixture(scope="module")
def analyze_report(tmp_path_factory):
    """One shared `rs analyze --json` run (the expensive fixture): tiny
    workload, all three required strategies, CPU backend."""
    metrics.REGISTRY.reset()
    out = []
    import contextlib
    import io

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = cli.main([
            "analyze", "--json", "--size-kb", "64", "--segment-kb", "16",
        ])
    out.append(rc)
    metrics.force_enable(False)
    report = json.loads(buf.getvalue())
    return rc, report


def test_analyze_json_schema_and_strategies(analyze_report):
    rc, report = analyze_report
    assert rc == 0
    assert report["kind"] == "rs_analyze" and report["schema"] == 1
    assert {"roofline", "strategies", "latency", "config",
            "backend"} <= set(report)
    rows = {(r["strategy"], r["op"]) for r in report["strategies"]}
    # The acceptance surface: table/bitplane/xor/native, encode + decode
    # (xor joined the default roofline workload with ISSUE 11).
    for s in ("table", "bitplane", "xor", "native"):
        assert (s, "encode") in rows and (s, "decode") in rows
    for r in report["strategies"]:
        assert r["achieved_gbps"] > 0
        assert r["arithmetic_intensity"] > 0
        assert r["bound"] in ("memory", "compute", "dispatch")
        assert r["cost_source"] in ("xla_cost_analysis", "analytic")
    # The native host codec has no XLA executable: always analytic.
    native_rows = [r for r in report["strategies"]
                   if r["strategy"] == "native"]
    assert all(r["cost_source"] == "analytic" for r in native_rows)


def test_analyze_reports_dispatch_and_file_op_percentiles(analyze_report):
    _, report = analyze_report
    lat = report["latency"]
    assert "rs_dispatch_wall_seconds" in lat
    assert "rs_file_op_wall_seconds" in lat
    series = next(iter(lat["rs_dispatch_wall_seconds"].values()))
    assert series["count"] > 0
    assert series["0.5"] is not None and series["0.99"] is not None
    assert series["max"] >= series["0.5"]


def test_analyze_rejects_unknown_strategy(capsys):
    assert cli.main(["analyze", "--strategies", "warp"]) == 2
    assert "unknown strategies" in capsys.readouterr().err


# ----- rs doctor ------------------------------------------------------------


def test_doctor_json_schema_stability(capsys):
    rc = cli.main(["doctor", "--json", "--no-probe"])
    assert rc == 0
    report = json.loads(capsys.readouterr().out)
    assert report["kind"] == "rs_doctor" and report["schema"] == 1
    # The stable section surface fleet tooling may depend on.
    for section in doctor.SECTIONS:
        assert section in report, f"doctor --json lost section {section!r}"
    assert isinstance(report["warnings"], list)
    assert report["jax"]["importable"] is True
    assert report["jax"]["backend"] == "cpu"
    assert isinstance(report["env"], dict)


def test_doctor_human_output_runs(capsys):
    assert cli.main(["doctor", "--no-probe"]) == 0
    out = capsys.readouterr().out
    assert "rs doctor @" in out and "jax" in out


def test_doctor_no_probe_does_not_claim_outage(capsys, monkeypatch):
    """--no-probe skips the endpoint check; an untested endpoint must
    render as 'not probed', never as UNREACHABLE."""
    monkeypatch.setenv("RS_METRICS_PORT", "9464")
    assert cli.main(["doctor", "--no-probe"]) == 0
    out = capsys.readouterr().out
    assert "not probed" in out and "UNREACHABLE" not in out


def test_doctor_ledger_and_roofline_sections(tmp_path, monkeypatch):
    ledger = str(tmp_path / "ledger.jsonl")
    monkeypatch.setenv("RS_RUNLOG", ledger)
    from gpu_rscode_tpu.obs import runlog

    runlog.append({"kind": "rs_roofline", "host": __import__(
        "socket").gethostname(), "ts": time.time(),
        "triad_gbps": 5.0, "gemm_gflops": 50.0})
    report = doctor.collect(probe_endpoint=False)
    assert report["ledger"]["path"] == ledger
    assert report["ledger"]["writable"] is True
    assert report["ledger"]["records"] == 1
    assert report["roofline"]["cached"] is True
    assert report["roofline"]["fresh"] is True
    assert report["roofline"]["triad_gbps"] == 5.0


# ----- disabled-path guard (tier-1) -----------------------------------------


def test_disabled_attribution_path_registers_nothing(tmp_path,
                                                     clean_registry,
                                                     monkeypatch):
    """Mirror of test_disabled_fault_plane_is_noop for the attribution
    layer: with RS_METRICS and RS_PROFILE unset, an encode must register
    no quantile series, no memory gauges, no collective counters — and
    the quantile accessor must hand back the shared NULL."""
    monkeypatch.delenv("RS_METRICS", raising=False)
    monkeypatch.delenv("RS_PROFILE", raising=False)
    assert metrics.quantile("anything") is metrics.NULL
    path = _mkfile(tmp_path, 40_000)
    api.encode_file(path, 4, 2, segment_bytes=8192)
    assert metrics.REGISTRY.snapshot() == {}, (
        "disabled-attribution encode registered metrics — the new "
        "instrumentation leaked past the RS_METRICS gate"
    )
    # And sampling device memory directly is a no-op while disabled.
    attrib.sample_device_memory()
    assert metrics.REGISTRY.snapshot() == {}


def test_profile_env_wraps_file_op(tmp_path, monkeypatch):
    """RS_PROFILE=<dir> captures a jax.profiler trace around a library
    call — no CLI involved (the lifted satellite surface) — and the
    capture carries the program's spans, the sink off again after."""
    from gpu_rscode_tpu.obs import tracing

    from test_obs import xplane_spans

    prof = tmp_path / "prof"
    monkeypatch.setenv("RS_PROFILE", str(prof))
    path = _mkfile(tmp_path, 40_000)
    api.encode_file(path, 4, 2, segment_bytes=8192)
    assert prof.exists() and any(prof.rglob("*")), (
        "RS_PROFILE set but no jax.profiler capture landed"
    )
    names = {n for n, _ in xplane_spans(prof)}
    assert {"rs.encode", "rs.dispatch", "rs.write_drain",
            "rs.encode compute"} <= names
    assert not tracing.enabled()


def test_profile_override_cleared_by_cli(tmp_path):
    """--profile-dir (the deprecated alias) latches and clears the
    override around the run: later in-process calls must not profile."""
    path = _mkfile(tmp_path, 40_000)
    prof = tmp_path / "prof"
    rc = cli.main([
        "-k", "2", "-n", "4", "-e", path, "--quiet",
        "--profile-dir", str(prof),
    ])
    assert rc == 0
    assert prof.exists() and any(prof.rglob("*"))
    assert api._PROFILE_DIR_OVERRIDE is None


# ----- quantile estimator unit coverage -------------------------------------


def test_quantile_estimator_exact_below_cap():
    est = percentile.QuantileEstimator(cap=128)
    vals = list(range(100))
    for v in vals:
        est.observe(v)
    assert est.count == 100 and est.min == 0 and est.max == 99
    assert est.quantile(0.5) == pytest.approx(49.5)
    assert est.quantile(1.0) == 99


def test_quantile_estimator_bounded_and_deterministic():
    a = percentile.QuantileEstimator(cap=64)
    b = percentile.QuantileEstimator(cap=64)
    for i in range(10_000):
        a.observe(i % 977)
        b.observe(i % 977)
    assert len(a.reservoir) == 64
    assert a.reservoir == b.reservoir  # seeded: same stream, same state
    assert a.max == 976 and a.min == 0  # exact extremes, never sampled


def test_quantile_registry_type_conflict():
    reg = metrics.Registry()
    reg.quantile("q", cap=32)
    with pytest.raises(ValueError):
        reg.quantile("q", cap=64)
    with pytest.raises(TypeError):
        reg.counter("q")
