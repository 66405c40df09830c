"""Unified observability layer: registry, tracer, wiring, CLI surfaces.

Covers the ISSUE contracts: registry thread-safety under concurrent
increments, histogram bucket edges, valid Chrome-trace export with paired
(complete "X") events per dispatched segment, the metrics-disabled path
registering NOTHING (the tier-1 guard against accidental always-on
instrumentation in the hot loop), and the ``rs stats`` / ``--metrics-json``
round-trip whose snapshot matches the run it instrumented.
"""

import glob
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from gpu_rscode_tpu import api, plan
from gpu_rscode_tpu.obs import metrics, tracing
from gpu_rscode_tpu.tools.make_conf import make_conf
from gpu_rscode_tpu.utils.fileformat import chunk_file_name
from gpu_rscode_tpu.utils.timing import PhaseTimer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def clean_registry():
    metrics.REGISTRY.reset()
    yield metrics.REGISTRY
    metrics.force_enable(False)
    metrics.REGISTRY.reset()


def _mkfile(tmp_path, size, seed=0, name="f.bin"):
    path = str(tmp_path / name)
    rng = np.random.default_rng(seed)
    open(path, "wb").write(
        rng.integers(0, 256, size=size, dtype=np.uint8).tobytes()
    )
    return path


# ----- registry -------------------------------------------------------------


def test_counter_thread_safety(clean_registry, monkeypatch):
    """Concurrent increments on the same labeled child must not lose
    updates (the registry serves every pipeline thread at once)."""
    monkeypatch.setenv("RS_METRICS", "1")
    c = metrics.counter("t_concurrent", "test")
    child = c.labels(op="x")
    N, M = 8, 2000

    def work():
        for _ in range(M):
            child.inc()
            c.inc(2)  # default child, same lock

    threads = [threading.Thread(target=work) for _ in range(N)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert child.value == N * M
    assert c.value == 2 * N * M


def test_histogram_bucket_edges(clean_registry, monkeypatch):
    """Prometheus ``le`` semantics: an observation equal to a bucket edge
    lands IN that bucket; cumulative counts include every lower bucket."""
    monkeypatch.setenv("RS_METRICS", "1")
    h = metrics.histogram("t_hist", "test", buckets=(0.001, 0.01, 0.1))
    for v in (0.001, 0.0005, 0.01, 0.05, 99.0):
        h.observe(v)
    child = h.labels()
    cum = child.cumulative()
    assert cum["0.001"] == 2      # 0.0005 and the edge value 0.001
    assert cum["0.01"] == 3
    assert cum["0.1"] == 4
    assert cum["+Inf"] == 5       # 99.0 overflows to +Inf only
    assert child.count == 5 and child.sum == pytest.approx(99.0615)
    snap = metrics.REGISTRY.snapshot()["t_hist"]
    assert snap["type"] == "histogram"
    assert snap["values"][""]["buckets"]["+Inf"] == 5


def test_gauge_and_type_conflict(clean_registry, monkeypatch):
    monkeypatch.setenv("RS_METRICS", "1")
    g = metrics.gauge("t_gauge", "test")
    g.set(3)
    g.inc()
    g.dec(2)
    assert g.value == 2
    with pytest.raises(TypeError):
        metrics.REGISTRY.counter("t_gauge")
    # Conflicting bucket edges on one histogram name are an error too —
    # silently reusing the first caller's edges would corrupt series.
    metrics.REGISTRY.histogram("t_hbuck", buckets=(1.0, 2.0))
    with pytest.raises(ValueError):
        metrics.REGISTRY.histogram("t_hbuck", buckets=(0.5,))


def test_render_text_exposition(clean_registry, monkeypatch):
    monkeypatch.setenv("RS_METRICS", "1")
    metrics.counter("t_c", "helpline").labels(op="e").inc(3)
    metrics.histogram("t_h", buckets=(1.0,)).observe(0.5)
    text = metrics.REGISTRY.render_text()
    assert "# HELP t_c helpline" in text
    assert "# TYPE t_c counter" in text
    assert 't_c{op="e"} 3' in text
    assert 't_h_bucket{le="1.0"} 1' in text
    assert "t_h_count 1" in text


def test_disabled_returns_null_and_registers_nothing(clean_registry,
                                                     monkeypatch):
    monkeypatch.delenv("RS_METRICS", raising=False)
    c = metrics.counter("t_never", "test")
    assert c is metrics.NULL
    c.labels(op="x").inc()
    c.observe(1.0)  # NULL absorbs every metric verb
    assert metrics.REGISTRY.snapshot() == {}


# ----- tracer ---------------------------------------------------------------


def test_trace_export_is_valid_chrome_trace(tmp_path):
    """Export loads as JSON; spans are complete ("X") events with ts+dur;
    nested spans on one lane are properly contained; lanes get
    thread_name metadata."""
    out = str(tmp_path / "t.json")
    with tracing.session(out) as t:
        assert tracing.active() is t
        with tracing.span("outer", lane="work", step=1):
            time.sleep(0.002)
            with tracing.span("inner", lane="work"):
                time.sleep(0.001)
        tracing.instant("marker", lane="work")
        tracing.counter("occupancy", staged=2)
    assert tracing.active() is None
    trace = json.load(open(out))
    evs = trace["traceEvents"]
    by_name = {e["name"]: e for e in evs}
    outer, inner = by_name["outer"], by_name["inner"]
    assert outer["ph"] == inner["ph"] == "X"
    assert outer["tid"] == inner["tid"]  # same lane
    # paired/nested: inner lies within outer's [ts, ts+dur]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6
    assert outer["args"] == {"step": 1}
    assert by_name["marker"]["ph"] == "i"
    assert by_name["occupancy"]["ph"] == "C"
    lanes = [
        e["args"]["name"] for e in evs
        if e["ph"] == "M" and e["name"] == "thread_name"
    ]
    assert "work" in lanes


def test_session_reentrant_and_env(tmp_path, monkeypatch):
    """RS_TRACE activates a session; an inner session joins the outer one
    (one coherent trace, the outer owns the export)."""
    out = str(tmp_path / "env.json")
    monkeypatch.setenv("RS_TRACE", out)
    with tracing.session() as t:
        with tracing.session("/nonexistent/ignored.json") as t2:
            assert t2 is t  # joined, not replaced
            with tracing.span("inner_op"):
                pass
    trace = json.load(open(out))
    assert any(e["name"] == "inner_op" for e in trace["traceEvents"])
    assert tracing.active() is None


def test_span_noop_without_session():
    with tracing.span("nothing", lane="x", a=1):
        pass
    tracing.instant("nothing")
    tracing.counter("nothing", v=1)
    assert tracing.active() is None


def test_trace_export_survives_numpy_span_args(tmp_path):
    """Caller-supplied span args may be numpy scalars; export degrades
    them to strings instead of losing the trace (and leaves no .rs_tmp
    behind on any path)."""
    out = str(tmp_path / "np.json")
    with tracing.session(out):
        with tracing.span("seg", lane="x", cols=np.int64(512),
                          frac=np.float32(0.5)):
            pass
    trace = json.load(open(out))
    ev = next(e for e in trace["traceEvents"] if e["name"] == "seg")
    assert ev["args"]["cols"] == "512"
    assert not (tmp_path / "np.json.rs_tmp").exists()


# ----- the profiler sink ----------------------------------------------------


def xplane_spans(log_dir) -> list[tuple[str, float]]:
    """(name, seconds) of every program span (``rs.``) in the one
    jax.profiler capture under ``log_dir``."""
    from jax.profiler import ProfileData

    [path] = glob.glob(os.path.join(
        str(log_dir), "plugins", "profile", "*", "*.xplane.pb"))
    return [(e.name, e.duration_ns / 1e9)
            for plane in ProfileData.from_file(path).planes
            if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events
            if e.name.startswith(tracing.PROFILER_PREFIX)]


@pytest.fixture
def profiler_sink():
    was = tracing.profiler_sink(True)
    yield
    tracing.profiler_sink(was)


def test_span_off_path_builds_no_annotation(monkeypatch):
    """Both sinks off: span() hands back the shared null context and never
    constructs a TraceAnnotation, from the phase timer's gate either."""
    import jax.profiler

    def boom(*a, **kw):
        raise AssertionError("a TraceAnnotation was built with tracing off")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom)
    assert not tracing.enabled() and tracing.active() is None
    assert tracing.span("dispatch", lane="dispatch", off=1) is tracing._NULL_CM
    with PhaseTimer(enabled=False).phase("encode compute"):
        pass


def test_tracing_imports_no_jax():
    code = ("import sys, gpu_rscode_tpu.obs.tracing; "
            "print('jax' in sys.modules)")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, timeout=120,
                         capture_output=True, text=True, check=True)
    assert out.stdout.strip() == "False"


def test_both_sinks_record_the_same_span(tmp_path, profiler_sink):
    import jax

    out = str(tmp_path / "t.json")
    with jax.profiler.trace(str(tmp_path / "prof")):
        with tracing.session(out):
            with tracing.span("outer", lane="work", step=1):
                with PhaseTimer(enabled=False).phase("inner (io)"):
                    time.sleep(0.002)
    names = [n for n, _ in xplane_spans(tmp_path / "prof")]
    assert names.count("rs.outer") == 1 and "rs.inner (io)" in names
    evs = json.load(open(out))["traceEvents"]
    assert {"outer", "inner (io)"} <= {e["name"] for e in evs}
    tracing.profiler_sink(False)
    assert not tracing.enabled()


def test_file_path_spans_reach_the_profiler(tmp_path, clean_registry,
                                            monkeypatch, profiler_sink):
    """A small checksummed encode and a repair under jax.profiler leave
    the file path's spans in the capture, each once per unit of work, and
    a one-deep writer queue behind slow writes makes the dispatch thread
    wait: its wait spans and rs_pipeline_wait_seconds_total agree."""
    import jax

    monkeypatch.setenv("RS_METRICS", "1")
    monkeypatch.setenv("RS_IO_WRITE_DEPTH", "1")
    monkeypatch.setenv("RS_FAULTS", "write:delay@ms=20")
    monkeypatch.delenv("RS_TRACE", raising=False)
    k, chunk = 4, 3000  # 1024-column segments: 3 per chunk
    path = _mkfile(tmp_path, k * chunk)
    with jax.profiler.trace(str(tmp_path / "prof")):
        api.encode_file(path, k, 2, segment_bytes=4096, checksums=True)
        os.remove(chunk_file_name(path, 1))
        assert api.repair_file(path, segment_bytes=4096) == [1]
    spans = xplane_spans(tmp_path / "prof")
    names = [n for n, _ in spans]
    for once in ("rs.encode", "rs.repair", "rs.scan_chunks"):
        assert names.count(once) == 1, once
    assert names.count("rs.dispatch") == 6  # 3 segments, each op
    # Per segment, an encode drains its native rows and then its parity,
    # each with a CRC span; a repair drains its rebuilt row.
    assert names.count("rs.write_drain") == 2 * 3 + 3
    assert names.count("rs.write natives (io)") == 3
    assert names.count("rs.crc") == 2 * 3 + 3
    assert "rs.wait_writer" in names
    waited = sum(d for n, d in spans if n.startswith("rs.wait_"))
    snap = metrics.REGISTRY.snapshot()
    counted = snap["rs_pipeline_wait_seconds_total"]["values"]
    assert counted['{wait="writer"}'] >= 0.02
    assert sum(counted.values()) == pytest.approx(waited, rel=0.05)
    # The repair's scan, timed for operators as well as spanned.
    scanned = sum(d for n, d in spans if n == "rs.scan_chunks")
    assert snap["rs_scrub_scan_seconds_total"]["values"][""] == \
        pytest.approx(scanned, rel=0.2, abs=0.002)


# ----- wiring: a traced + metered encode ------------------------------------


def test_encode_trace_has_paired_events_per_segment(tmp_path, clean_registry,
                                                    monkeypatch):
    """ISSUE acceptance: RS_TRACE on an encode produces a file that
    json.loads and contains a complete ("X") dispatch event for EVERY
    dispatched segment, plus H2D-stage spans; the metrics snapshot's
    segment counts match the same run."""
    monkeypatch.setenv("RS_METRICS", "1")
    trace_path = str(tmp_path / "enc.json")
    monkeypatch.setenv("RS_TRACE", trace_path)
    plan.PLAN_CACHE.clear()
    k, seg_bytes = 4, 4096  # seg_cols 1024
    chunk = 2 * 1024 + 700  # 2 full segments + 1 tail each
    path = _mkfile(tmp_path, k * chunk)
    api.encode_file(path, k, 2, segment_bytes=seg_bytes)
    n_segments = len(api._segment_spans(chunk, 1024))

    trace = json.load(open(trace_path))
    disp = [
        e for e in trace["traceEvents"]
        if e["name"] == "dispatch" and e["ph"] == "X"
    ]
    assert len(disp) == n_segments
    assert all("dur" in e and e["args"]["op"] == "encode" for e in disp)
    offs = sorted(e["args"]["off"] for e in disp)
    assert offs == sorted(off for off, _ in api._segment_spans(chunk, 1024))
    stages = [
        e for e in trace["traceEvents"]
        if e["name"] == "h2d_stage" and e["ph"] == "X"
    ]
    assert len(stages) == n_segments

    snap = metrics.unified_snapshot()
    seg_values = snap["metrics"]["segments_dispatched"]["values"]
    assert sum(seg_values.values()) == n_segments
    assert snap["metrics"]["rs_segments_staged_total"]["values"][""] == (
        n_segments
    )
    # plan-cache behaviour is part of the same snapshot
    assert snap["plan_cache"]["misses"] >= 1
    assert snap["plan_cache"]["hits"] >= n_segments - 2
    assert snap["metrics"]["rs_file_ops_total"]["values"]['{op="encode"}'] == 1


def test_trace_path_api_option(tmp_path):
    """The explicit trace_path= keyword works without RS_TRACE."""
    path = _mkfile(tmp_path, 5000)
    out = str(tmp_path / "api.json")
    api.encode_file(path, 4, 2, trace_path=out)
    trace = json.load(open(out))
    assert any(e["name"] == "encode" for e in trace["traceEvents"])
    conf = make_conf(6, 4, path)
    dec_trace = str(tmp_path / "dec.json")
    dec_out = str(tmp_path / "out.bin")
    api.decode_file(path, conf, dec_out, trace_path=dec_trace)
    assert any(
        e["name"] == "decode"
        for e in json.load(open(dec_trace))["traceEvents"]
    )


def test_metrics_disabled_path_registers_nothing(tmp_path, clean_registry,
                                                 monkeypatch):
    """The tier-1 guard against accidental always-on instrumentation: an
    encode with RS_METRICS unset must leave the registry EMPTY and record
    no trace events, and the disabled instrumentation seam must stay
    within noise of a no-op timer call."""
    monkeypatch.delenv("RS_METRICS", raising=False)
    monkeypatch.delenv("RS_TRACE", raising=False)
    path = _mkfile(tmp_path, 20_000)
    api.encode_file(path, 4, 2, segment_bytes=4096)
    assert metrics.REGISTRY.snapshot() == {}, (
        "disabled-metrics encode registered metrics — instrumentation "
        "leaked past the RS_METRICS gate"
    )
    assert tracing.active() is None

    # Timing half: the per-event disabled seam (counter lookup + labels +
    # inc, and a span context) against a bare no-op timer.  Bound is
    # generous (CI noise) but far below what real registration/recording
    # costs at volume — an always-on path also fails the snapshot check
    # above, which is the authoritative guard.
    timer = PhaseTimer(enabled=False)

    def noop_baseline(n):
        t0 = time.perf_counter()
        for _ in range(n):
            with timer.phase("x"):
                pass
        return time.perf_counter() - t0

    def disabled_seam(n):
        t0 = time.perf_counter()
        for _ in range(n):
            metrics.counter("t_hot").labels(op="e").inc()
            with tracing.span("x", lane="hot"):
                pass
        return time.perf_counter() - t0

    n = 5000
    noop_baseline(n), disabled_seam(n)  # warm both paths
    base = min(noop_baseline(n) for _ in range(3))
    seam = min(disabled_seam(n) for _ in range(3))
    per_op = seam / n
    assert per_op < 50e-6, f"disabled seam costs {per_op * 1e6:.1f}us/op"
    assert seam < max(20 * base, 25e-3), (seam, base)
    assert metrics.REGISTRY.snapshot() == {}


# ----- PhaseTimer satellites ------------------------------------------------


def test_phase_timer_add_respects_enabled():
    t = PhaseTimer(enabled=False)
    t.add("x", 1.0)
    assert not t.acc and not t.counts and not t.best


def test_phase_timer_add_accumulates_and_tracks_best():
    t = PhaseTimer()
    t.add("x", 2.0)
    t.add("x", 0.5)
    assert t.acc["x"] == 2.5 and t.counts["x"] == 2 and t.best["x"] == 0.5


def test_phase_timer_comm_classification_is_exact():
    """Comm phases are identified by an explicit parenthesized tag, not by
    substring: 'dispatch ratio' / 'prioritize' must NOT count as
    communication even though they contain 'io'."""
    assert PhaseTimer.is_comm("stage segment (io)")
    assert PhaseTimer.is_comm("write parity (io)")
    assert not PhaseTimer.is_comm("encode dispatch")
    assert not PhaseTimer.is_comm("dispatch ratio")      # contains 'io'
    assert not PhaseTimer.is_comm("prioritize buffers")  # contains 'io'
    assert not PhaseTimer.is_comm("verify checksums")
    t = PhaseTimer()
    t.add("stage segment (io)", 1.0)
    t.add("dispatch ratio", 2.0)
    s = t.summary()
    assert "total communication: 1000.000 ms" in s
    assert "total computation: 2000.000 ms" in s


def test_existing_phase_names_classify_exactly():
    """Every phase name the file layer emits keeps its historical
    classification under the tag-set rule."""
    comm = [
        "write natives (io)", "stage segment (io)", "write parity (io)",
        "write metadata (io)", "read metadata (io)", "open chunks (io)",
        "write output (io)", "scan chunks (io)", "write chunks (io)",
        "promote chunks (io)",
    ]
    comp = [
        "encode dispatch", "encode compute", "decode dispatch",
        "decode compute", "repair dispatch", "repair compute",
        "invert matrix", "invert matrices (batched)", "rebuild matrix",
        "verify checksums",
    ]
    for name in comm:
        assert PhaseTimer.is_comm(name), name
    for name in comp:
        assert not PhaseTimer.is_comm(name), name


# ----- CLI surfaces ---------------------------------------------------------


def test_cli_metrics_json_roundtrip(tmp_path, clean_registry, capsys):
    """--metrics-json force-enables collection and dumps a snapshot whose
    plan-cache and segment counters match the run; `rs stats` in the same
    process agrees."""
    from gpu_rscode_tpu.cli import main

    plan.PLAN_CACHE.clear()
    k, seg_bytes = 4, 4096
    chunk = 2 * 1024 + 700
    path = _mkfile(tmp_path, k * chunk)
    mpath = str(tmp_path / "m.json")
    assert main([
        "-k", "4", "-n", "6", "-e", path, "--quiet",
        "--segment-bytes", str(seg_bytes), "--metrics-json", mpath,
    ]) == 0
    snap = json.load(open(mpath))
    n_segments = len(api._segment_spans(chunk, 1024))
    assert snap["metrics_enabled"] is True
    seg_values = snap["metrics"]["segments_dispatched"]["values"]
    assert sum(seg_values.values()) == n_segments
    assert snap["plan_cache"]["hits"] + snap["plan_cache"]["misses"] >= (
        n_segments
    )
    assert snap["plan_cache"]["misses"] >= 1

    capsys.readouterr()
    assert main(["stats"]) == 0
    stats_snap = json.loads(capsys.readouterr().out.strip())
    assert stats_snap["metrics"]["segments_dispatched"]["values"] == (
        seg_values
    )
    assert stats_snap["plan_cache"]["hits"] == snap["plan_cache"]["hits"]


def test_cli_stats_text_exposition(clean_registry, capsys, monkeypatch):
    from gpu_rscode_tpu.cli import main

    monkeypatch.setenv("RS_METRICS", "1")
    metrics.counter("t_cli_text", "h").inc(7)
    assert main(["stats", "--text"]) == 0
    out = capsys.readouterr().out
    assert "t_cli_text 7" in out and "# TYPE t_cli_text counter" in out


def test_cli_stats_usage_error_returns_int(capsys):
    """The stats subcommand keeps the CLI's int-return contract on usage
    errors instead of letting argparse raise SystemExit."""
    from gpu_rscode_tpu.cli import main

    assert main(["stats", "--bogus"]) == 2
    capsys.readouterr()


def test_cli_trace_flag(tmp_path, capsys):
    from gpu_rscode_tpu.cli import main

    path = _mkfile(tmp_path, 5000)
    tpath = str(tmp_path / "cli.json")
    assert main(
        ["-k", "4", "-n", "6", "-e", path, "--quiet", "--trace", tpath]
    ) == 0
    trace = json.load(open(tpath))
    assert any(
        e["name"] == "dispatch" and e["ph"] == "X"
        for e in trace["traceEvents"]
    )


def test_cli_metrics_json_unwritable_path_fails_fast(tmp_path):
    """An unwritable --metrics-json path must be rejected BEFORE the run
    (usage error), not crash with a traceback after minutes of encoding."""
    from gpu_rscode_tpu.cli import main

    path = _mkfile(tmp_path, 1000)
    assert main([
        "-k", "4", "-n", "6", "-e", path, "--quiet",
        "--metrics-json", str(tmp_path / "no_dir" / "m.json"),
    ]) == 2
    # A pure usage error (validated before the probe) creates no file.
    upath = tmp_path / "u.json"
    assert main([
        "-k", "4", "-n", "6", "-e", path, "--quiet", "--stripe", "2",
        "--metrics-json", str(upath),
    ]) == 2
    assert not upath.exists()


def test_cli_metrics_json_written_on_failed_run(tmp_path, clean_registry):
    """A failing operation still dumps the collected snapshot (most
    valuable exactly then) — never a zero-byte probe leftover."""
    from gpu_rscode_tpu.cli import main

    mpath = str(tmp_path / "fail.json")
    assert main([
        "-k", "4", "-n", "6", "-e", str(tmp_path / "missing.bin"),
        "--quiet", "--metrics-json", mpath,
    ]) == 1
    snap = json.load(open(mpath))  # valid JSON, not an empty probe file
    assert snap["metrics_enabled"] is True
    # Same contract on a post-probe USAGE error (missing -n, exit 2).
    mpath2 = str(tmp_path / "usage.json")
    assert main([
        "-k", "4", "-e", str(tmp_path / "x.bin"),
        "--quiet", "--metrics-json", mpath2,
    ]) == 2
    assert json.load(open(mpath2))["metrics_enabled"] is True


def test_trace_export_failure_warns_not_raises(tmp_path):
    """A bad trace path must neither fail a successful file operation nor
    bury a real exception — export errors degrade to a warning."""
    import warnings

    path = _mkfile(tmp_path, 1000)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        files = api.encode_file(
            path, 4, 2, trace_path=str(tmp_path / "no_dir" / "t.json")
        )
    assert files  # the encode itself succeeded
    assert any("trace export" in str(w.message) for w in caught)
    assert tracing.active() is None


def test_staging_ring_occupancy_drains_to_zero(tmp_path, clean_registry,
                                               monkeypatch):
    """The ring gauge must show the tail drain: after the run it reads 0,
    not pinned at depth (the 'did the pipeline stay fed' signal)."""
    monkeypatch.setenv("RS_METRICS", "1")
    path = _mkfile(tmp_path, 20_000)
    api.encode_file(path, 4, 2, segment_bytes=4096)
    snap = metrics.REGISTRY.snapshot()
    assert snap["rs_staging_ring_occupancy"]["values"][""] == 0


def test_cli_scrub_metrics_and_trace(tmp_path, clean_registry, capsys):
    """Scrub rides the same observability surfaces as the data ops (the
    PR-4 lift of the old rejection): --metrics-json dumps a snapshot
    carrying the scrub counters, --trace exports the scan spans."""
    import os

    from gpu_rscode_tpu.cli import main
    from gpu_rscode_tpu.utils.fileformat import chunk_file_name

    path = _mkfile(tmp_path, 9_000)
    assert main(["-k", "4", "-n", "6", "-e", path, "--checksum",
                 "--quiet"]) == 0
    os.unlink(chunk_file_name(path, 2))
    with open(chunk_file_name(path, 3), "r+b") as fp:  # CRC mismatch
        fp.seek(1)
        fp.write(b"\xff")
    mpath, tpath = str(tmp_path / "m.json"), str(tmp_path / "t.json")
    assert main(["--scrub", "-i", path, "--metrics-json", mpath,
                 "--trace", tpath]) == 0  # still decodable -> healthy exit
    capsys.readouterr()
    snap = json.load(open(mpath))
    chunks = snap["metrics"]["rs_scrub_chunks_total"]["values"]
    assert chunks['{state="healthy"}'] == 4
    assert chunks['{state="missing"}'] == 1
    assert chunks['{state="crc_mismatch"}'] == 1
    scanned = snap["metrics"]["rs_scrub_archives_scanned_total"]["values"]
    assert scanned['{outcome="damaged"}'] == 1
    verdicts = snap["metrics"]["rs_scrub_verdicts_total"]["values"]
    assert verdicts['{decodable="True"}'] == 1
    trace = json.load(open(tpath))
    scans = [e for e in trace["traceEvents"]
             if e.get("ph") == "X" and e["name"] == "scan_chunks"]
    assert scans and scans[0]["args"]["file"] == path


def test_repair_outcome_counters(tmp_path, clean_registry, monkeypatch):
    """The scrub/repair loop's verdict series: healthy vs rebuilt archives
    and the rebuilt-chunk volume."""
    import os

    monkeypatch.setenv("RS_METRICS", "1")
    from gpu_rscode_tpu.utils.fileformat import chunk_file_name

    path = _mkfile(tmp_path, 12_000)
    api.encode_file(path, 4, 2, checksums=True)
    assert api.repair_file(path) == []          # healthy pass
    os.unlink(chunk_file_name(path, 0))
    os.unlink(chunk_file_name(path, 4))
    assert sorted(api.repair_file(path)) == [0, 4]
    snap = metrics.REGISTRY.snapshot()
    outcomes = snap["rs_repair_outcomes_total"]["values"]
    assert outcomes['{outcome="healthy"}'] == 1
    assert outcomes['{outcome="rebuilt"}'] == 1
    assert snap["rs_repair_chunks_rebuilt_total"]["values"][""] == 2


def test_cli_repair_metrics_json(tmp_path, clean_registry, capsys):
    """--metrics-json on repair: the snapshot reflects the rebuild run."""
    import os

    from gpu_rscode_tpu.cli import main
    from gpu_rscode_tpu.utils.fileformat import chunk_file_name

    path = _mkfile(tmp_path, 9_000)
    assert main(["-k", "4", "-n", "6", "-e", path, "--quiet"]) == 0
    os.unlink(chunk_file_name(path, 1))
    mpath = str(tmp_path / "rm.json")
    assert main(
        ["--repair", "-i", path, "--quiet", "--metrics-json", mpath]
    ) == 0
    snap = json.load(open(mpath))
    ops = snap["metrics"]["rs_file_ops_total"]["values"]
    assert ops['{op="repair"}'] == 1
    assert any("decode" in k for k in
               snap["metrics"]["segments_dispatched"]["values"])


def test_unified_snapshot_includes_plan_and_autotune(clean_registry):
    snap = metrics.unified_snapshot()
    assert {"metrics", "plan_cache", "mesh_plan_cache",
            "autotune_decisions"} <= set(snap)
    assert "hits" in snap["plan_cache"]
    assert "compile_seconds" in snap["plan_cache"]
    json.dumps(snap)  # must be JSON-serializable end to end
