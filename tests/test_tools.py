"""Smoke tests for the benchmark/probe tools' CLI surfaces.

Each tool is hardware-oriented (real verdicts come from TPU captures), but
its argument parsing, oracle verification, and jsonl output contract must
not rot between hardware sessions — these run tiny CPU configurations in a
child interpreter (the tools import jax; the suite's conftest already pins
the CPU platform via env inherited by the child).
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_tool(mod: str, *args: str, timeout: int = 240):
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    run = subprocess.run(
        [sys.executable, "-m", mod, *args],
        capture_output=True, text=True, timeout=timeout, cwd=REPO, env=env,
    )
    assert run.returncode == 0, run.stderr[-800:]
    lines = [l for l in run.stdout.splitlines() if l.startswith("{")]
    assert lines, f"no jsonl output from {mod}: {run.stdout[-400:]}"
    rows = [json.loads(l) for l in lines]
    # Capture contract (obs/runlog.capture_header): the FIRST json line of
    # every bench tool is the shared identity header, so bench_captures/
    # files are self-describing and `rs history` can ingest them.
    hdr = rows[0]
    assert hdr.get("kind") == "capture_header", hdr
    assert hdr["tool"] == mod.rsplit(".", 1)[1]
    for field in ("run", "host", "backend", "ts"):
        assert field in hdr, hdr
    return [r for r in rows if r.get("kind") != "capture_header"]


def test_expand_probe_smoke():
    got = _run_tool(
        "gpu_rscode_tpu.tools.expand_probe",
        "--mb", "2", "--trials", "1", "--tile", "2048",
        "--expand", "shift", "packed32", "nibble_const",
    )
    verdicts = {k: v for d in got for k, v in d.items()}
    assert set(verdicts) == {"shift", "packed32", "nibble_const"}
    # On CPU (interpret mode) every formulation runs and verifies — a
    # fail:* verdict here means the formulation itself broke, not Mosaic.
    assert all(isinstance(v, float) for v in verdicts.values()), verdicts


def test_k_sweep_smoke():
    got = _run_tool(
        "gpu_rscode_tpu.tools.k_sweep",
        "--mb", "2", "--trials", "1", "--ks", "4", "--tiles", "2048",
    )
    verdicts = {k: v for d in got for k, v in d.items()}
    assert "k4_acc-int8@2048" in verdicts
    assert verdicts["k4_best"]["contraction_depth"] == 32


def test_inverse_bench_smoke():
    got = _run_tool(
        "gpu_rscode_tpu.tools.inverse_bench",
        "--batch", "16", "--k", "8", "--trials", "1",
    )
    row = got[0]
    assert row["k"] == 8 and row["batch"] == 16
    assert row["invertible"] > 0 and row["device_dispatch_s"] > 0
    # Round 5: the scan-free variant is measured alongside, and must agree
    # with the pivoting dispatch wherever it claims success.
    assert row["nopivot_dispatch_s"] > 0 and row["nopivot_ok"] > 0


def test_io_bench_smoke():
    import pytest

    from gpu_rscode_tpu import native

    if not native.available():
        pytest.skip("native library unavailable (no C++ toolchain)")
    got = _run_tool(
        "gpu_rscode_tpu.tools.io_bench", "--mb", "64", "--trials", "1",
        "--dir", "/tmp",
    )
    calls = {d["call"] for d in got}
    assert calls == {"stripe_read", "scatter_write", "gather_rows"}
    assert all(d["serial"] > 0 and d["threads8"] > 0 for d in got)


def test_mesh_bench_smoke():
    got = _run_tool(
        "gpu_rscode_tpu.tools.mesh_bench", "--mb", "2", "--trials", "1",
    )
    summary = got[-1]
    res = summary["results"]
    # On the CPU mesh every mode runs interpret/XLA and must bit-verify.
    assert all(isinstance(res[m], float) for m in
               ("cols_pallas", "stripe_pallas", "cols_bitplane")), res


def test_mesh_overhead_smoke():
    got = _run_tool(
        "gpu_rscode_tpu.tools.mesh_overhead",
        "--mb", "1", "2", "--trials", "1", timeout=360,
    )
    modes = {d["mode"] for d in got if "devices" in d}
    assert modes == {"single", "cols", "stripe"}
    ratios = [d for d in got if "overhead_vs_single" in d]
    assert {d["mode"] for d in ratios} == {"cols", "stripe"}



def test_encode_split_smoke():
    """Per-op rows of the encode split: two lane tasks a segment (native
    rows, then parity), the lane's parts adding up to its busy time, and
    the renames timed; the disk probe's rows before them."""
    got = _run_tool(
        "gpu_rscode_tpu.tools.encode_split", "--bytes", "600001",
        "--ops", "2", "--disk", "1", "--k", "4", "--p", "2",
    )
    parts = [d["part"] for d in got]
    assert parts == ["disk", "setup", "encode", "encode"]
    for row in got[2:]:
        assert row["tasks"] == 2  # one 150,001-column segment
        lane = sum(row[k] for k in ("crc_s", "natives_s", "parity_s",
                                    "d2h_s", "task_other_s"))
        assert lane == pytest.approx(row["lane_s"], abs=1e-5)
        assert row["crc_s"] > 0 and row["natives_s"] > 0
        assert 0 < row["promote_s"] <= row["tail_s"] <= row["op_s"]


def test_encode_split_counts_each_lane_second_once():
    """A ``crc`` span inside a native copy (as the lane's tasks once
    nested them) counts as CRC, not twice; time outside the lane's tasks
    counts in no part of it."""
    from gpu_rscode_tpu.tools.encode_split import split

    def x(name, ts, dur):
        return {"ph": "X", "name": name, "ts": ts * 1e6, "dur": dur * 1e6}

    row = split([
        x("encode", 0.0, 10.0),
        x("write_drain", 1.0, 4.0), x("write natives (io)", 1.0, 3.0),
        x("crc", 2.0, 1.0),
        x("write_drain", 6.0, 2.0), x("encode compute", 6.0, 0.5),
        x("write parity (io)", 6.5, 1.0),
        x("stage segment (io)", 0.0, 2.0), x("promote chunks (io)", 8.5, 1.0),
    ])
    assert row == {
        "op_s": 10.0, "tasks": 2, "lane_s": 6.0, "head_s": 1.0,
        "tail_s": 2.0, "lane_gap_s": 1.0, "crc_s": 1.0, "natives_s": 2.0,
        "parity_s": 1.0, "d2h_s": 0.5, "task_other_s": 1.5,
        "promote_s": 1.0, "gather_s": 2.0, "wait_writer_s": 0,
        "wait_segment_s": 0,
    }
