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

