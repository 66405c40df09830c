"""2-process multi-host integration test (simulated hosts on one machine).

The reference cannot leave one machine (pthread multi-GPU only, SURVEY §2);
the TPU build's multi-host layer (parallel/distributed.py) was previously
only single-process-tested.  This spawns two REAL OS processes, each with 4
virtual CPU devices, wires them with ``jax.distributed`` over a localhost
coordinator, and runs both sharding modes of the GF-GEMM — including the
stripe-axis psum crossing the process boundary (the DCN path).
"""

import os
import socket
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "multihost_worker.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# Passes on jaxlib 0.9.0: the gloo transport crash of the old 0.4.37 pin
# (>~30 KB messages) is gone.
def test_two_process_sharded_gemm(tmp_path):
    port = _free_port()
    procs = []
    for pid in range(2):
        env = {
            # Minimal clean env: CPU backend with 4 virtual devices per
            # "host".
            "PATH": os.environ.get("PATH", ""),
            "HOME": os.environ.get("HOME", "/root"),
            "PYTHONPATH": REPO,
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
            "JAX_COORDINATOR_ADDRESS": f"127.0.0.1:{port}",
            "JAX_NUM_PROCESSES": "2",
            "JAX_PROCESS_ID": str(pid),
            # Shared "filesystem" for the multi-host file-layer encode.
            "RS_MULTIHOST_DIR": str(tmp_path),
        }
        procs.append(
            subprocess.Popen(
                [sys.executable, WORKER],
                env=env,
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
            )
        )
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=240)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.fail(f"multihost workers timed out; partial output: {outs}")
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {i} failed:\n{out}"
        assert "MULTIHOST_OK" in out, f"worker {i} output:\n{out}"
