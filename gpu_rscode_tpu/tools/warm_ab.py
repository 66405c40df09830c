"""``tools/warm_ab.py --ab`` — cross-process warm-start A/B for the
persistent schedule/autotune store (docs/XOR.md "The persistent store").

The warm-path tax this measures: a FRESH process (CLI invocation,
restarted ``rs serve`` daemon) used to pay the full strategy-autotune
candidate sweep (``RS_STRATEGY_AUTOTUNE=measure``: seconds per class)
and a fresh Paar-CSE schedule build per coefficient matrix, because both
decisions died with the process.  With the store, process one persists
``rs_autotune`` + ``rs_xor_schedule`` records into the run ledger and
process two resolves/loads instead of re-probing/re-scheduling.

A/B discipline: every trial spawns REAL subprocesses (the unit of the
claim is a fresh process, so in-process timing would be meaningless):

* **cold** — store disabled (``RS_SCHEDULE_STORE=0``),
  ``RS_STRATEGY_AUTOTUNE=measure``: first ``strategy="auto"`` encode
  pays the candidate sweep; the schedule build runs the real Paar pass.
* **warm** — store pointed at a ledger a seeder process (same config,
  measure mode) populated once: ``auto`` resolves ``source="ledger"``
  with zero probing, and schedule builds load from the store (the child
  reports ``store.built`` — the validator asserts it is ZERO).

Per-child measurements: wall of the first ``auto`` encode (the
first-op latency a daemon restart or CLI start sees), wall of a
decode-matrix-sized ``build_schedule`` (the Paar vs store-load
comparison isolated from XLA compile noise), and the store/decision
stats.  Captures join ``bench_captures/`` via the shared
``capture_header``.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time

_CHILD = r"""
import json, os, sys, time

root, work, k, p, w, size = (
    sys.argv[1], sys.argv[2], int(sys.argv[3]), int(sys.argv[4]),
    int(sys.argv[5]), int(sys.argv[6]),
)
sys.path.insert(0, root)
import jax
import numpy as np

from gpu_rscode_tpu import api, tune
from gpu_rscode_tpu.ops import xor_gemm
from gpu_rscode_tpu.ops.gf import get_field

tag = f"{os.getpid()}"
payload = os.path.join(work, f"payload_{tag}.bin")
rng = np.random.default_rng(20260804)
with open(payload, "wb") as fp:
    fp.write(rng.integers(0, 256, size, dtype=np.uint8).tobytes())

# First-op latency: what a fresh process pays before its first auto
# encode completes (measure mode: the candidate sweep; ledger mode:
# nothing but the encode itself).
t0 = time.perf_counter()
api.encode_file(payload, k, p, w=w, strategy="auto")
first = time.perf_counter() - t0

# Decode-matrix-sized schedule build (seeded -> same digest in every
# process): cold runs the real Paar pass, warm loads from the store.
# 24x24 dense random sits well inside RS_XOR_MAX_TERMS at w=8/16 while
# still costing a measurable Paar pass.
gf = get_field(w)
mrng = np.random.default_rng(20260805)
M = mrng.integers(1, gf.size, size=(24, 24)).astype(gf.dtype)
t1 = time.perf_counter()
sched = xor_gemm.build_schedule(M, w)
sched_wall = time.perf_counter() - t1

decisions = tune.decisions()
print(json.dumps({
    "platform": jax.devices()[0].platform,
    "first_op_wall_s": round(first, 6),
    "schedule_wall_s": round(sched_wall, 6),
    "schedule_digest": sched.digest,
    "store": xor_gemm.store_stats(),
    "autotune_sources": sorted({
        d.get("source") or "measured" for d in decisions.values()
    }),
}))
"""


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    )))


def _run_child(work: str, store: str | None, autotune: str, *,
               k: int, p: int, w: int, size: int) -> dict:
    env = dict(os.environ)
    env["RS_STRATEGY_AUTOTUNE"] = autotune
    env.pop("RS_RUNLOG", None)
    env["RS_SCHEDULE_STORE"] = store if store else "0"
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD, _repo_root(), work,
         str(k), str(p), str(w), str(size)],
        capture_output=True, text=True, env=env, timeout=600,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"warm_ab child failed (rc={proc.returncode}):\n{proc.stderr}"
        )
    return json.loads(proc.stdout.strip().splitlines()[-1])


def run_ab(*, k: int, p: int, w: int, size_mb: float, trials: int,
           quiet: bool = False) -> list[dict]:
    import shutil

    size = int(size_mb * 1024 * 1024)
    work = tempfile.mkdtemp(prefix="rs_warm_ab_")
    store = os.path.join(work, "store.jsonl")
    try:
        # Seed the store once: a measure-mode process persists its
        # verdict and schedules — this is "process one" of the claim.
        seed = _run_child(work, store, "measure", k=k, p=p, w=w,
                          size=size)
        cold_first, cold_sched = [], []
        warm_first, warm_sched = [], []
        warm_children = []
        for _ in range(max(1, trials)):
            cold = _run_child(work, None, "measure", k=k, p=p, w=w,
                              size=size)
            warm = _run_child(work, store, "prior", k=k, p=p, w=w,
                              size=size)
            cold_first.append(cold["first_op_wall_s"])
            cold_sched.append(cold["schedule_wall_s"])
            warm_first.append(warm["first_op_wall_s"])
            warm_sched.append(warm["schedule_wall_s"])
            warm_children.append(warm)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    row = {
        "kind": "warm_ab",
        "op": "encode",
        "platform": seed["platform"],
        "config": {"k": k, "n": k + p, "w": w},
        "bytes": size,
        "trials": trials,
        "cold": {
            "first_op_wall_s": cold_first,
            "schedule_wall_s": cold_sched,
        },
        "warm": {
            "first_op_wall_s": warm_first,
            "schedule_wall_s": warm_sched,
        },
        "best_first_op_s": {
            "cold": min(cold_first), "warm": min(warm_first),
        },
        "best_schedule_s": {
            "cold": min(cold_sched), "warm": min(warm_sched),
        },
        "first_op_speedup": round(min(cold_first) / min(warm_first), 3),
        "schedule_speedup": round(
            min(cold_sched) / max(min(warm_sched), 1e-9), 3
        ),
        # The contract bits the CI validator gates on: a warm process
        # must BUILD no schedules (loads only) and must resolve auto
        # from the ledger, not a probe.
        "warm_schedule_builds": max(
            c["store"]["built"] for c in warm_children
        ),
        "warm_autotune_sources": sorted({
            s for c in warm_children for s in c["autotune_sources"]
        }),
        "seed_store_entries": seed["store"]["stored"],
    }
    if not quiet:
        print(
            f"warm_ab: k={k} p={p} w={w}: first-op "
            f"{row['best_first_op_s']['cold']:.3f}s cold -> "
            f"{row['best_first_op_s']['warm']:.3f}s warm "
            f"({row['first_op_speedup']}x); schedule "
            f"{row['best_schedule_s']['cold'] * 1e3:.1f}ms -> "
            f"{row['best_schedule_s']['warm'] * 1e3:.1f}ms "
            f"({row['schedule_speedup']}x); warm builds: "
            f"{row['warm_schedule_builds']}",
            file=sys.stderr,
        )
    return [row]


def main(argv=None) -> int:
    import argparse

    from ..obs import runlog as _runlog

    ap = argparse.ArgumentParser(
        prog="warm_ab",
        description="Cross-process warm-start A/B: persistent "
        "schedule/autotune store on vs off, real subprocesses per arm "
        "(docs/XOR.md).",
    )
    ap.add_argument("--ab", action="store_true",
                    help="run the A/B comparison (the only mode)")
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--p", type=int, default=4)
    ap.add_argument("--w", type=int, default=8, choices=(8, 16))
    ap.add_argument("--size-mb", type=float, default=4.0,
                    help="encode payload in MiB (default 4)")
    ap.add_argument("--trials", type=int, default=3)
    ap.add_argument("--capture", default=None,
                    help="capture JSONL path (default bench_captures/"
                    "warm_ab_<backend>_<ts>.jsonl; '-' disables)")
    ap.add_argument("--json", action="store_true")
    try:
        args = ap.parse_args(argv)
    except SystemExit as e:
        return int(e.code or 0)
    if not args.ab:
        print("warm_ab: pass --ab (the A/B comparison is the bench)",
              file=sys.stderr)
        return 2
    rows = run_ab(k=args.k, p=args.p, w=args.w, size_mb=args.size_mb,
                  trials=args.trials, quiet=args.json)
    capture = args.capture
    if capture is None:
        os.makedirs("bench_captures", exist_ok=True)
        stamp = time.strftime("%Y%m%dT%H%M%SZ", time.gmtime())
        # The parent never loads jax (the children are the measurement,
        # one at a time on the parent's platform): name the series by
        # the platform the children reported.
        capture = os.path.join(
            "bench_captures",
            f"warm_ab_{rows[0]['platform']}_{stamp}.jsonl",
        )
    if capture != "-":
        with open(capture, "w") as fp:
            fp.write(json.dumps(_runlog.capture_header("warm_ab")) + "\n")
            for row in rows:
                fp.write(json.dumps(row) + "\n")
        print(f"warm_ab: capture -> {capture}", file=sys.stderr)
    if args.json:
        print(json.dumps({"rows": rows, "capture": capture}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
