"""Benchmark — encode GB/s at the BASELINE headline config (k=10, n=14).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "detail"}.
Baseline: the reference's published GPU encode bandwidth, 1356.835 MB/s
(Tesla C2050, design.tex:490; BASELINE.md) == 1.356835 GB/s.

Method: a (k=10, p=4) stripe resident on the device is encoded by each
GEMM strategy (fused Pallas kernel first, then the XOR-lowered, XLA
bit-plane and table paths, segmented to bound HBM); every strategy's
output is verified bit-exact against the native CPU oracle on a sample
before its time counts.  The reported number is the best verified
strategy's steady-state device throughput (file bytes / wall),
comparable to the reference's kernel-bandwidth figure (which likewise
excludes PCIe copies).

Runs in one process and needs a TPU: without one it exits 1 and prints
no metric line.  The cell benchmark (ROADMAP queue 1 item 1) replaces it.
"""

import json
import sys
import time as _time_mod

import numpy as np

_T0 = _time_mod.time()

K, P = 10, 4
BASELINE_GBPS = 1.356835


def _mark(phase: str) -> None:
    """Phase timestamp on stderr — when the bench is slow or killed the
    log must show where the time went."""
    print(f"# [{_time_mod.time() - _T0:7.1f}s] {phase}", file=sys.stderr, flush=True)


def _verify(small_fn, oracle_slice):
    """Bit-exactness gate on a small slab (cheap: runs the strategy on the
    4 KB slice only, not the full stripe)."""
    got = np.asarray(small_fn())
    if not np.array_equal(got, oracle_slice):
        raise AssertionError("output mismatch vs CPU oracle")


def main() -> int:
    from gpu_rscode_tpu.utils.backend import configure_compile_cache

    configure_compile_cache()
    _mark("backend init")
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(
            f"bench.py: needs a TPU, found platform {dev.platform!r}; "
            "no measurement taken",
            file=sys.stderr,
        )
        return 1
    _mark(f"backend ready: {dev.device_kind} x{len(jax.devices())}")

    from gpu_rscode_tpu import native
    from gpu_rscode_tpu.models.vandermonde import vandermonde_matrix
    from gpu_rscode_tpu.ops.gemm import gf_matmul_jit
    from gpu_rscode_tpu.ops.pallas_gemm import gf_matmul_pallas
    from gpu_rscode_tpu.ops.xor_gemm import gf_matmul_xor
    from gpu_rscode_tpu.tools._bench_timing import time_device_fn as _time

    m = 32 * 1024 * 1024  # bytes per chunk
    seg = 4 * 1024 * 1024  # XLA bitplane segment (bounds HBM expansion)

    A = vandermonde_matrix(P, K)
    rng = np.random.default_rng(0)
    B_host = rng.integers(0, 256, size=(K, m), dtype=np.uint8)
    Ad = jax.device_put(A)
    Bd = jax.device_put(B_host)
    sample = native.gemm(A, B_host[:, :4096])  # CPU-oracle verification slab
    Bd_small = jax.device_put(B_host[:, :4096])

    def segmented(fn):
        def run():
            outs = [fn(Bd[:, off : off + seg]) for off in range(0, m, seg)]
            return jax.numpy.concatenate(outs, axis=1)

        return run

    small = {
        "pallas": lambda: gf_matmul_pallas(Ad, Bd_small),
        "xor": lambda: gf_matmul_xor(A, Bd_small, 8),
        "bitplane": lambda: gf_matmul_jit(Ad, Bd_small, strategy="bitplane"),
        "table": lambda: gf_matmul_jit(Ad, Bd_small, strategy="table"),
    }
    candidates = [
        ("pallas", lambda: gf_matmul_pallas(Ad, Bd)),
        # The XOR-lowered bitsliced strategy (docs/XOR.md): same segment
        # discipline as the other XLA paths.
        ("xor", segmented(lambda b: gf_matmul_xor(A, b, 8))),
        ("bitplane", segmented(
            lambda b: gf_matmul_jit(Ad, b, strategy="bitplane"))),
        ("table", segmented(lambda b: gf_matmul_jit(Ad, b, strategy="table"))),
    ]
    data_bytes = K * m
    detail = {
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
    }
    best = (None, 0.0)
    for name, fn in candidates:
        try:
            _mark(f"verify {name}")
            _verify(small[name], sample)
            _mark(f"time {name}")
            gbps = data_bytes / _time(fn) / 1e9
            detail[name] = round(gbps, 3)
            if gbps > best[1]:
                best = (name, gbps)
        except Exception as e:
            detail[name] = f"failed: {type(e).__name__}"
    _mark(f"strategies done: {detail}")
    if best[0] is None:
        print(f"bench.py: every strategy failed: {detail}", file=sys.stderr)
        return 1

    # 4-erasure recovery latency (BASELINE's second headline): reconstruct
    # the P lost natives from the surviving k chunks with the best strategy.
    from gpu_rscode_tpu.models.vandermonde import total_matrix
    from gpu_rscode_tpu.ops.inverse import invert_matrix

    T = total_matrix(P, K)
    surv = list(range(P, P + K))
    inv_missing = invert_matrix(T[surv])[:P]  # only the lost rows
    survivors = jax.device_put(
        np.concatenate([B_host[P:], native.gemm(T[K:], B_host)], axis=0)[:K]
    )
    if best[0] == "pallas":
        def run_decode():
            return gf_matmul_pallas(jax.device_put(inv_missing), survivors)
    else:
        def run_decode():
            outs = [
                gf_matmul_jit(
                    jax.device_put(inv_missing),
                    survivors[:, off : off + seg],
                    strategy=best[0],
                )
                for off in range(0, m, seg)
            ]
            return jax.numpy.concatenate(outs, axis=1)

    try:
        _mark("time decode")
        dec_dt = _time(run_decode)
        detail["decode_gbps"] = round(data_bytes / dec_dt / 1e9, 3)
        detail["recovery_latency_ms"] = round(1e3 * dec_dt, 2)
    except Exception as e:
        detail["decode"] = f"failed: {type(e).__name__}"
    _mark("done")
    print(
        json.dumps(
            {
                "metric": f"encode_bandwidth_k{K}_n{K + P}_{dev.platform}",
                "value": round(best[1], 3),
                "unit": "GB/s",
                "vs_baseline": round(best[1] / BASELINE_GBPS, 2),
                "detail": {"strategy": best[0], **detail},
            }
        ),
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
