"""chip_smoke.py — the main path end to end on the chip, once.

Bring-up proof, not a benchmark: every phase drives the system through
the entry points a user calls, on seeded data, and checks the bytes
against a plain reference.  One process; any failure exits non-zero.

    python chip_smoke.py [--seed N]          # one chip
    python chip_smoke.py --chips 4 [--seed N]  # the mesh path only

Phases (one chip):

1. file path, RS(10,4), w=8, 1 GiB (HDFS's RS-10-4-1024k geometry, 16
   segments of the default 64 MiB): encode_file (strategy auto, which
   must resolve to the fused kernel) against the native C++ codec's
   archive, byte for byte; decode_file with the first 4 chunks gone;
   repair_file; auto_decode_file past a flipped byte;
2. wide symbols, RS(10,4), w=16, 256 MiB: parity against the NumPy
   GF(2^16) oracle on a 1 MiB slice of each parity chunk, then a decode
   with 4 natives gone;
3. the store: an in-process ``ServeDaemon`` — POST /encode + /decode of
   64 MiB, 256 PUTs of 1 MiB at k=10 n=14, a chunk file of one stripe
   deleted, 32 degraded GETs byte-verified, drain.

``--chips 4`` runs only the mesh path: the 1 GiB RS(10,4) encode on a
cols-sharded and a stripe-sharded 4-chip mesh, each against the native
archive, then auto_decode_file and repair_file on the mesh.

One JSON line per phase on stdout ({phase, wall_s, bytes,
first_dispatch_s, strategy}); the last line is
{"ok": true, "device": {"platform", "kind", "count"}}.
"""

from __future__ import annotations

import argparse
import filecmp
import json
import os
import shutil
import sys
import tempfile
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import numpy as np

MiB = 1 << 20
K, P = 10, 4


def _write_data(path: str, size: int, seed: int) -> str:
    rng = np.random.default_rng(seed)
    with open(path, "wb") as fp:
        for off in range(0, size, 64 * MiB):
            fp.write(rng.bytes(min(64 * MiB, size - off)))
    return path


def _strategy_counts(op: str = "") -> dict:
    """segments_dispatched totals by strategy, of one op when given
    (metrics must be on)."""
    from gpu_rscode_tpu.obs import metrics

    snap = metrics.REGISTRY.snapshot().get("segments_dispatched", {})
    out: dict = {}
    for labels, n in snap.get("values", {}).items():
        if op and f'op="{op}"' not in labels:
            continue
        strat = labels.split('strategy="', 1)[1].split('"', 1)[0]
        out[strat] = out.get(strat, 0) + n
    return out


def _strategies_since(before: dict) -> list:
    now = _strategy_counts()
    return sorted(s for s, n in now.items() if n > before.get(s, 0))


def _same(a: str, b: str) -> None:
    if not filecmp.cmp(a, b, shallow=False):
        raise AssertionError(f"{a} differs from {b}")


def _same_archive(path: str, ref: str, n: int, rows=None) -> None:
    from gpu_rscode_tpu.utils.fileformat import chunk_file_name

    for i in range(n) if rows is None else rows:
        _same(chunk_file_name(path, i), chunk_file_name(ref, i))
    if rows is None:
        _same(path + ".METADATA", ref + ".METADATA")


def _flip_byte(path: str, off: int) -> None:
    with open(path, "r+b") as fp:
        fp.seek(off)
        b = fp.read(1)[0]
        fp.seek(off)
        fp.write(bytes([b ^ 0xFF]))


def _line(phase: str, t0: float, nbytes: int, first: float | None,
          strategy) -> dict:
    return {
        "phase": phase, "wall_s": time.perf_counter() - t0,
        "bytes": nbytes, "first_dispatch_s": first, "strategy": strategy,
    }


def phase_file(work: str, *, size: int = 1024 * MiB, seed: int = 0,
               segment_bytes: int | None = None, outputs=None) -> dict:
    """Phase 1: encode/decode/repair/auto-decode of one RS(10,4) file."""
    from gpu_rscode_tpu import api
    from gpu_rscode_tpu.codec import RSCodec
    from gpu_rscode_tpu.tools.make_conf import make_conf
    from gpu_rscode_tpu.utils.fileformat import chunk_file_name

    seg = dict(segment_bytes=segment_bytes) if segment_bytes else {}
    for d in ("dev", "ref"):
        os.makedirs(os.path.join(work, d))
    src = _write_data(os.path.join(work, "file.bin"), size, seed)
    dev = os.path.join(work, "dev", "file.bin")
    ref = os.path.join(work, "ref", "file.bin")
    os.link(src, dev)  # the same bytes under three names, no copy
    os.link(src, ref)
    t0 = time.perf_counter()
    warm = api.warm_plan(K, P, file_bytes=size, **seg)
    first = time.perf_counter() - t0
    before = _strategy_counts()
    api.encode_file(dev, K, P, checksums=True, **seg)
    if _strategies_since(before) != [warm["strategy"]]:
        raise AssertionError(f"auto -> {warm}, encode ran "
                             f"{_strategies_since(before)}")
    api.encode_file(ref, K, P, checksums=True, strategy="cpu", **seg)
    _same_archive(dev, ref, K + P)

    # An explicit codec dispatch on the first MiB of the natives: its
    # output array is what the device-placement check at the end reads.
    codec = RSCodec(K, P, strategy="auto")
    cols = min(MiB, os.path.getsize(chunk_file_name(dev, 0)))
    natives = np.stack([
        np.fromfile(chunk_file_name(dev, i), np.uint8, cols)
        for i in range(K)
    ])
    parity = codec.encode(natives)
    want = np.stack([
        np.fromfile(chunk_file_name(ref, K + j), np.uint8, cols)
        for j in range(P)
    ])
    np.testing.assert_array_equal(np.asarray(parity), want)
    if outputs is not None:
        outputs.append(parity)

    for i in range(P):  # natives 0..3: decode must really invert
        os.remove(chunk_file_name(dev, i))
    conf = make_conf(K + P, K, dev)
    out = api.decode_file(dev, conf, os.path.join(work, "decoded.bin"), **seg)
    _same(out, src)
    os.remove(out)
    rebuilt = api.repair_file(dev, **seg)
    if sorted(rebuilt) != list(range(P)):
        raise AssertionError(f"repair rebuilt {rebuilt}, want 0..{P - 1}")
    _same_archive(dev, ref, K + P, rows=range(P))
    _flip_byte(chunk_file_name(dev, K + 1), 12345 % cols)
    out = api.auto_decode_file(dev, os.path.join(work, "auto.bin"), **seg)
    _same(out, src)
    strategies = [s for s in _strategies_since(before) if s != "cpu"]
    return _line("file_rs10_4_w8", t0, size, first, strategies)


def phase_wide(work: str, *, size: int = 256 * MiB, seed: int = 1,
               oracle_bytes: int = MiB, segment_bytes: int | None = None,
               outputs=None) -> dict:
    """Phase 2: w=16 round trip; parity checked against the NumPy oracle."""
    from gpu_rscode_tpu import api
    from gpu_rscode_tpu.ops.gf import get_field
    from gpu_rscode_tpu.tools.make_conf import make_conf
    from gpu_rscode_tpu.utils.fileformat import (
        chunk_file_name,
        read_metadata_ext,
    )

    seg = dict(segment_bytes=segment_bytes) if segment_bytes else {}
    os.makedirs(os.path.join(work, "w16"))
    src = _write_data(os.path.join(work, "w16", "wide.bin"), size, seed)
    t0 = time.perf_counter()
    warm = api.warm_plan(K, P, w=16, file_bytes=size, **seg)
    first = time.perf_counter() - t0
    before = _strategy_counts()
    api.encode_file(src, K, P, w=16, checksums=True, **seg)
    if _strategies_since(before) != [warm["strategy"]]:
        raise AssertionError(f"auto -> {warm}, w=16 encode ran "
                             f"{_strategies_since(before)}")
    _size, p, k, total, w, _crcs = read_metadata_ext(src + ".METADATA")
    assert (k, p, w) == (K, P, 16), (k, p, w)
    syms = min(oracle_bytes, os.path.getsize(chunk_file_name(src, 0))) // 2
    chunks = [
        np.fromfile(chunk_file_name(src, i), np.uint16, syms)
        for i in range(K + P)
    ]
    want = get_field(16).matmul(np.asarray(total)[K:], np.stack(chunks[:K]))
    np.testing.assert_array_equal(np.stack(chunks[K:]), want)
    for i in range(P):
        os.remove(chunk_file_name(src, i))
    conf = make_conf(K + P, K, src)
    _same(api.decode_file(src, conf, os.path.join(work, "w16.out"), **seg),
          src)
    strategies = _strategies_since(before)
    return _line("wide_rs10_4_w16", t0, size, first, strategies)


def _http(method: str, url: str, body: bytes | None = None) -> bytes:
    req = urllib.request.Request(url, data=body, method=method)
    with urllib.request.urlopen(req, timeout=600) as resp:
        if resp.status != 200:
            raise AssertionError(f"{method} {url} -> {resp.status}")
        return resp.read()


def phase_store(work: str, *, encode_bytes: int = 64 * MiB,
                objects: int = 256, object_bytes: int = MiB,
                gets: int = 32, seed: int = 2, clients: int = 8) -> dict:
    """Phase 3: the daemon's file and object paths, degraded GETs."""
    from gpu_rscode_tpu.serve.daemon import ServeDaemon
    from gpu_rscode_tpu.utils.fileformat import chunk_file_name

    rng = np.random.default_rng(seed)
    payload = rng.bytes(encode_bytes)
    objs = {f"obj-{i:04d}": rng.bytes(object_bytes) for i in range(objects)}
    # obj_cache_bytes=0: every GET reads the stripe, none is a cache hit.
    daemon = ServeDaemon(os.path.join(work, "serve"), port=0,
                         obj_cache_bytes=0)
    daemon.start()
    base = f"http://127.0.0.1:{daemon.port}"
    try:
        t0 = time.perf_counter()
        before = _strategy_counts()
        daemon.warm(K, P, file_bytes=encode_bytes)
        first = time.perf_counter() - t0
        _http("POST", f"{base}/encode?name=big.bin&k={K}&n={K + P}"
              "&checksum=1", payload)
        if _http("POST", f"{base}/decode?name=big.bin") != payload:
            raise AssertionError("daemon decode differs from the upload")
        with ThreadPoolExecutor(clients) as pool:
            list(pool.map(
                lambda kv: _http(
                    "PUT", f"{base}/o/bkt/{kv[0]}?k={K}&n={K + P}", kv[1]),
                objs.items(),
            ))
        listing = json.loads(_http("GET", f"{base}/o/bkt?limit=100000"))
        by_arc: dict = {}
        for o in listing["objects"]:
            by_arc.setdefault(o["arc"], []).append(o["key"])
        if sorted(k for ks in by_arc.values() for k in ks) != sorted(objs):
            raise AssertionError("bucket listing lost objects")
        arc = sorted(by_arc)[0]  # the first (sealed) stripe
        os.remove(chunk_file_name(
            os.path.join(daemon.tenant_path("default", "bkt"), arc), 0))
        decodes = sum(_strategy_counts("decode").values())
        for key in sorted(by_arc[arc])[:gets]:
            if _http("GET", f"{base}/o/bkt/{key}") != objs[key]:
                raise AssertionError(f"degraded GET of {key} differs")
        if sum(_strategy_counts("decode").values()) <= decodes:
            raise AssertionError("the degraded GETs dispatched no decode")
        strategies = _strategies_since(before)
        if not daemon.drain(timeout=600):
            raise AssertionError("daemon drain did not finish")
    finally:
        daemon.close(drain=False)
    return _line("store_serve", t0,
                 encode_bytes + objects * object_bytes, first, strategies)


def phase_mesh(work: str, *, n_devices: int = 4, size: int = 1024 * MiB,
               seed: int = 3, segment_bytes: int | None = None,
               outputs=None) -> dict:
    """--chips 4: encode on a cols- and a stripe-sharded mesh, then
    auto-decode and repair on the mesh, all against the native archive."""
    from gpu_rscode_tpu import api
    from gpu_rscode_tpu.parallel.mesh import make_mesh
    from gpu_rscode_tpu.parallel.sharded import put_sharded
    from gpu_rscode_tpu.utils.fileformat import chunk_file_name

    seg = dict(segment_bytes=segment_bytes) if segment_bytes else {}
    src = _write_data(os.path.join(work, "mesh.bin"), size, seed)
    paths = {}
    for d in ("cols", "stripe", "ref"):
        os.makedirs(os.path.join(work, d))
        paths[d] = os.path.join(work, d, "mesh.bin")
        os.link(src, paths[d])
    api.encode_file(paths["ref"], K, P, checksums=True, strategy="cpu", **seg)
    meshes = {"cols": make_mesh(n_devices),
              "stripe": make_mesh(n_devices, stripe=2)}
    # The staged operand must spread over every chip of the mesh.
    probe = np.zeros((K, 128 * n_devices), np.uint8)
    for name, mesh in meshes.items():
        arr = put_sharded(probe, mesh, stripe_sharded=name == "stripe")
        devs = {s.device for s in arr.addressable_shards}
        if len(devs) != n_devices:
            raise AssertionError(f"{name} mesh staged on {len(devs)} devices")
        if outputs is not None:
            outputs.append(arr)
    t0 = time.perf_counter()
    first = None
    before = _strategy_counts()
    for name, mesh in meshes.items():
        t1 = time.perf_counter()
        api.encode_file(paths[name], K, P, mesh=mesh, checksums=True,
                        stripe_sharded=name == "stripe", **seg)
        first = first or time.perf_counter() - t1
        _same_archive(paths[name], paths["ref"], K + P)
    dev, mesh = paths["cols"], meshes["cols"]
    for i in range(P - 1):
        os.remove(chunk_file_name(dev, i))
    _flip_byte(chunk_file_name(dev, K), 100)
    out = api.auto_decode_file(dev, os.path.join(work, "mesh.out"),
                               mesh=mesh, **seg)
    _same(out, src)
    rebuilt = api.repair_file(dev, mesh=mesh, **seg)
    if sorted(rebuilt) != [*range(P - 1), K]:
        raise AssertionError(f"mesh repair rebuilt {rebuilt}")
    _same_archive(dev, paths["ref"], K + P)
    strategies = _strategies_since(before)
    return _line(f"mesh_{n_devices}chip", t0, 2 * size, first, strategies)


def check_outputs(outputs, platform: str = "tpu") -> None:
    """No output array of a device phase lives off the chip."""
    import jax

    for arr in list(outputs) + list(jax.live_arrays()):
        for d in arr.devices():
            if d.platform != platform:
                raise AssertionError(f"an output lives on {d}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    try:
        from gpu_rscode_tpu.utils.backend import configure_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the package is not importable: {e}",
              file=sys.stderr)
        return 2
    configure_compile_cache()
    import jax
    import jaxlib

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found platform {dev.platform!r}",
              file=sys.stderr)
        return 1
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(jax.devices())} "
              "devices", file=sys.stderr)
        return 1
    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:
        libtpu = None
    print(json.dumps({
        "device_kind": dev.device_kind, "device_count": len(jax.devices()),
        "jax": jax.__version__, "jaxlib": jaxlib.__version__,
        "libtpu": libtpu,
    }), flush=True)

    from gpu_rscode_tpu import native
    from gpu_rscode_tpu.obs import metrics

    if not native.available():
        print("chip_smoke: the native reference codec did not build",
              file=sys.stderr)
        return 1
    metrics.force_enable()
    outputs: list = []
    work = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        if args.chips == 4:
            phases = [lambda d: phase_mesh(d, seed=args.seed + 3,
                                           outputs=outputs)]
        else:
            phases = [
                lambda d: phase_file(d, seed=args.seed, outputs=outputs),
                lambda d: phase_wide(d, seed=args.seed + 1, outputs=outputs),
                lambda d: phase_store(d, seed=args.seed + 2),
            ]
        for i, run in enumerate(phases):
            d = os.path.join(work, f"phase{i}")
            os.makedirs(d)
            line = run(d)
            print(json.dumps(line), flush=True)
            if line["strategy"] != ["pallas"]:
                raise AssertionError(f"{line['phase']} ran {line['strategy']}")
            shutil.rmtree(d)
        refolds = metrics.REGISTRY.snapshot().get(
            "rs_pallas_refold_total", {}).get("values", {})
        if not sum(refolds.values()):
            raise AssertionError("rs_pallas_refold_total did not move")
        check_outputs(outputs)
        stats = dev.memory_stats() or {}
        print(json.dumps({
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "refolds": refolds,
        }), flush=True)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": args.chips,
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
