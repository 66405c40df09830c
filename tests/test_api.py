"""End-to-end file encode/decode round-trips — the de-facto system test of
the reference (encode, build conf, decode, compare bytes), automated."""

import os

import numpy as np
import pytest

from gpu_rscode_tpu import api
from gpu_rscode_tpu.tools.make_conf import make_conf
from gpu_rscode_tpu.utils.fileformat import chunk_file_name, chunk_size_for


def _mkfile(tmp_path, size, seed=0):
    path = str(tmp_path / f"data_{size}.bin")
    rng = np.random.default_rng(seed)
    with open(path, "wb") as fp:
        fp.write(rng.integers(0, 256, size=size, dtype=np.uint8).tobytes())
    return path


@pytest.mark.parametrize(
    "k,n,size",
    [
        (4, 6, 1000),      # size % k == 0
        (4, 6, 1001),      # tail padding
        (10, 14, 40001),   # the BASELINE (k=10,n=14) config
        (2, 3, 7),         # tiny
        (1, 2, 50),        # k=1 degenerate: pure replication+parity
    ],
)
def test_roundtrip_worst_case_erasure(tmp_path, k, n, size):
    path = _mkfile(tmp_path, size, seed=size)
    orig = open(path, "rb").read()
    files = api.encode_file(path, k, n - k)
    assert len(files) == n + 1  # n chunks + METADATA
    conf = make_conf(n, k, path)  # survivors = last k chunks
    out = str(tmp_path / "out.bin")
    got_path = api.decode_file(path, conf, out)
    assert got_path == out
    assert open(out, "rb").read() == orig


def test_roundtrip_all_natives(tmp_path):
    """Identity-submatrix fast case (the examples/conf scenario)."""
    path = _mkfile(tmp_path, 5000, seed=1)
    orig = open(path, "rb").read()
    api.encode_file(path, 4, 2)
    conf = make_conf(6, 4, path, survivors=[0, 1, 2, 3])
    out = str(tmp_path / "o")
    api.decode_file(path, conf, out)
    assert open(out, "rb").read() == orig


def test_roundtrip_mixed_pattern(tmp_path):
    path = _mkfile(tmp_path, 12345, seed=2)
    orig = open(path, "rb").read()
    api.encode_file(path, 4, 3)
    conf = make_conf(7, 4, path, survivors=[0, 6, 2, 5])  # scrambled order too
    out = str(tmp_path / "o")
    api.decode_file(path, conf, out)
    assert open(out, "rb").read() == orig


def test_roundtrip_overwrite_input_default(tmp_path):
    path = _mkfile(tmp_path, 900, seed=3)
    orig = open(path, "rb").read()
    api.encode_file(path, 3, 2)
    conf = make_conf(5, 3, path)
    os.remove(path)  # simulate the original being lost
    got = api.decode_file(path, conf)  # default output = in_file
    assert got == path
    assert open(path, "rb").read() == orig


def test_chunk_files_deterministic_padding(tmp_path):
    """Tail chunk and parity must be deterministic (explicit zero padding) —
    the reference's GPU path encodes heap garbage here (encode.cu:325-330)."""
    path = _mkfile(tmp_path, 1001, seed=4)
    api.encode_file(path, 4, 2)
    chunk = chunk_size_for(1001, 4)
    first = [open(chunk_file_name(path, i), "rb").read() for i in range(6)]
    # wipe and re-encode: all chunk files byte-identical
    api.encode_file(path, 4, 2)
    second = [open(chunk_file_name(path, i), "rb").read() for i in range(6)]
    assert first == second
    assert all(len(c) == chunk for c in first)
    # tail of last native chunk is zeros
    tail = first[3][1001 - 3 * chunk :]
    assert tail == b"\x00" * (4 * chunk - 1001)


def test_segmented_matches_single_shot(tmp_path):
    """Streaming through small segments must produce identical bytes to one
    big dispatch (the -s / segment knob cannot change results)."""
    path = _mkfile(tmp_path, 50_000, seed=5)
    api.encode_file(path, 4, 2)
    ref = [open(chunk_file_name(path, i), "rb").read() for i in range(6)]
    api.encode_file(path, 4, 2, segment_bytes=4096, pipeline_depth=3)
    seg = [open(chunk_file_name(path, i), "rb").read() for i in range(6)]
    assert ref == seg


def test_decode_wrong_conf_count(tmp_path):
    path = _mkfile(tmp_path, 1000, seed=6)
    api.encode_file(path, 4, 2)
    conf = str(tmp_path / "badconf")
    open(conf, "w").write("_0_data_1000.bin\n_1_data_1000.bin\n")
    with pytest.raises(ValueError, match="need k=4"):
        api.decode_file(path, conf)


def test_encode_empty_file_rejected(tmp_path):
    path = str(tmp_path / "empty")
    open(path, "wb").close()
    with pytest.raises(ValueError, match="empty"):
        api.encode_file(path, 4, 2)


def test_cauchy_generator_roundtrip(tmp_path):
    path = _mkfile(tmp_path, 3333, seed=7)
    orig = open(path, "rb").read()
    api.encode_file(path, 4, 2, generator="cauchy")
    conf = make_conf(6, 4, path, survivors=[5, 4, 1, 0])
    out = str(tmp_path / "o")
    api.decode_file(path, conf, out)
    assert open(out, "rb").read() == orig


def test_cpu_strategy_roundtrip(tmp_path):
    """The native host codec path (CPU-RS oracle role) end-to-end."""
    path = _mkfile(tmp_path, 7777, seed=8)
    orig = open(path, "rb").read()
    api.encode_file(path, 4, 2, strategy="cpu")
    conf = make_conf(6, 4, path)
    out = str(tmp_path / "o")
    api.decode_file(path, conf, out, strategy="cpu")
    assert open(out, "rb").read() == orig


def test_cpu_strategy_chunks_match_device_strategy(tmp_path):
    """Bit-exactness contract: native CPU codec and the TPU bitplane path
    must produce identical parity bytes (the reference's GPU/CPU padding
    divergence is exactly what this guards against)."""
    from gpu_rscode_tpu.utils.fileformat import chunk_file_name

    path = _mkfile(tmp_path, 10_001, seed=9)
    api.encode_file(path, 4, 2, strategy="cpu")
    cpu = [open(chunk_file_name(path, i), "rb").read() for i in range(6)]
    api.encode_file(path, 4, 2, strategy="bitplane")
    dev = [open(chunk_file_name(path, i), "rb").read() for i in range(6)]
    assert cpu == dev


def test_partial_recovery_single_erasure(tmp_path):
    """Only one chunk lost: decode must copy surviving natives byte-for-byte
    and reconstruct just the missing row."""
    path = _mkfile(tmp_path, 44_444, seed=10)
    orig = open(path, "rb").read()
    api.encode_file(path, 5, 3)
    conf = make_conf(8, 5, path, survivors=[0, 1, 3, 4, 7])  # lost native 2
    out = str(tmp_path / "o")
    api.decode_file(path, conf, out)
    assert open(out, "rb").read() == orig


def test_partial_recovery_all_parity_survivors(tmp_path):
    """Worst case: every native lost, survivors are parity-only + natives
    beyond p (full GEMM path)."""
    path = _mkfile(tmp_path, 9_876, seed=11)
    orig = open(path, "rb").read()
    api.encode_file(path, 3, 3)
    conf = make_conf(6, 3, path, survivors=[5, 4, 3])  # all parity
    out = str(tmp_path / "o")
    api.decode_file(path, conf, out)
    assert open(out, "rb").read() == orig


# ----- checksum extension ---------------------------------------------------


def test_checksum_roundtrip_and_verify(tmp_path):
    """CRC32 extension lines are written, parsed back, and verified clean on
    decode; the metadata stays parseable as the base (reference) format."""
    from gpu_rscode_tpu.utils.fileformat import (
        metadata_file_name,
        read_checksums,
        read_metadata,
    )

    path = _mkfile(tmp_path, 12_345, seed=21)
    orig = open(path, "rb").read()
    api.encode_file(path, 4, 2, checksums=True)
    meta = metadata_file_name(path)
    crcs = read_checksums(meta)
    assert sorted(crcs) == list(range(6))  # one CRC per chunk, natives+parity
    # Base-format parse is unaffected by the trailing extension lines.
    total_size, p, k, mat = read_metadata(meta)
    assert (total_size, p, k) == (12_345, 2, 4)
    conf = make_conf(6, 4, path)
    out = str(tmp_path / "out.bin")
    api.decode_file(path, conf, out)  # auto-verify, must pass
    assert open(out, "rb").read() == orig


def test_checksum_detects_corrupt_survivor(tmp_path):
    path = _mkfile(tmp_path, 20_000, seed=22)
    api.encode_file(path, 4, 2, checksums=True)
    conf = make_conf(6, 4, path)  # survivors 2..5
    victim = chunk_file_name(path, 3)
    data = bytearray(open(victim, "rb").read())
    data[100] ^= 0xFF
    open(victim, "wb").write(bytes(data))
    with pytest.raises(api.ChunkIntegrityError) as ei:
        api.decode_file(path, conf, str(tmp_path / "o"))
    assert 3 in ei.value.bad_chunks
    # Skipping verification decodes the corrupt bytes without complaint.
    api.decode_file(path, conf, str(tmp_path / "o2"), verify_checksums=False)


def test_checksum_absent_is_not_verified(tmp_path):
    """Default encode writes no checksums; decode must not require them,
    and verify_checksums=True must then fail fast."""
    path = _mkfile(tmp_path, 5_000, seed=23)
    orig = open(path, "rb").read()
    api.encode_file(path, 4, 2)
    conf = make_conf(6, 4, path)
    out = str(tmp_path / "o")
    api.decode_file(path, conf, out)
    assert open(out, "rb").read() == orig
    with pytest.raises(ValueError, match="no checksum"):
        api.decode_file(path, conf, out, verify_checksums=True)


def test_checksum_segmented_encode_consistent(tmp_path):
    """CRCs accumulated across multiple streamed segments equal whole-file
    CRCs (FIFO drain order contract)."""
    import zlib

    from gpu_rscode_tpu.utils.fileformat import (
        metadata_file_name,
        read_checksums,
    )

    path = _mkfile(tmp_path, 50_000, seed=24)
    api.encode_file(path, 4, 2, checksums=True, segment_bytes=4096)
    crcs = read_checksums(metadata_file_name(path))
    for i in range(6):
        whole = zlib.crc32(open(chunk_file_name(path, i), "rb").read())
        assert crcs[i] == whole, f"chunk {i}"


# ----- native chunks written from the gathered segments ---------------------


def _check_archive(path, data, k, p, chunk, checksums):
    """The k natives are the plain file ranges, tail zero-padded; the CRC
    lines (if any) are the files' own; the parity decodes the data back
    with the first k chunks lost."""
    import zlib

    from gpu_rscode_tpu.utils.fileformat import (
        metadata_file_name,
        read_checksums,
    )

    files = [open(chunk_file_name(path, i), "rb").read() for i in range(k + p)]
    plain = [data[i * chunk:(i + 1) * chunk].ljust(chunk, b"\0")
             for i in range(k)]
    assert files[:k] == plain
    want = {i: zlib.crc32(f) for i, f in enumerate(files)} if checksums else {}
    assert read_checksums(metadata_file_name(path)) == want
    conf = make_conf(k + p, k, path, survivors=list(range(p, k + p)))
    out = path + ".out"
    api.decode_file(path, conf, out)
    assert open(out, "rb").read() == data


@pytest.mark.parametrize("checksums", [True, False])
@pytest.mark.parametrize("w", [8, 16])
def test_natives_leave_from_the_gathered_segments(tmp_path, w, checksums):
    """Each segment's drain writes the segment's native rows from the
    gathered host segment: over 13 segments and a zero-padded tail the
    archive is exact, and rs_io_write_bytes_total{call="native_rows"}
    counts every native byte once."""
    from gpu_rscode_tpu.obs import metrics

    k, p, size = 4, 2, 50_001
    path = _mkfile(tmp_path, size, seed=40 + w)
    data = open(path, "rb").read()
    chunk = chunk_size_for(size, k, w // 8)
    assert chunk * k > size and chunk // 1024 >= 12  # tail pad, 13 segments
    metrics.REGISTRY.reset()
    metrics.force_enable()
    try:
        api.encode_file(path, k, p, w=w, checksums=checksums,
                        segment_bytes=4096)
        counted = metrics.REGISTRY.snapshot()["rs_io_write_bytes_total"]
    finally:
        metrics.force_enable(False)
        metrics.REGISTRY.reset()
    assert counted["values"]['{call="native_rows"}'] == k * chunk
    _check_archive(path, data, k, p, chunk, checksums)


@pytest.mark.parametrize("fault", ["plane", "mid_drain"])
def test_native_crcs_commit_once_when_a_drain_retries(tmp_path, monkeypatch,
                                                      fault):
    """A transient write fault retries the whole drain task; each native
    CRC is still committed once, so the CRC lines are those of the files.
    ``plane``: RS_FAULTS fails drains before they write; ``mid_drain``:
    the first native and the first parity write of each segment fail
    inside their drains."""
    import errno

    from gpu_rscode_tpu import native
    from gpu_rscode_tpu.resilience import faults

    monkeypatch.setenv("RS_RETRY_BASE_MS", "1")
    monkeypatch.setenv("RS_RETRY_ATTEMPTS", "10")
    real = native.scatter_write
    failed = []

    def flaky(files, arr, off, **kw):
        if fault == "mid_drain" and (off, len(files)) not in failed:
            failed.append((off, len(files)))
            raise OSError(errno.EIO, "transient write")
        return real(files, arr, off, **kw)

    monkeypatch.setattr(native, "scatter_write", flaky)
    k, p, size = 4, 2, 50_001
    path = _mkfile(tmp_path, size, seed=45)
    data = open(path, "rb").read()
    plan = (faults.parse_plan("write:ioerror@p=0.3", seed=7)
            if fault == "plane" else None)
    with faults.activate(plan):
        api.encode_file(path, k, p, checksums=True, segment_bytes=4096)
    if fault == "plane":
        assert plan.injected[("ioerror", "write")] > 0
    else:
        assert len(failed) == 2 * 13
    _check_archive(path, data, k, p, chunk_size_for(size, k), True)


# ----- wide-symbol (GF(2^16)) file coding -----------------------------------


@pytest.mark.parametrize("size", [10_000, 10_001, 9_999])
def test_wide_symbol_roundtrip_worst_case(tmp_path, size):
    """w=16 file coding: chunks hold LE uint16 symbols, chunk size is
    2-aligned, .METADATA records gfwidth, decode auto-detects and recovers
    bit-exactly under the worst-case erasure (incl. odd file sizes)."""
    from gpu_rscode_tpu.utils.fileformat import (
        metadata_file_name,
        read_field_width,
    )

    path = _mkfile(tmp_path, size, seed=size + 1)
    orig = open(path, "rb").read()
    api.encode_file(path, 4, 2, w=16)
    assert read_field_width(metadata_file_name(path)) == 16
    assert os.path.getsize(chunk_file_name(path, 0)) % 2 == 0
    conf = make_conf(6, 4, path)
    out = str(tmp_path / "out.bin")
    api.decode_file(path, conf, out)
    assert open(out, "rb").read() == orig


def test_wide_symbol_metadata_matrix_parses(tmp_path):
    """Wide metadata carries entries > 255 and parses back as uint16."""
    from gpu_rscode_tpu.utils.fileformat import (
        metadata_file_name,
        read_metadata,
    )

    path = _mkfile(tmp_path, 4_096, seed=31)
    api.encode_file(path, 8, 4, w=16)
    _, p, k, mat = read_metadata(metadata_file_name(path))
    assert (p, k) == (4, 8)
    assert mat.dtype == np.uint16
    assert mat.max() > 255  # (j+1)^i over GF(2^16) exceeds a byte at k=8,p=4


def test_wide_symbol_with_checksums(tmp_path):
    """Both metadata extensions coexist."""
    from gpu_rscode_tpu.utils.fileformat import (
        metadata_file_name,
        read_checksums,
        read_field_width,
    )

    path = _mkfile(tmp_path, 7_777, seed=32)
    orig = open(path, "rb").read()
    api.encode_file(path, 4, 2, w=16, checksums=True)
    meta = metadata_file_name(path)
    assert read_field_width(meta) == 16
    assert sorted(read_checksums(meta)) == list(range(6))
    conf = make_conf(6, 4, path)
    out = str(tmp_path / "o")
    api.decode_file(path, conf, out)
    assert open(out, "rb").read() == orig


def test_default_width_unchanged(tmp_path):
    """w=8 metadata must carry NO gfwidth line (byte-compat preserved)."""
    from gpu_rscode_tpu.utils.fileformat import (
        metadata_file_name,
        read_field_width,
    )

    path = _mkfile(tmp_path, 1_000, seed=33)
    api.encode_file(path, 4, 2)
    assert read_field_width(metadata_file_name(path)) == 8
    assert "gfwidth" not in open(metadata_file_name(path)).read()


def test_bad_width_rejected(tmp_path):
    path = _mkfile(tmp_path, 100, seed=34)
    with pytest.raises(ValueError, match="width"):
        api.encode_file(path, 2, 1, w=4)


def test_decode_rejects_unknown_gfwidth(tmp_path):
    """A foreign/corrupt '# gfwidth' value must fail with a clean error,
    not a crash (file-supplied input)."""
    from gpu_rscode_tpu.utils.fileformat import metadata_file_name

    path = _mkfile(tmp_path, 2_000, seed=35)
    api.encode_file(path, 4, 2)
    with open(metadata_file_name(path), "a") as fp:
        fp.write("# gfwidth 4\n")
    conf = make_conf(6, 4, path)
    with pytest.raises(ValueError, match="gfwidth"):
        api.decode_file(path, conf, str(tmp_path / "o"))


# ----- auto-decode (survivor auto-discovery) --------------------------------


def test_auto_decode_skips_corrupt_and_missing(tmp_path):
    """Self-healing flow: one chunk deleted, one corrupted — auto-decode
    must detect both via CRC, pick healthy survivors, and recover."""
    path = _mkfile(tmp_path, 33_333, seed=41)
    orig = open(path, "rb").read()
    api.encode_file(path, 4, 3, checksums=True)
    os.remove(chunk_file_name(path, 1))  # native lost
    victim = chunk_file_name(path, 2)  # native corrupted
    data = bytearray(open(victim, "rb").read())
    data[7] ^= 0x55
    open(victim, "wb").write(bytes(data))
    out = str(tmp_path / "o")
    got = api.auto_decode_file(path, out)
    assert got == out
    assert open(out, "rb").read() == orig
    # The chosen conf is written as an auditable artifact.
    conf = open(path + ".auto.conf").read().split()
    assert len(conf) == 4
    assert not any(nm.startswith("_1_") or nm.startswith("_2_") for nm in conf)


def test_auto_decode_without_checksums(tmp_path):
    """Without CRC lines, auto-decode still handles missing chunks (it just
    cannot detect silent corruption)."""
    path = _mkfile(tmp_path, 10_000, seed=42)
    orig = open(path, "rb").read()
    api.encode_file(path, 4, 2)
    os.remove(chunk_file_name(path, 0))
    os.remove(chunk_file_name(path, 3))
    out = str(tmp_path / "o")
    api.auto_decode_file(path, out)
    assert open(out, "rb").read() == orig


def test_auto_decode_too_few_survivors(tmp_path):
    path = _mkfile(tmp_path, 5_000, seed=43)
    api.encode_file(path, 4, 2)
    for i in (0, 1, 2):
        os.remove(chunk_file_name(path, i))
    with pytest.raises(ValueError, match="healthy"):
        api.auto_decode_file(path, str(tmp_path / "o"))


def test_decode_rejects_out_of_range_matrix_entry(tmp_path):
    """A w=8 metadata whose matrix carries an entry > 255 must be rejected,
    not silently wrapped into GF(2^8)."""
    from gpu_rscode_tpu.utils.fileformat import metadata_file_name

    path = _mkfile(tmp_path, 2_000, seed=36)
    api.encode_file(path, 4, 2)
    meta = metadata_file_name(path)
    lines = open(meta).read().splitlines()
    lines[2] = lines[2].replace(lines[2].split()[0], "300", 1)
    open(meta, "w").write("\n".join(lines) + "\n")
    conf = make_conf(6, 4, path)
    with pytest.raises(ValueError, match="out of range"):
        api.decode_file(path, conf, str(tmp_path / "o"))


def test_auto_strategy_resolves_off_tpu(tmp_path):
    """strategy='auto' must resolve to bitplane on the CPU test backend and
    round-trip bit-exactly."""
    from gpu_rscode_tpu.codec import RSCodec

    assert RSCodec(4, 2, strategy="auto").strategy == "bitplane"
    path = _mkfile(tmp_path, 8_000, seed=51)
    orig = open(path, "rb").read()
    api.encode_file(path, 4, 2)  # default auto
    conf = make_conf(6, 4, path)
    out = str(tmp_path / "o")
    api.decode_file(path, conf, out)
    assert open(out, "rb").read() == orig


def test_auto_strategy_on_mesh_resolves_bitplane():
    """auto + mesh must pick the sharded-proven bitplane path (the mesh body
    has no Mosaic fallback)."""
    from gpu_rscode_tpu.codec import RSCodec
    from gpu_rscode_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(4)
    assert RSCodec(4, 2, strategy="auto", mesh=mesh).strategy == "bitplane"


def test_pallas_failure_fails_encode_file_cleanly(monkeypatch, tmp_path):
    """A backend/Mosaic failure in the fused kernel fails the encode:
    nothing runs in its place, and no chunk, metadata or .rs_tmp file
    is left behind."""
    import jax

    from gpu_rscode_tpu import codec as codec_mod

    def boom(A, B, w=8):
        raise jax.errors.JaxRuntimeError("MOSAIC: backend exploded")

    monkeypatch.setattr(codec_mod, "_gf_matmul_pallas_eager", boom)
    path = tmp_path / "f.bin"
    path.write_bytes(np.random.default_rng(0).bytes(20_000))
    with pytest.raises(codec_mod.FusedKernelError, match="backend exploded"):
        api.encode_file(str(path), 4, 2, strategy="pallas")
    assert os.listdir(tmp_path) == ["f.bin"]


def test_pallas_failure_message_names_shape(monkeypatch):
    """The raised error names the dispatch shape and width, so a kernel
    the chip refuses says which one."""
    import jax

    from gpu_rscode_tpu import codec as codec_mod
    from gpu_rscode_tpu.codec import RSCodec

    def boom(A, B, w=8):
        raise jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED: vmem")

    monkeypatch.setattr(codec_mod, "_gf_matmul_pallas_eager", boom)
    c = RSCodec(4, 2, strategy="pallas")
    with pytest.raises(codec_mod.FusedKernelError) as e:
        c.encode(np.zeros((4, 512), dtype=np.uint8))
    msg = str(e.value)
    assert "A(2, 4)" in msg and "B(4, 512)" in msg and "w=8" in msg
    assert "one device" in msg and "RESOURCE_EXHAUSTED" in msg


def test_pallas_failure_keeps_cause_and_decode_output(monkeypatch, tmp_path):
    """Any failure inside the fused dispatch raises with its cause
    chained and the strategy unchanged; a failing decode writes no
    output file."""
    from gpu_rscode_tpu import codec as codec_mod
    from gpu_rscode_tpu.codec import RSCodec

    path = tmp_path / "f.bin"
    path.write_bytes(np.random.default_rng(1).bytes(20_000))
    api.encode_file(str(path), 4, 2, strategy="bitplane")
    conf = make_conf(6, 4, str(path))

    def boom(A, B, w=8):
        raise ValueError("shape bug")

    monkeypatch.setattr(codec_mod, "_gf_matmul_pallas_eager", boom)
    c = RSCodec(4, 2, strategy="pallas")
    with pytest.raises(codec_mod.FusedKernelError) as e:
        c.encode(np.zeros((4, 512), dtype=np.uint8))
    assert isinstance(e.value.__cause__, ValueError)
    assert c.strategy == "pallas"
    out = tmp_path / "out.bin"
    with pytest.raises(codec_mod.FusedKernelError):
        api.decode_file(str(path), conf, str(out), strategy="pallas")
    assert not out.exists()
    assert not [f for f in os.listdir(tmp_path) if ".rs_tmp" in f]


def test_codec_pallas_dispatch_is_eager_for_autotune(monkeypatch):
    """RS_PALLAS_REFOLD=autotune must CALIBRATE in the production codec
    path — i.e. the single-device pallas dispatch runs eagerly so the env
    resolution sees concrete arrays.  A refactor back to an outer jit
    would silently turn autotune into the static default (the tracer
    guard) and this pins it: the timer must actually run."""
    from gpu_rscode_tpu.codec import RSCodec
    from gpu_rscode_tpu.ops import pallas_gemm as pg

    timed = []
    real = pg._time_refold
    monkeypatch.setattr(
        pg, "_time_refold", lambda run: timed.append(1) or real(run)
    )
    monkeypatch.setattr(pg, "_AUTOTUNE_CACHE", {})
    monkeypatch.setenv("RS_PALLAS_REFOLD", "autotune")

    c = RSCodec(4, 2, strategy="pallas")
    rng = np.random.default_rng(7)
    data = rng.integers(0, 256, size=(4, 512), dtype=np.uint8)
    out = np.asarray(c.encode(data))
    assert len(timed) == 2  # both refold variants were really timed
    np.testing.assert_array_equal(out, c.gf.matmul(c.parity_block, data))


# ----- chunk repair ---------------------------------------------------------


def test_repair_rebuilds_missing_and_corrupt(tmp_path):
    """Lost parity + corrupt native are both regenerated byte-identically
    and the CRC lines refreshed; a later plain decode succeeds."""
    import zlib

    from gpu_rscode_tpu.utils.fileformat import (
        metadata_file_name,
        read_checksums,
    )

    path = _mkfile(tmp_path, 25_000, seed=61)
    orig = open(path, "rb").read()
    api.encode_file(path, 4, 2, checksums=True)
    golden = {i: open(chunk_file_name(path, i), "rb").read() for i in range(6)}
    os.remove(chunk_file_name(path, 5))  # parity lost
    victim = chunk_file_name(path, 1)  # native corrupted
    data = bytearray(golden[1])
    data[0] ^= 0xA5
    open(victim, "wb").write(bytes(data))

    rebuilt = api.repair_file(path)
    assert rebuilt == [1, 5]
    for i in range(6):
        assert open(chunk_file_name(path, i), "rb").read() == golden[i], i
    crcs = read_checksums(metadata_file_name(path))
    for i in range(6):
        assert crcs[i] == zlib.crc32(golden[i])
    # archive healthy afterwards
    assert api.repair_file(path) == []
    conf = make_conf(6, 4, path)
    out = str(tmp_path / "o")
    api.decode_file(path, conf, out)
    assert open(out, "rb").read() == orig


def test_repair_without_checksums(tmp_path):
    """No CRC lines: repair still rebuilds missing chunks (corruption is
    undetectable, as documented)."""
    path = _mkfile(tmp_path, 9_000, seed=62)
    api.encode_file(path, 3, 2)
    golden = open(chunk_file_name(path, 4), "rb").read()
    os.remove(chunk_file_name(path, 4))
    assert api.repair_file(path) == [4]
    assert open(chunk_file_name(path, 4), "rb").read() == golden


def test_repair_wide_symbols(tmp_path):
    path = _mkfile(tmp_path, 11_111, seed=63)
    api.encode_file(path, 4, 2, w=16, checksums=True)
    golden = open(chunk_file_name(path, 0), "rb").read()
    os.remove(chunk_file_name(path, 0))
    assert api.repair_file(path) == [0]
    assert open(chunk_file_name(path, 0), "rb").read() == golden


def test_repair_too_many_losses(tmp_path):
    path = _mkfile(tmp_path, 5_000, seed=64)
    api.encode_file(path, 4, 2)
    for i in (0, 1, 2):
        os.remove(chunk_file_name(path, i))
    with pytest.raises(ValueError, match="healthy"):
        api.repair_file(path)


def test_scan_reports_truncated_as_corrupt(tmp_path):
    """A present-but-truncated chunk is damage, not loss — it must appear
    under 'corrupt' in the health report and be repairable in place."""
    path = _mkfile(tmp_path, 12_000, seed=65)
    api.encode_file(path, 4, 2, checksums=True)
    victim = chunk_file_name(path, 2)
    golden = open(victim, "rb").read()
    open(victim, "wb").write(golden[:-50])  # truncate
    report = api.scan_file(path)
    assert report["corrupt"] == [2]
    assert report["missing"] == []
    assert report["decodable"]
    assert api.repair_file(path) == [2]
    assert open(victim, "rb").read() == golden


def test_subset_search_capped_vs_exhausted():
    """The subset search must distinguish 'every combination tried, none
    inverts' (ValueError) from 'cap hit, verdict unknown'
    (UndecidedSubsetError) — an operator must not read a capped search as
    proof the archive is unrecoverable."""
    from gpu_rscode_tpu.api import UndecidedSubsetError, _ChunkScan, _select_decodable_subset

    def scan_with(healthy, k):
        n = len(healthy)
        mat = np.zeros((n + k, k), dtype=np.uint8)  # all-singular (non-MDS)
        return _ChunkScan(
            "f", 100, n + k - k, k, mat, 8, {}, 10, list(healthy), {}
        )

    # C(13,3) = 286 > 100 -> capped
    with pytest.raises(UndecidedSubsetError, match="not proven"):
        _select_decodable_subset(scan_with(range(13), 3))
    # C(4,3) = 4 < 100 -> exhausted, plain ValueError
    with pytest.raises(ValueError, match="among healthy"):
        try:
            _select_decodable_subset(scan_with(range(4), 3))
        except UndecidedSubsetError:
            pytest.fail("exhausted search misreported as capped")


def test_scan_file_decodable_unknown_when_capped(tmp_path, monkeypatch):
    """scan_file surfaces the capped case structurally: decodable='unknown',
    and the scrub CLI exits 1 (not proven healthy)."""
    from gpu_rscode_tpu import api as api_mod
    from gpu_rscode_tpu import cli
    from gpu_rscode_tpu.api import UndecidedSubsetError

    path = _mkfile(tmp_path, 4_000, seed=66)
    api.encode_file(path, 4, 2)

    def capped(scan):
        raise UndecidedSubsetError("cap hit")

    monkeypatch.setattr(api_mod, "_select_decodable_subset", capped)
    report = api.scan_file(path)
    assert report["decodable"] == "unknown"
    assert cli.main(["--scrub", "-i", path]) == 1


# ----- mesh-sharded file layer ----------------------------------------------


def test_mesh_sharded_file_roundtrip_matches_single_device(tmp_path):
    """encode_file over an 8-device (cols) mesh must write byte-identical
    chunks to the single-device path, and decode over the mesh recovers."""
    from gpu_rscode_tpu.parallel.mesh import make_mesh

    path = _mkfile(tmp_path, 70_001, seed=81)
    orig = open(path, "rb").read()
    api.encode_file(path, 4, 2)
    single = [open(chunk_file_name(path, i), "rb").read() for i in range(6)]

    mesh = make_mesh(8)
    api.encode_file(path, 4, 2, mesh=mesh)
    sharded = [open(chunk_file_name(path, i), "rb").read() for i in range(6)]
    assert single == sharded

    conf = make_conf(6, 4, path)
    out = str(tmp_path / "o")
    api.decode_file(path, conf, out, mesh=mesh)
    assert open(out, "rb").read() == orig


@pytest.mark.parametrize("strategy", ["auto", "pallas"])
def test_stripe_sharded_file_roundtrip(tmp_path, strategy):
    """Wide-stripe mode end-to-end at the file layer: the k axis sharded
    over 2 devices, psum carrying the XOR accumulation.  strategy='pallas'
    drives the fused kernel's pre-parity output through the file API."""
    from gpu_rscode_tpu.parallel.mesh import make_mesh

    path = _mkfile(tmp_path, 33_000, seed=82)
    orig = open(path, "rb").read()
    mesh = make_mesh(8, stripe=2)
    api.encode_file(path, 4, 2, mesh=mesh, stripe_sharded=True, strategy=strategy)
    conf = make_conf(6, 4, path)
    out = str(tmp_path / "o")
    api.decode_file(path, conf, out, mesh=mesh, stripe_sharded=True, strategy=strategy)
    assert open(out, "rb").read() == orig


@pytest.mark.parametrize("stripe", [1, 2])
def test_mesh_repair_byte_identical(tmp_path, stripe):
    """Archive repair fans out over the mesh (the reference's multi-GPU
    decode analog, decode.cu:335-378): rebuilt chunks must be byte-identical
    to the single-device goldens, stripe-sharded mode included."""
    from gpu_rscode_tpu.parallel.mesh import make_mesh

    path = _mkfile(tmp_path, 41_003, seed=83)
    api.encode_file(path, 4, 2, checksums=True)
    golden = {i: open(chunk_file_name(path, i), "rb").read() for i in range(6)}
    os.remove(chunk_file_name(path, 4))  # parity lost
    victim = chunk_file_name(path, 0)  # native corrupted
    data = bytearray(golden[0])
    data[100] ^= 0x5A
    open(victim, "wb").write(bytes(data))

    mesh = make_mesh(8, stripe=stripe)
    rebuilt = api.repair_file(
        path, mesh=mesh, stripe_sharded=stripe > 1
    )
    assert rebuilt == [0, 4]
    for i in range(6):
        assert open(chunk_file_name(path, i), "rb").read() == golden[i], i


def test_mesh_auto_decode_roundtrip(tmp_path):
    """auto-decode with the GEMM sharded over the mesh: deleted + corrupt
    chunks excluded, file recovered bit-exactly."""
    from gpu_rscode_tpu.parallel.mesh import make_mesh

    path = _mkfile(tmp_path, 52_000, seed=84)
    orig = open(path, "rb").read()
    api.encode_file(path, 4, 2, checksums=True)
    os.remove(chunk_file_name(path, 1))
    victim = chunk_file_name(path, 3)
    raw = bytearray(open(victim, "rb").read())
    raw[7] ^= 0xFF
    open(victim, "wb").write(bytes(raw))

    out = str(tmp_path / "o")
    mesh = make_mesh(8)
    api.auto_decode_file(path, out, mesh=mesh)
    assert open(out, "rb").read() == orig
    chosen = open(path + ".auto.conf").read()
    assert "_1_" not in chosen and "_3_" not in chosen


def test_auto_strategy_detects_tpu_by_device_platform(monkeypatch):
    """strategy='auto' resolves by the default device's platform: a TPU
    device gets the fused kernel, anything else the bitplane path."""
    import gpu_rscode_tpu.codec as codec_mod

    class _FakeDev:
        platform = "tpu"

    monkeypatch.setattr(codec_mod.jax, "devices", lambda: [_FakeDev()])
    assert codec_mod._tpu_devices_present() is True
    c = codec_mod.RSCodec(4, 2, strategy="auto")
    assert c.strategy == "pallas"

    _FakeDev.platform = "cpu"
    assert codec_mod._tpu_devices_present() is False
    assert codec_mod.RSCodec(4, 2, strategy="auto").strategy == "bitplane"


def test_device_platform_reads_default_device(monkeypatch):
    """device_platform is the default device's platform, whatever the
    backend calls itself; a backend that cannot start raises instead of
    passing for a CPU."""
    import jax

    from gpu_rscode_tpu.utils import backend as b

    class _FakeDev:
        platform = "tpu"

    monkeypatch.setattr(jax, "default_backend", lambda: "other")
    monkeypatch.setattr(jax, "devices", lambda: [_FakeDev()])
    assert b.device_platform() == "tpu"
    assert b.tpu_devices_present() is True

    def _boom():
        raise RuntimeError("uninitialisable")

    monkeypatch.setattr(jax, "devices", _boom)
    with pytest.raises(RuntimeError, match="uninitialisable"):
        b.tpu_devices_present()


def test_decode_zero_size_foreign_archive(tmp_path):
    # A reference encode of an empty file: totalSize=0 sizes-only metadata
    # plus zero-byte chunks (cpu-rs.c:492-495 has no empty-file guard).
    # Decode must rebuild the empty original, not crash on empty memmaps.
    f = str(tmp_path / "empty.bin")
    (tmp_path / "empty.bin.METADATA").write_text("0 2 4\n")
    for i in range(6):
        (tmp_path / f"_{i}_empty.bin").write_bytes(b"")
    conf = str(tmp_path / "conf")
    with open(conf, "w") as fp:
        fp.write("".join(f"_{i}_empty.bin\n" for i in range(4)))
    out = api.decode_file(f, conf, str(tmp_path / "out.bin"))
    assert os.path.getsize(out) == 0
    # Overwrite-input default path too (no pre-existing in_file needed).
    out2 = api.decode_file(f, conf)
    assert out2 == f and os.path.getsize(f) == 0


def test_zero_size_foreign_archive_repair_scrub_auto(tmp_path):
    # The same zero-byte foreign archive through the archive-maintenance
    # surface: scrub reports it decodable, repair recreates deleted chunks
    # (as empty files), auto-decode rebuilds the empty original.
    f = str(tmp_path / "empty.bin")
    (tmp_path / "empty.bin.METADATA").write_text("0 2 4\n")
    for i in range(6):
        (tmp_path / f"_{i}_empty.bin").write_bytes(b"")
    report = api.scan_file(f)
    assert report["decodable"] is True and report["missing"] == []
    os.remove(str(tmp_path / "_1_empty.bin"))
    os.remove(str(tmp_path / "_5_empty.bin"))
    assert api.repair_file(f) == [1, 5]
    for i in (1, 5):
        p = str(tmp_path / f"_{i}_empty.bin")
        assert os.path.exists(p) and os.path.getsize(p) == 0
    out = api.auto_decode_file(f, str(tmp_path / "out.bin"))
    assert os.path.getsize(out) == 0


def test_zero_size_decode_still_enforces_contracts(tmp_path):
    # The fast path must not skip validation: a conf naming absent chunks
    # fails, and verify_checksums=True without CRC lines fails.
    f = str(tmp_path / "empty.bin")
    (tmp_path / "empty.bin.METADATA").write_text("0 2 4\n")
    for i in range(4):
        (tmp_path / f"_{i}_empty.bin").write_bytes(b"")
    conf = str(tmp_path / "conf")
    with open(conf, "w") as fp:
        fp.write("".join(f"_{i}_empty.bin\n" for i in range(4)))
    with pytest.raises(ValueError, match="no checksum lines"):
        api.decode_file(f, conf, str(tmp_path / "o"), verify_checksums=True)
    badconf = str(tmp_path / "badconf")
    with open(badconf, "w") as fp:
        fp.write("_0_empty.bin\n_1_empty.bin\n_2_empty.bin\n_9_nope.bin\n")
    with pytest.raises(FileNotFoundError):
        api.decode_file(f, badconf, str(tmp_path / "o"))


def test_zero_size_repair_enforces_k_healthy(tmp_path):
    # Repairability must match scan_file's decodable verdict: a zero-size
    # archive with fewer than k healthy chunks cannot produce a valid
    # k-chunk conf, so repair refuses it too (no zero-survivor rebuild).
    f = str(tmp_path / "empty.bin")
    (tmp_path / "empty.bin.METADATA").write_text("0 2 4\n")
    for i in range(3):  # only 3 of the k=4 needed
        (tmp_path / f"_{i}_empty.bin").write_bytes(b"")
    assert api.scan_file(f)["decodable"] is False
    with pytest.raises(ValueError, match="healthy"):
        api.repair_file(f)


def test_repair_fleet_batched_inversion(tmp_path):
    """Fleet scrub-and-repair: all survivor inversions of a (k, w) config
    solved in one batched on-device dispatch, rebuilds byte-identical."""
    from gpu_rscode_tpu.utils.fileformat import metadata_file_name

    configs = [(4, 2, 5000), (4, 2, 7001), (6, 3, 9000)]
    paths, golden = [], {}
    for k, p, size in configs:
        path = _mkfile(tmp_path, size, seed=size)
        api.encode_file(path, k, p, checksums=True)
        paths.append((path, k, p))
        golden[path] = {
            i: open(chunk_file_name(path, i), "rb").read()
            for i in range(k + p)
        }
    # Damage: archive0 loses two chunks, archive1 gets one corrupted,
    # archive2 stays healthy.
    os.remove(chunk_file_name(paths[0][0], 0))
    os.remove(chunk_file_name(paths[0][0], 5))
    with open(chunk_file_name(paths[1][0], 2), "r+b") as fp:
        fp.seek(3)
        b = fp.read(1)[0]
        fp.seek(3)
        fp.write(bytes([b ^ 0xFF]))

    from gpu_rscode_tpu.ops import inverse as inverse_mod

    calls = []
    real_batch = inverse_mod.invert_matrix_jax_batch

    def counting_batch(Ms, w=8, **kw):
        calls.append(np.asarray(Ms).shape)
        return real_batch(Ms, w, **kw)

    import gpu_rscode_tpu.api as api_mod
    old = inverse_mod.invert_matrix_jax_batch
    inverse_mod.invert_matrix_jax_batch = counting_batch
    try:
        results = api.repair_fleet([p for p, _, _ in paths])
    finally:
        inverse_mod.invert_matrix_jax_batch = old

    assert results[paths[0][0]] == [0, 5]
    assert results[paths[1][0]] == [2]
    assert results[paths[2][0]] == []
    # Two damaged archives share (k=4, w=8): ONE batched dispatch of 2.
    assert calls == [(2, 4, 4)], calls
    for path, k, p in paths:
        for i in range(k + p):
            assert (
                open(chunk_file_name(path, i), "rb").read() == golden[path][i]
            ), f"{path} chunk {i}"


def test_repair_fleet_mixed_widths(tmp_path):
    """A fleet mixing GF(2^8) and GF(2^16) archives groups by (k, w) and
    rebuilds each byte-identically — the wide-symbol field goes through
    the same batched no-pivot inversion path (tables(16) gathers)."""
    a = _mkfile(tmp_path, 6000, seed=41)
    b = _mkfile(tmp_path, 6002, seed=42)  # even size: w=16 symbol-aligned
    api.encode_file(a, 4, 2, checksums=True)
    api.encode_file(b, 4, 2, w=16, checksums=True)
    golden = {
        p: {i: open(chunk_file_name(p, i), "rb").read() for i in range(6)}
        for p in (a, b)
    }
    os.remove(chunk_file_name(a, 0))
    os.remove(chunk_file_name(b, 1))
    os.remove(chunk_file_name(b, 3))

    results = api.repair_fleet([a, b])
    assert results == {a: [0], b: [1, 3]}
    for p in (a, b):
        for i in range(6):
            assert (
                open(chunk_file_name(p, i), "rb").read() == golden[p][i]
            ), f"{p} chunk {i}"


def test_repair_fleet_deep_k_routes_to_host_on_tpu(tmp_path, monkeypatch):
    """Measured routing (bench_captures/inverse_nopivot_tpu_20260801T*):
    on TPU backends the batched device inverter loses at every measured
    k=128 batch, so depths where _device_invert_min_batch_tpu returns
    None take the per-archive host path instead of the device batch."""
    from gpu_rscode_tpu.ops import inverse as inverse_mod
    from gpu_rscode_tpu.utils import backend as backend_mod
    import gpu_rscode_tpu.api as api_mod

    path = _mkfile(tmp_path, 5000, seed=77)
    api.encode_file(path, 4, 2, checksums=True)
    golden = {
        i: open(chunk_file_name(path, i), "rb").read() for i in range(6)
    }
    os.remove(chunk_file_name(path, 1))

    # Pretend this is a TPU backend where k=4 counts as "deep" (the
    # routing function returns None), but keep the GEMM on the CPU-safe
    # bitplane strategy (the interpret gate is pallas-only, so
    # tpu_devices_present=True must not reach a compile).
    monkeypatch.setattr(backend_mod, "tpu_devices_present", lambda: True)
    monkeypatch.setattr(
        api_mod,
        "_device_invert_min_batch_tpu",
        lambda k: None if k > 2 else 1,
    )

    def forbidden_batch(Ms, w=8):
        raise AssertionError(
            "device batch dispatched for a deep-k group on a TPU backend"
        )

    monkeypatch.setattr(
        inverse_mod, "invert_matrix_jax_batch", forbidden_batch
    )
    results = api.repair_fleet([path], strategy="bitplane")
    assert results == {path: [1]}
    for i in range(6):
        assert open(chunk_file_name(path, i), "rb").read() == golden[i]


def test_device_invert_routing_matches_committed_capture():
    """Evidence lock: _device_invert_min_batch_tpu must agree with the
    committed k x batch grid it cites
    (bench_captures/inverse_nopivot_tpu_20260801T001751Z.jsonl).  Every
    measured cell the function routes to the DEVICE must have measured a
    device win (speedup >= 1), and a depth the function host-routes
    entirely (None) must have lost every measured cell — so the
    thresholds cannot drift from the capture without re-measurement."""
    import json
    import pathlib

    import gpu_rscode_tpu.api as api_mod

    cap = (
        pathlib.Path(__file__).resolve().parents[1]
        / "bench_captures"
        / "inverse_nopivot_tpu_20260801T001751Z.jsonl"
    )
    cells = [
        json.loads(line)
        for line in cap.read_text().splitlines()
        if line.startswith("{")
    ]
    assert len(cells) >= 12  # the 4x4 grid minus any wedged tail
    by_k: dict[int, dict[int, float]] = {}
    for c in cells:
        by_k.setdefault(c["k"], {})[c["batch"]] = c["speedup_vs_host_loop"]
    for k, batches in by_k.items():
        min_batch = api_mod._device_invert_min_batch_tpu(k)
        if min_batch is None:
            assert all(s < 1.0 for s in batches.values()), (k, batches)
        else:
            device_cells = {
                b: s for b, s in batches.items() if b >= min_batch
            }
            assert device_cells, (k, min_batch, batches)
            assert all(s >= 1.0 for s in device_cells.values()), (
                k, min_batch, device_cells,
            )
            # Pin the threshold from below too: the largest measured
            # batch the function host-routes must have measured a LOSS,
            # else the threshold drifted upward past a measured win.
            host_cells = [b for b in batches if b < min_batch]
            if host_cells:
                assert batches[max(host_cells)] < 1.0, (
                    k, min_batch, batches,
                )


def test_repair_fleet_small_batch_routes_to_host_on_tpu(tmp_path, monkeypatch):
    """Measured routing (ADVICE r4 / inverse_nopivot_tpu_20260801T*): the
    device dispatch loses at small batches for every k (the ~0.14 s flat
    dispatch floor), and a typical scrub damages few archives per (k, w)
    group — so groups below _device_invert_min_batch_tpu(k) take the host
    path on TPU backends."""
    from gpu_rscode_tpu.ops import inverse as inverse_mod
    from gpu_rscode_tpu.utils import backend as backend_mod
    import gpu_rscode_tpu.api as api_mod

    path = _mkfile(tmp_path, 5000, seed=78)
    api.encode_file(path, 4, 2, checksums=True)
    os.remove(chunk_file_name(path, 1))

    monkeypatch.setattr(backend_mod, "tpu_devices_present", lambda: True)
    # k=4 must be device-eligible (min batch not None) so that the
    # 1-archive group is rejected by the BATCH gate specifically.
    min_batch = api_mod._device_invert_min_batch_tpu(4)
    assert min_batch is not None and min_batch > 1

    def forbidden_batch(Ms, w=8, **kw):
        raise AssertionError(
            "device batch dispatched for a 1-archive group on a TPU backend"
        )

    monkeypatch.setattr(
        inverse_mod, "invert_matrix_jax_batch", forbidden_batch
    )
    assert api.repair_fleet([path], strategy="bitplane") == {path: [1]}


def test_repair_fleet_device_batch_uses_nopivot(tmp_path, monkeypatch):
    """When the device batch IS dispatched it must run the scan-free
    elimination (pivot=False) — the verify-and-fallback below it makes
    that safe; on TPU it is perf-neutral vs pivoting (the r5 capture
    refuted the pivot-scan theory of the k=128 loss) and on CPU it wins,
    so it stays the dispatch."""
    from gpu_rscode_tpu.ops import inverse as inverse_mod

    paths = []
    for s in range(2):
        p = _mkfile(tmp_path, 3000 + s, seed=90 + s)
        api.encode_file(p, 4, 2, checksums=True)
        os.remove(chunk_file_name(p, 1))
        paths.append(p)

    seen = {}
    real = inverse_mod.invert_matrix_jax_batch

    def spy(Ms, w=8, *, pivot=True):
        seen["pivot"] = pivot
        return real(Ms, w, pivot=pivot)

    # repair_fleet imports the symbol at call time from ops.inverse, so
    # patching the module attribute intercepts the production dispatch.
    monkeypatch.setattr(inverse_mod, "invert_matrix_jax_batch", spy)
    results = api.repair_fleet(paths, strategy="bitplane")
    assert results == {p: [1] for p in paths}
    assert seen["pivot"] is False


def test_repair_fleet_all_or_nothing(tmp_path):
    """An unrecoverable archive anywhere in the fleet aborts the whole pass
    before any rebuild is written."""
    a = _mkfile(tmp_path, 4000, seed=1)
    b = _mkfile(tmp_path, 6000, seed=2)
    api.encode_file(a, 4, 2)
    api.encode_file(b, 4, 2)
    os.remove(chunk_file_name(a, 1))          # recoverable damage
    for i in range(3):                         # unrecoverable: 3 of 6 gone
        os.remove(chunk_file_name(b, i))
    with pytest.raises(ValueError, match="unrecoverable archives"):
        api.repair_fleet([a, b])
    # All-or-nothing: a's damaged chunk was NOT rebuilt.
    assert not os.path.exists(chunk_file_name(a, 1))
    # Repairing only the healthy-enough archive then succeeds.
    assert api.repair_fleet([a]) == {a: [1]}
    assert os.path.exists(chunk_file_name(a, 1))


def test_repair_fleet_zero_size_all_or_nothing(tmp_path):
    """An unrecoverable ZERO-SIZE archive must abort the fleet pass during
    validation (before any rebuild), same as a normal unrecoverable one."""
    a = _mkfile(tmp_path, 4000, seed=11)
    api.encode_file(a, 4, 2)
    os.remove(chunk_file_name(a, 1))  # recoverable damage
    z = str(tmp_path / "empty.bin")
    (tmp_path / "empty.bin.METADATA").write_text("0 2 4\n")
    for i in range(3):  # 3 healthy < k=4 and chunk 5 missing -> unhealthy
        (tmp_path / f"_{i}_empty.bin").write_bytes(b"")
    with pytest.raises(ValueError, match="unrecoverable archives"):
        api.repair_fleet([a, z])
    assert not os.path.exists(chunk_file_name(a, 1))  # nothing repaired
