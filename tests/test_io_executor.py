"""Write-behind drain executor + fleet pipeline overlap semantics.

Covers the 5th pipeline stage (docs/IO.md): DrainExecutor ordering /
backpressure / error relay, AsyncWindow delegation, the prefetcher
context guard, drain-path equivalence (sync vs write-behind byte
identity, CRC metadata consistency), slow-writer backpressure, writer
exception propagation through the file APIs, and the fleet entry points.
"""

import os
import threading
import time
import zlib

import numpy as np
import pytest

from gpu_rscode_tpu import api
from gpu_rscode_tpu.parallel.io_executor import (
    DrainExecutor,
    FleetPipeline,
    run_rows,
)
from gpu_rscode_tpu.parallel.pipeline import AsyncWindow, SegmentPrefetcher
from gpu_rscode_tpu.tools.make_conf import make_conf
from gpu_rscode_tpu.utils.fileformat import (
    chunk_file_name,
    metadata_file_name,
    read_metadata_ext,
)


# ---- DrainExecutor unit semantics ------------------------------------------


def test_executor_ordered_commits_fifo():
    got = []
    with DrainExecutor(workers=3, ordered=True) as ex:
        assert ex.workers == 1  # ordered clamps to one consumer
        for i in range(20):
            ex.submit(lambda i=i: got.append(i))
    assert got == list(range(20))


def test_executor_unordered_runs_everything():
    got = []
    lock = threading.Lock()

    def task(i):
        with lock:
            got.append(i)

    with DrainExecutor(workers=3, ordered=False) as ex:
        for i in range(30):
            ex.submit(lambda i=i: task(i))
    assert sorted(got) == list(range(30))


def test_executor_sync_mode_runs_inline():
    got = []
    ex = DrainExecutor(workers=0)
    ex.submit(lambda: got.append(threading.current_thread().name))
    assert got == [threading.current_thread().name]
    ex.flush()  # no-op, no error


def test_executor_backpressure_bounds_queue():
    """With depth=2 and a slow worker, submit must block rather than
    queue unboundedly: at most depth tasks wait behind the running one."""
    release = threading.Event()
    peak = []

    def slow():
        release.wait(timeout=10)

    with DrainExecutor(workers=1, depth=2) as ex:
        t0 = time.perf_counter()
        ex.submit(slow)          # picked up by the worker
        ex.submit(lambda: None)  # queue slot 1
        ex.submit(lambda: None)  # queue slot 2
        assert time.perf_counter() - t0 < 1.0  # none of those blocked

        blocked = threading.Event()

        def fourth():
            ex.submit(lambda: peak.append("ran"))
            blocked.set()

        t = threading.Thread(target=fourth, daemon=True)
        t.start()
        assert not blocked.wait(timeout=0.3)  # queue full: submit blocks
        release.set()
        assert blocked.wait(timeout=10)
        t.join(timeout=10)
    assert peak == ["ran"]


def test_executor_error_reraises_at_submit_and_flush():
    with pytest.raises(OSError, match="disk gone"):
        with DrainExecutor(workers=1) as ex:
            ex.submit(lambda: (_ for _ in ()).throw(OSError("disk gone")))
            # The error surfaces at the next touch point: keep submitting
            # until the latched exception re-raises (or flush at exit).
            for _ in range(50):
                ex.submit(lambda: None)
                time.sleep(0.01)
            ex.flush()


def test_executor_flush_reraises_without_further_submits():
    ex = DrainExecutor(workers=1)
    with pytest.raises(ValueError, match="boom"):
        with ex:
            ex.submit(lambda: (_ for _ in ()).throw(ValueError("boom")))
            # clean exit path: __exit__ flush must re-raise


def test_executor_exceptional_exit_cancels_queued():
    ran = []
    release = threading.Event()
    with pytest.raises(RuntimeError, match="dispatch died"):
        with DrainExecutor(workers=1, depth=8) as ex:
            ex.submit(lambda: release.wait(timeout=10))
            for i in range(5):
                ex.submit(lambda i=i: ran.append(i))
            release.set()
            raise RuntimeError("dispatch died")
    # the in-flight task finished; queued ones were discarded (the stream
    # already failed — committing more segments would be wrong)
    assert ran == [] or len(ran) < 5


def test_executor_error_cancels_queue_before_reraise():
    """Once _check_error re-raises, nothing still queued may run — in a
    fleet the queued task can be an archive's finalize/promote, and
    committing an archive whose drain failed would leave a
    complete-looking but corrupt archive (review finding, PR 3)."""
    ran = []
    gate = threading.Event()
    with pytest.raises(OSError, match="disk gone"):
        with DrainExecutor(workers=2, ordered=False, depth=8) as ex:
            def fail_then_park():
                raise OSError("disk gone")

            ex.submit(fail_then_park)
            ex.submit(lambda: gate.wait(timeout=1))  # parks worker B
            ex.submit(lambda: ran.append("late"))    # queued behind both
            # Wait for the error to latch, then touch the executor: the
            # re-raise must cancel the queue in the same step.
            deadline = time.time() + 5
            while time.time() < deadline:
                time.sleep(0.01)
                ex.submit(lambda: None)  # raises once the error latched
            pytest.fail("error never surfaced")
    gate.set()
    time.sleep(0.1)
    assert ran == []  # the queued task never ran after the re-raise


def test_executor_submit_outside_context_raises():
    ex = DrainExecutor(workers=1)
    with pytest.raises(RuntimeError, match="context manager"):
        ex.submit(lambda: None)


def test_fleet_pipeline_rejects_unordered_lane():
    with pytest.raises(ValueError, match="ordered"):
        FleetPipeline(DrainExecutor(workers=2, ordered=False))


def test_fleet_pipeline_commit_order_and_abort():
    events = []
    pipe = FleetPipeline(DrainExecutor(ordered=True))
    with pipe.executor:
        k1 = pipe.register(lambda: events.append("cleanup1"))
        pipe.executor.submit(lambda: events.append("write1"))
        pipe.commit(k1, lambda: events.append("final1"))
        k2 = pipe.register(lambda: events.append("cleanup2"))
        pipe.executor.submit(lambda: events.append("write2"))
        pipe.commit(k2, lambda: events.append("final2"))
    pipe.abort()  # both finalizes succeeded: nothing left to clean
    assert events == ["write1", "final1", "write2", "final2"]


def test_fleet_pipeline_abort_runs_uncommitted_cleanups():
    events = []
    pipe = FleetPipeline(DrainExecutor(workers=0))
    pipe.register(lambda: events.append("cleanup"))
    pipe.abort()
    assert events == ["cleanup"]


def test_run_rows_parallel_and_error(monkeypatch):
    monkeypatch.setenv("RS_IO_READERS", "3")
    out = [0] * 16
    run_rows(16, lambda i: out.__setitem__(i, i * i))
    assert out == [i * i for i in range(16)]
    with pytest.raises(OSError, match="pread"):
        run_rows(8, lambda i: (_ for _ in ()).throw(OSError("pread")))


# ---- AsyncWindow + executor ------------------------------------------------


def test_window_delegates_drain_to_executor():
    drained = []
    with DrainExecutor(workers=1) as ex:
        with AsyncWindow(2, lambda t, f: drained.append((t, f)), executor=ex) as w:
            for i in range(5):
                w.push(i, f"f{i}")
        ex.flush()
        assert drained == [(i, f"f{i}") for i in range(5)]


def test_window_abort_resets_inflight_gauge():
    """Satellite: an aborting window must not leave rs_pipeline_inflight
    frozen at its last nonzero value."""
    from gpu_rscode_tpu.obs import metrics as obs_metrics

    obs_metrics.force_enable()
    try:
        obs_metrics.REGISTRY.reset()
        with pytest.raises(RuntimeError):
            with AsyncWindow(4, lambda t, f: None) as w:
                w.push(0, "a")
                w.push(1, "b")
                raise RuntimeError("dispatch died")
        gauge = obs_metrics.REGISTRY.gauge("rs_pipeline_inflight")
        assert gauge.value == 0
    finally:
        obs_metrics.force_enable(False)
        obs_metrics.REGISTRY.reset()


def test_prefetcher_outside_context_raises():
    """Satellite: __next__ without the context manager must raise instead
    of blocking forever on the never-fed queue."""
    pf = SegmentPrefetcher([(0, 1)], lambda off, cols: off)
    with pytest.raises(RuntimeError, match="context manager"):
        next(pf)


# ---- drain-path equivalence through the file APIs --------------------------


def _make_file(tmp_path, name="f.bin", size=300_000, seed=3):
    path = str(tmp_path / name)
    rng = np.random.default_rng(seed)
    data = rng.integers(0, 256, size=size, dtype=np.uint8).tobytes()
    with open(path, "wb") as fp:
        fp.write(data)
    return path, data


@pytest.mark.parametrize("writers", ["0", "2"])
def test_roundtrip_byte_identical_and_crc_consistent(
    tmp_path, monkeypatch, writers
):
    """Encode -> decode round-trips byte-identical with write-behind on
    and off, and the # crc32 metadata lines match the actual chunk bytes
    (the incremental CRC accumulated on the writer lane must equal a
    post-hoc CRC of the files)."""
    monkeypatch.setenv("RS_IO_WRITERS", writers)
    path, data = _make_file(tmp_path)
    api.encode_file(path, 4, 2, segment_bytes=64 * 1024, checksums=True)
    _, _, _, _, _, crcs = read_metadata_ext(metadata_file_name(path))
    assert sorted(crcs) == list(range(6))
    for i in range(6):
        with open(chunk_file_name(path, i), "rb") as fp:
            assert zlib.crc32(fp.read()) == crcs[i], f"chunk {i} crc"
    conf = make_conf(6, 4, path)
    out = str(tmp_path / "out.bin")
    api.decode_file(path, conf, out)
    with open(out, "rb") as fp:
        assert fp.read() == data


def test_sync_and_writebehind_chunks_identical(tmp_path, monkeypatch):
    """The same encode with RS_IO_WRITERS=0 and =2 must produce identical
    chunk bytes and .METADATA."""
    path, _ = _make_file(tmp_path)
    outputs = {}
    for writers in ("0", "2"):
        monkeypatch.setenv("RS_IO_WRITERS", writers)
        api.encode_file(path, 4, 3, segment_bytes=64 * 1024, checksums=True)
        outputs[writers] = [
            open(chunk_file_name(path, i), "rb").read() for i in range(7)
        ] + [open(metadata_file_name(path), "rb").read()]
    assert outputs["0"] == outputs["2"]


def test_slow_writer_backpressure_still_correct(tmp_path, monkeypatch):
    """An induced slow writer (every row-scatter write sleeps) forces the
    dispatch loop into backpressure; bytes must still be correct."""
    monkeypatch.setenv("RS_IO_WRITERS", "1")
    monkeypatch.setenv("RS_IO_WRITE_DEPTH", "1")
    from gpu_rscode_tpu import native

    real = native.scatter_write

    def slow_scatter(files, arr, off, **kw):
        time.sleep(0.05)
        return real(files, arr, off, **kw)

    monkeypatch.setattr(native, "scatter_write", slow_scatter)
    path, data = _make_file(tmp_path, size=200_000)
    api.encode_file(path, 4, 2, segment_bytes=32 * 1024, checksums=True)
    monkeypatch.setattr(native, "scatter_write", real)
    conf = make_conf(6, 4, path)
    out = str(tmp_path / "out.bin")
    api.decode_file(path, conf, out)
    assert open(out, "rb").read() == data


def test_writer_exception_fails_encode_atomically(tmp_path, monkeypatch):
    """A writer-side exception (disk error mid-parity-write) must
    propagate out of encode_file and leave no partial outputs — same
    contract as a dispatch-side failure."""
    monkeypatch.setenv("RS_IO_WRITERS", "1")
    from gpu_rscode_tpu import native

    calls = []
    real = native.scatter_write

    def failing_scatter(files, arr, off, **kw):
        calls.append(1)
        if len(calls) >= 2:
            raise OSError("disk gone (writer lane)")
        return real(files, arr, off, **kw)

    monkeypatch.setattr(native, "scatter_write", failing_scatter)
    path, _ = _make_file(tmp_path)
    with pytest.raises(OSError, match="disk gone"):
        api.encode_file(path, 4, 2, segment_bytes=32 * 1024, checksums=True)
    leftovers = sorted(
        f for f in os.listdir(tmp_path) if f != os.path.basename(path)
    )
    assert leftovers == []


def test_writer_exception_fails_decode_and_cleans_tmp(tmp_path, monkeypatch):
    monkeypatch.setenv("RS_IO_WRITERS", "1")
    path, _ = _make_file(tmp_path)
    api.encode_file(path, 4, 2, segment_bytes=64 * 1024)
    conf = make_conf(6, 4, path)
    out = str(tmp_path / "out.bin")

    import gpu_rscode_tpu.api as api_mod

    real = np.asarray
    calls = []

    def failing_asarray(x, *a, **kw):
        if hasattr(x, "devices"):  # only the drain's D2H materialisation
            calls.append(1)
            if len(calls) >= 2:
                raise OSError("D2H wedged")
        return real(x, *a, **kw)

    monkeypatch.setattr(api_mod.np, "asarray", failing_asarray)
    with pytest.raises(OSError, match="D2H wedged"):
        api.decode_file(path, conf, out, segment_bytes=64 * 1024)
    monkeypatch.setattr(api_mod.np, "asarray", real)
    assert not os.path.exists(out + ".rs_tmp")
    assert not os.path.exists(out)


# ---- fleet entry points ----------------------------------------------------


def _damaged_fleet(tmp_path, count=4, k=4, p=2):
    files = []
    for i in range(count):
        path, data = _make_file(
            tmp_path, name=f"a{i}.bin", size=150_000 + 7 * i, seed=i
        )
        api.encode_file(path, k, p, segment_bytes=32 * 1024, checksums=True)
        os.unlink(chunk_file_name(path, 0))
        os.unlink(chunk_file_name(path, k))
        files.append((path, data))
    return files


@pytest.mark.parametrize("writers", ["0", "2"])
def test_repair_fleet_interleaved_correct(tmp_path, monkeypatch, writers):
    monkeypatch.setenv("RS_IO_WRITERS", writers)
    files = _damaged_fleet(tmp_path)
    results = api.repair_fleet([f for f, _ in files])
    for path, data in files:
        assert results[path] == [0, 4]
        # rebuilt chunks decode back to the original bytes
        conf = make_conf(6, 4, path)
        out = path + ".dec"
        api.decode_file(path, conf, out)
        assert open(out, "rb").read() == data


def test_repair_fleet_failure_cleans_pending_tmps(tmp_path, monkeypatch):
    """A failure mid-fleet must not leave .rs_tmp litter for archives
    whose commit had not run yet."""
    monkeypatch.setenv("RS_IO_WRITERS", "1")
    files = _damaged_fleet(tmp_path, count=3)
    from gpu_rscode_tpu import native

    real = native.scatter_write
    calls = []

    def failing_scatter(fps, arr, off):
        calls.append(1)
        if len(calls) >= 4:
            raise OSError("fleet disk gone")
        return real(fps, arr, off)

    monkeypatch.setattr(native, "scatter_write", failing_scatter)
    with pytest.raises(OSError, match="fleet disk gone"):
        api.repair_fleet(
            [f for f, _ in files], segment_bytes=32 * 1024
        )
    monkeypatch.setattr(native, "scatter_write", real)
    litter = [
        f for f in os.listdir(tmp_path) if f.endswith(".rs_tmp")
    ]
    assert litter == []


def test_encode_fleet_and_decode_fleet_roundtrip(tmp_path, monkeypatch):
    monkeypatch.setenv("RS_IO_WRITERS", "2")
    files = []
    for i in range(3):
        path, data = _make_file(
            tmp_path, name=f"b{i}.bin", size=120_000 + i, seed=10 + i
        )
        files.append((path, data))
    written = api.encode_fleet(
        [f for f, _ in files], 4, 2, checksums=True,
        segment_bytes=32 * 1024,
    )
    assert set(written) == {f for f, _ in files}
    for path, _ in files:
        assert os.path.exists(metadata_file_name(path))
    outs = {f: f + ".dec" for f, _ in files}
    results = api.decode_fleet([f for f, _ in files], outs)
    for path, data in files:
        assert results[path] == outs[path]
        assert open(outs[path], "rb").read() == data


def test_encode_fleet_failure_cleans_up(tmp_path, monkeypatch):
    """First file encodes, second file fails mid-stream: the fleet raises,
    file 1's archive is committed, file 2 leaves no temps or chunks."""
    monkeypatch.setenv("RS_IO_WRITERS", "1")
    p1, _ = _make_file(tmp_path, name="ok.bin", seed=1)
    p2, _ = _make_file(tmp_path, name="bad.bin", seed=2)
    from gpu_rscode_tpu.codec import RSCodec

    real = RSCodec.encode
    state = {"file_done": False}

    def boom(self, data):
        if state["file_done"]:
            raise RuntimeError("device fell over on file 2")
        return real(self, data)

    monkeypatch.setattr(RSCodec, "encode", boom)
    orig_encode_file = api.encode_file

    def tracking_encode(f, *a, **kw):
        out = orig_encode_file(f, *a, **kw)
        state["file_done"] = True
        return out

    monkeypatch.setattr(api, "encode_file", tracking_encode)
    with pytest.raises(RuntimeError, match="file 2"):
        api.encode_fleet([p1, p2], 4, 2, segment_bytes=32 * 1024)
    # file 1 fully committed
    assert os.path.exists(metadata_file_name(p1))
    # file 2: nothing (no chunks, no metadata, no temps)
    bad_litter = [
        f for f in os.listdir(tmp_path)
        if "bad.bin" in f and f != "bad.bin"
    ]
    assert bad_litter == []
