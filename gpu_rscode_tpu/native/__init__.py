"""ctypes bindings for the native host runtime (rs_native.cpp).

The shared library is built on first use with g++, next to the source,
under a name keyed on the source's hash, the build flags and the host's
CPU flags: a ``-march=native`` build from another host or another
revision of the source is never loaded.  Every entry point has a NumPy
fallback so the framework works on machines without a toolchain — the
native path is a performance feature, the Python path is the contract.

Maps the reference's native host layer: CPU codec oracle (cpu-rs.c), host
inverter (cpu-decode.c), staging copies (encode.cu:389-398).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import platform
import subprocess
import threading
import time

import numpy as np

from ..obs import metrics as _obs_metrics

_DIR = os.path.dirname(__file__)
_SRC = os.path.join(_DIR, "rs_native.cpp")
_CXXFLAGS = ("-O3", "-march=native", "-shared", "-fPIC", "-std=c++17")
_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_build_failed = False


class NativeUnavailable(RuntimeError):
    pass


def _count_io(direction: str, call: str, nbytes: int, seconds: float) -> None:
    """rs_io_* accounting for one staging call (no-op unless RS_METRICS).
    ``direction`` is "read" or "write"; ``call`` labels the staging
    primitive so read and write balances stay attributable per path."""
    _obs_metrics.counter(
        f"rs_io_{direction}_bytes_total",
        f"bytes {direction} by the staging-I/O layer",
    ).labels(call=call).inc(nbytes)
    _obs_metrics.counter(
        f"rs_io_{direction}_seconds_total",
        f"wall seconds in staging-I/O {direction} calls",
    ).labels(call=call).inc(seconds)


def _host_cpu_flags() -> str:
    """The CPU feature flags ``-march=native`` compiles against."""
    try:
        with open("/proc/cpuinfo") as fp:
            for line in fp:
                if line.startswith(("flags", "Features")):
                    return line.split(":", 1)[1].strip()
    except OSError:
        pass
    return platform.machine()


def lib_path() -> str:
    """Where the library built from this source on this host lives."""
    h = hashlib.sha256()
    with open(_SRC, "rb") as fp:
        h.update(fp.read())
    h.update(" ".join(_CXXFLAGS).encode())
    h.update(_host_cpu_flags().encode())
    return os.path.join(_DIR, f"librs_native-{h.hexdigest()[:16]}.so")


def _build(so: str) -> str:
    # Compile to a pid-suffixed temp and atomically rename so concurrent
    # processes never dlopen a half-written .so.
    tmp = f"{so}.{os.getpid()}.tmp"
    cmd = ["g++", *_CXXFLAGS, "-o", tmp, _SRC, "-lpthread"]
    try:
        subprocess.run(cmd, check=True, capture_output=True, text=True)
        os.replace(tmp, so)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    return so


def get_lib() -> ctypes.CDLL:
    """Load (building if needed) the native library; raises
    NativeUnavailable if no toolchain."""
    global _lib, _build_failed
    with _lock:
        if _lib is not None:
            return _lib
        if _build_failed:
            raise NativeUnavailable("native build failed earlier this session")
        try:
            so = lib_path()
            if not os.path.exists(so):
                _build(so)
            lib = ctypes.CDLL(so)
        except (OSError, subprocess.CalledProcessError) as e:
            _build_failed = True
            raise NativeUnavailable(f"cannot build/load rs_native: {e}") from e

        u8p = np.ctypeslib.ndpointer(dtype=np.uint8, flags="C_CONTIGUOUS")
        lib.rs_gf_init.restype = ctypes.c_int
        lib.rs_gemm.argtypes = [u8p, u8p, u8p, ctypes.c_int, ctypes.c_int,
                                ctypes.c_longlong, ctypes.c_int]
        lib.rs_gemm.restype = None
        lib.rs_invert.argtypes = [u8p, u8p, ctypes.c_int]
        lib.rs_invert.restype = ctypes.c_int
        lib.rs_stripe_read.argtypes = [
            ctypes.c_char_p, u8p, ctypes.c_longlong, ctypes.c_int,
            ctypes.c_longlong, ctypes.c_longlong, ctypes.c_longlong,
        ]
        lib.rs_stripe_read.restype = ctypes.c_longlong
        lib.rs_scatter_write.argtypes = [
            ctypes.POINTER(ctypes.c_int), u8p, ctypes.c_int,
            ctypes.c_longlong, ctypes.c_longlong,
        ]
        lib.rs_scatter_write.restype = ctypes.c_int
        lib.rs_gather_rows.argtypes = [
            ctypes.POINTER(ctypes.c_int), u8p, ctypes.c_int,
            ctypes.c_longlong, ctypes.c_longlong,
        ]
        lib.rs_gather_rows.restype = ctypes.c_int
        lib.rs_gf_init()
        _lib = lib
        return lib


def available() -> bool:
    try:
        get_lib()
        return True
    except NativeUnavailable:
        return False


def gemm(A: np.ndarray, B: np.ndarray, nthreads: int = 0) -> np.ndarray:
    """Native GF(256) GEMM; NumPy-oracle fallback when no toolchain."""
    A = np.ascontiguousarray(A, dtype=np.uint8)
    B = np.ascontiguousarray(B, dtype=np.uint8)
    p, k = A.shape
    k2, m = B.shape
    assert k == k2, (A.shape, B.shape)
    try:
        lib = get_lib()
    except NativeUnavailable:
        from ..ops.gf import get_field

        return get_field(8).matmul(A, B)
    C = np.empty((p, m), dtype=np.uint8)
    lib.rs_gemm(A, B, C, p, k, m, nthreads or os.cpu_count() or 1)
    return C


def invert(M: np.ndarray) -> np.ndarray:
    """Native Gauss-Jordan inverse; raises SingularMatrixError if singular."""
    from ..ops.inverse import SingularMatrixError, invert_matrix

    M = np.ascontiguousarray(M, dtype=np.uint8)
    k = M.shape[0]
    try:
        lib = get_lib()
    except NativeUnavailable:
        return invert_matrix(M)
    out = np.empty((k, k), dtype=np.uint8)
    if lib.rs_invert(M, out, k) != 0:
        raise SingularMatrixError("matrix not invertible (native)")
    return out


def stripe_read(
    path: str,
    chunk: int,
    k: int,
    off: int,
    cols: int,
    total_size: int,
    fallback_src: np.ndarray | None = None,
) -> np.ndarray:
    """(k, cols) stripe segment of a file via native pread.

    ``fallback_src``: an already-open memmap of ``path`` used when the
    native library is unavailable (avoids re-mapping the file per segment).
    """
    dst = np.empty((k, cols), dtype=np.uint8)
    t0 = time.perf_counter()
    try:
        lib = get_lib()
    except NativeUnavailable:
        src = (
            fallback_src
            if fallback_src is not None
            else np.memmap(path, dtype=np.uint8, mode="r")
        )
        dst[:] = 0

        def read_row(i: int) -> None:
            lo = i * chunk + off
            hi = min(lo + cols, (i + 1) * chunk, total_size)
            if lo < hi:
                dst[i, : hi - lo] = src[lo:hi]

        # Fan the per-chunk range copies across the shared reader pool
        # (RS_IO_READERS) — each row touches a distinct slice of dst and a
        # distinct source range, so the rows are independent.
        from ..parallel.io_executor import run_rows

        run_rows(k, read_row)
        _count_io("read", "stripe_read", dst.nbytes, time.perf_counter() - t0)
        return dst
    got = lib.rs_stripe_read(path.encode(), dst, chunk, k, off, cols, total_size)
    if got < 0:
        raise OSError(f"rs_stripe_read failed for {path!r} (I/O error or truncated file)")
    _count_io("read", "stripe_read", dst.nbytes, time.perf_counter() - t0)
    return dst


def scatter_write(
    files, arr: np.ndarray, off: int, call: str = "scatter_write"
) -> None:
    """Write each row of (p, cols) ``arr`` to the matching open binary file
    at byte offset ``off`` (native pwrite; Python seek/write fallback).
    ``call`` labels the bytes in ``rs_io_write_*_total``: ``native_rows``
    for a row-layout encode's native rows, else ``scatter_write``."""
    arr = np.ascontiguousarray(arr, dtype=np.uint8)
    p, cols = arr.shape
    assert len(files) == p
    t0 = time.perf_counter()
    try:
        lib = get_lib()
    except NativeUnavailable:
        for fp, row in zip(files, arr):
            fp.seek(off)
            fp.write(row.tobytes())
        _count_io("write", call, arr.nbytes, time.perf_counter() - t0)
        return
    for fp in files:
        fp.flush()  # nothing buffered may straddle the raw pwrite below
    fds = (ctypes.c_int * p)(*[fp.fileno() for fp in files])
    if lib.rs_scatter_write(fds, arr, p, cols, off) != 0:
        raise OSError("rs_scatter_write failed (short write)")
    _count_io("write", call, arr.nbytes, time.perf_counter() - t0)


def gather_rows(files, off: int, cols: int, fallback_maps=None) -> np.ndarray:
    """(k, cols) segment at byte offset ``off`` of k open chunk files —
    the decode-side staging twin of :func:`stripe_read` (native pread per
    row; memmap slice-copy fallback).

    ``files``: open binary file objects (one per surviving chunk).
    ``fallback_maps``: memmaps used when the native library is
    unavailable.  Callers invoking this in a per-segment loop should pass
    them (built once per file set) — omitting them re-mmaps every file on
    every fallback call and requires ``f.name`` to be a real path.
    """
    k = len(files)
    dst = np.empty((k, cols), dtype=np.uint8)
    t0 = time.perf_counter()
    try:
        lib = get_lib()
    except NativeUnavailable:
        maps = fallback_maps
        if maps is None:
            maps = [np.memmap(f.name, dtype=np.uint8, mode="r") for f in files]

        def read_row(i: int) -> None:
            dst[i] = maps[i][off : off + cols]

        # Distinct memmaps and distinct dst rows: fan across the shared
        # reader pool (RS_IO_READERS), mirroring rs_native.cpp's run_rows.
        from ..parallel.io_executor import run_rows

        run_rows(k, read_row)
        _count_io("read", "gather_rows", dst.nbytes, time.perf_counter() - t0)
        return dst
    fds = (ctypes.c_int * k)(*[f.fileno() for f in files])
    if lib.rs_gather_rows(fds, dst, k, off, cols) != 0:
        raise OSError("rs_gather_rows failed (short read)")
    _count_io("read", "gather_rows", dst.nbytes, time.perf_counter() - t0)
    return dst
