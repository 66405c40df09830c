"""Closed-loop repair driver for an LRC(12, 2, 2) extent: one repairer
loses one fragment and repairs it back to back, the lost index rotating
over all sixteen from the seed (data, local and global parities alike,
as a storage node's loss reaches the extent).

Set-up encodes the extent with the configuration's generator and warms
both repair plans: a data fragment (rebuilt from its local group, a 1 x 6
GEMM) and a global parity (from twelve fragments, a 1 x 12 GEMM).

The metric (the mix's ``"metric"``) is ``file_loop``'s: extent bytes of
every repair completed in the window / the window.  ``correct`` compares
every fragment the window moved aside and every fragment left in the
archive with the reference (``rsbench/refs/lrc_gf8.py``): data fragments
with the extent, parity fragments with the reference's parity; every CRC
line of ``.METADATA`` with the CRC32 of the reference's fragment; and the
program's count of local rebuilds with the losses that have one.
"""

from __future__ import annotations

import os
import time
import zlib

import numpy as np

from ..refs import lrc_gf8 as lrc
from ..refs import rs_gf8 as ref
from ..work import Gemm
from . import file_loop

MiB = 1 << 20
LOCAL_PLANS = ("rs_repair_plans_total", '{plan="local"}')


def _local_plans_counted() -> int:
    from gpu_rscode_tpu.obs import metrics

    name, labels = LOCAL_PLANS
    snap = metrics.REGISTRY.snapshot().get(name, {})
    return int(snap.get("values", {}).get(labels, 0))


class Driver(file_loop.Driver):
    def __init__(self, cfg: dict, mix: dict, seed: int, seconds: float,
                 workdir: str, span):
        super().__init__(cfg, mix, seed, seconds, workdir, span)
        if (self.k, self.p, cfg["l"], cfg["r"]) != (lrc.K, lrc.P, lrc.L,
                                                     lrc.R):
            raise ValueError("the reference is LRC(12, 2, 2)")
        self.generator = cfg["generator"]
        self.n = self.k + self.p
        self.local_expected = 0
        self.local_counted = 0

    def setup(self) -> dict:
        t0 = time.perf_counter()
        with self.span("rsb.setup.data"):
            self.data = file_loop.block_group(self.seed, self.size)
            with open(self.path, "wb") as fp:
                fp.write(self.data.data)
        t1 = time.perf_counter()
        with self.span("rsb.setup.warm"):
            self._encode()
            os.makedirs(self.kept)
            for i in (0, lrc.K + lrc.L):  # a local plan, then a global one
                os.remove(self._chunk(i))
                self._repair()
        return {"data_s": t1 - t0, "warm_s": time.perf_counter() - t1}

    def _encode(self) -> None:
        from gpu_rscode_tpu import api

        api.encode_file(self.path, self.k, self.p, generator=self.generator,
                        checksums=True)

    @staticmethod
    def _has_local_plan(i: int) -> bool:
        return i < lrc.K + lrc.L

    def window(self, seconds: float) -> dict:
        t = 0
        local = 0
        counted0 = _local_plans_counted()
        t0 = time.perf_counter()
        while True:
            lost = (self.seed + t) % self.n
            keep = os.path.join(self.kept, f"{t}_{lost}")
            try:
                with self.span("rsb.repair_file"):
                    os.rename(self._chunk(lost), keep)
                    got = self._repair()
                self.rebuilt.append((lost, list(got), keep))
                local += self._has_local_plan(lost)
            except Exception as e:  # an op that fails is counted, not fatal
                self.failed += 1
                self.errors.append(f"{type(e).__name__}: {e}")
            t += 1
            if time.perf_counter() - t0 >= seconds:
                break
        wall = time.perf_counter() - t0
        self.local_expected = local
        self.local_counted = _local_plans_counted() - counted0
        done = t - self.failed
        return {
            "attempted": t, "failed": self.failed,
            "metrics": {self.metric: done * self.size / wall / 1e9},
            "notes": {"ops": t, "local": local, "window_s": wall,
                      "op_s_mean": wall / t, "errors": self.errors[:3]},
            "gemms": [(Gemm(1, lrc.GROUP, self.chunk), local),
                      (Gemm(1, self.k, self.chunk), done - local)],
        }

    # -- correctness ----------------------------------------------------------

    def _path_bytes_wrong(self, path: str, lo: int, want: np.ndarray) -> int:
        try:
            with open(path, "rb") as fp:
                got = np.frombuffer(os.pread(fp.fileno(), want.size, lo),
                                    np.uint8)
        except OSError:
            return want.size
        if got.size != want.size:
            return want.size
        return int(np.count_nonzero(got != want))

    def _check_repair(self) -> dict:
        kept: dict[int, list[str]] = {}
        misdirected = 0
        for lost, got, keep in self.rebuilt:
            misdirected += got != [lost]
            kept.setdefault(lost, []).append(keep)
        wrong = 0
        for paths in kept.values():
            for path in paths:
                if os.path.getsize(path) != self.chunk:
                    wrong += self.chunk
        # The reference's sixteen fragments in column blocks: the data
        # rows from the extent, the parity by doubling and XOR on the
        # device; every fragment of the archive and every one moved aside
        # is compared block by block, and the CRCs accumulate.
        dev = ref.DeviceParity(lrc.parity_matrix())
        crcs = [0] * self.n
        step = 16 * MiB
        for lo in range(0, self.chunk, step):
            hi = min(self.chunk, lo + step)
            rows = self._rows(self.data, lo, hi)
            frags = list(rows) + list(dev(rows))
            for i, want in enumerate(frags):
                crcs[i] = zlib.crc32(want, crcs[i])
                for path in [self._chunk(i)] + kept.get(i, []):
                    wrong += self._path_bytes_wrong(path, lo, want)
        lines = {}
        with open(self.path + ".METADATA") as fp:
            for line in fp:
                parts = line.split()
                if parts[:2] == ["#", "crc32"]:
                    lines[int(parts[2])] = int(parts[3], 16)
        lines_wrong = sum(lines.get(i) != crcs[i] & 0xFFFFFFFF
                          for i in range(self.n))
        return {"rebuilt_bytes_wrong": (wrong, 0),
                "crc_lines_wrong": (lines_wrong, 0),
                "repairs_misdirected": (misdirected, 0),
                "local_rebuilds_missed": (
                    abs(self.local_expected - self.local_counted), 0),
                "ops_failed": (self.failed, 0)}
