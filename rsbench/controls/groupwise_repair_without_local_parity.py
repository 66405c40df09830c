"""Control of the ``lrc_file_loop`` repair mix: ``repair_file`` replaced
by the plain reference, which rebuilds a lost data fragment as the XOR of
the other five data fragments of its group and leaves the group's local
parity out.  Parity fragments it rebuilds as the reference does."""

from __future__ import annotations

import os

import numpy as np

from rsbench.drivers import lrc_file_loop
from rsbench.refs import lrc_gf8 as lrc


class Driver(lrc_file_loop.Driver):
    def _read(self, i: int) -> np.ndarray:
        return np.fromfile(self._chunk(i), np.uint8)

    def _repair(self) -> list[int]:
        lost = [i for i in range(self.n)
                if not os.path.exists(self._chunk(i))]
        for i in lost:
            if i < lrc.K:
                others = [self._read(j) for j in lrc.group_of(i)[:-1]
                          if j != i]
                row = lrc.local_rebuild(others)
            elif i < lrc.K + lrc.L:
                row = lrc.local_rebuild(
                    [self._read(j) for j in lrc.group_of(i)[:-1]])
            else:
                data = np.stack([self._read(j) for j in range(lrc.K)])
                row = lrc.global_rebuild(data, i - lrc.K - lrc.L)
            with open(self._chunk(i), "wb") as fp:
                fp.write(row.data)
        return lost
