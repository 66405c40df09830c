"""The plain model of a YCSB table served by an RS(k, p) object store,
written from the published descriptions and importing nothing of the
program.

* Keys: YCSB's ``buildKeyName`` with hashed inserts, ``"user"`` and the
  decimal FNV-1a 64 hash of the record number (``Utils.fnvhash64``).
* Values: every record's bytes and every PUT's new bytes are made from
  the seed, in bulk.
* The log: per key, every PUT with the instants its client sent it and
  saw it acknowledged.  A GET sent at ``s`` and answered at ``e`` may
  return the value of the last PUT acknowledged before ``s`` (any of
  them where several overlapped one another), or of a PUT in flight at
  some instant of [s, e]; nothing else.
* Packing: records load in record order, ``batch`` at a time, into
  stripes that take whole batches until their size reaches
  ``stripe_bytes``; the interleaved layout puts file byte ``f`` of a
  stripe in native chunk ``f % k`` (GF(2^8), one byte a symbol).
* Parity: ``rs_gf8``'s, of each stripe's k native chunk rows.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field

import numpy as np

from . import rs_gf8

FNV_OFFSET = 0xCBF29CE484222325
FNV_PRIME = 1099511628211
MASK64 = (1 << 64) - 1


def rng(seed: int, stream: int) -> np.random.Generator:
    """The seed's generator for one named use: streams never overlap."""
    return np.random.Generator(np.random.SFC64([seed & MASK64, stream]))


def seeded_bytes(seed: int, stream: int, nbytes: int) -> np.ndarray:
    raw = rng(seed, stream).bit_generator.random_raw(-(-nbytes // 8))
    return raw.view(np.uint8)[:nbytes]


def fnvhash64(vals) -> np.ndarray:
    """YCSB's ``Utils.fnvhash64`` over int64 values: FNV-1a over the eight
    little-endian bytes, then Java's ``Math.abs`` of the signed result."""
    v = np.asarray(vals, dtype=np.int64).astype(np.uint64)
    h = np.full(v.shape, FNV_OFFSET, dtype=np.uint64)
    with np.errstate(over="ignore"):
        for i in range(8):
            h ^= (v >> np.uint64(8 * i)) & np.uint64(0xFF)
            h *= np.uint64(FNV_PRIME)
    return np.abs(h.astype(np.int64))


def key_names(recnums) -> list[str]:
    return [f"user{h}" for h in fnvhash64(recnums).tolist()]


# -- the values and the log ---------------------------------------------------

PRELOAD = -1  # the version id of a record's loaded value


@dataclass
class Put:
    version: int          # the op id whose bytes it writes
    sent: float
    acked: float | None   # None: never acknowledged (failed)


@dataclass
class Log:
    """Per-key PUT history, on the client's clock."""

    puts: dict[int, list[Put]] = field(default_factory=dict)

    def add(self, rec: int, version: int, sent: float,
            acked: float | None) -> None:
        self.puts.setdefault(rec, []).append(Put(version, sent, acked))

    def _versions(self, rec: int) -> list[Put]:
        return [Put(PRELOAD, -math.inf, -math.inf)] + self.puts.get(rec, [])

    def allowed(self, rec: int, sent: float, done: float) -> set[int]:
        """Version ids a GET of ``rec`` sent at ``sent`` and answered at
        ``done`` may return."""
        vs = self._versions(rec)
        before = [v for v in vs if v.acked is not None and v.acked < sent]
        out = set()
        for v in before:
            # Superseded for sure: a later PUT was sent after v's ack and
            # was itself acknowledged before the GET was sent.
            if not any(w.sent > v.acked for w in before):
                out.add(v.version)
        for v in vs:
            if (v.acked is None or v.acked >= sent) and v.sent < done:
                out.add(v.version)
        return out

    def final(self, rec: int) -> set[int]:
        """Version ids the key may hold once every PUT has ended."""
        return self.allowed(rec, math.inf, math.inf)

    def written(self) -> list[int]:
        return sorted(self.puts)


def bytes_wrong(got: bytes, wants) -> int:
    """Bytes of ``got`` that differ from the closest of ``wants``."""
    g = np.frombuffer(got, np.uint8)
    best = None
    for w in wants:
        n = min(g.size, w.size)
        d = int(np.count_nonzero(g[:n] != w[:n])) + abs(g.size - w.size)
        best = d if best is None else min(best, d)
    return g.size if best is None else best


# -- packing and parity -------------------------------------------------------

@dataclass(frozen=True)
class Placement:
    stripe: np.ndarray   # stripe number (0-based) of every record
    at: np.ndarray       # byte offset of every record in its stripe
    sizes: list[int]     # bytes of every stripe after the load


def pack(n: int, value_bytes: int, batch: int, stripe_bytes: int) -> Placement:
    stripe = np.empty(n, np.int64)
    at = np.empty(n, np.int64)
    sizes: list[int] = []
    for lo in range(0, n, batch):
        hi = min(n, lo + batch)
        if not sizes or sizes[-1] >= stripe_bytes:
            sizes.append(0)
        stripe[lo:hi] = len(sizes) - 1
        at[lo:hi] = sizes[-1] + value_bytes * np.arange(hi - lo)
        sizes[-1] += value_bytes * (hi - lo)
    return Placement(stripe, at, sizes)


def lost_row_zeroed(value: np.ndarray, at: int, k: int, lost: int):
    """``value``, stored at byte ``at`` of an interleaved stripe, with the
    bytes that native chunk ``lost`` holds set to zero."""
    out = value.copy()
    out[(at + np.arange(value.size)) % k == lost] = 0
    return out


def parity_bytes_wrong(natives: np.ndarray, parity: np.ndarray,
                       dev: "rs_gf8.DeviceParity", block: int) -> int:
    """Bytes of the (p, cols) ``parity`` rows that differ from the
    reference's parity of the (k, cols) ``natives``, in column blocks of
    one width (the tail zero-padded: zeros add nothing to a parity)."""
    cols = natives.shape[1]
    wrong = 0
    for lo in range(0, cols, block):
        hi = min(cols, lo + block)
        rows = np.zeros((natives.shape[0], block), np.uint8)
        rows[:, : hi - lo] = natives[:, lo:hi]
        want = dev(rows)[:, : hi - lo]
        wrong += int(np.count_nonzero(want != parity[:, lo:hi]))
    return wrong
