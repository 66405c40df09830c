"""YCSB's request-key chooser for ``requestdistribution=zipfian``, as
the YCSB core workload builds it (Cooper et al., SoCC 2010; YCSB
``CoreWorkload``, ``ScrambledZipfianGenerator``, ``ZipfianGenerator``),
vectorised.

``CoreWorkload`` draws keys from a ``ScrambledZipfianGenerator`` over
``recordcount + 1`` items and redraws any record number past the last
loaded one.  The scrambled generator draws a rank from a zipfian over
10^10 items at the constant 0.99 (with YCSB's precomputed zeta, so no
sum over 10^10 terms) and folds it onto the items by ``fnvhash64 %
items``: the hottest records sit anywhere in the key space, and the far
tail of ranks lands on every record about evenly.
"""

from __future__ import annotations

import numpy as np

from .refs.objstore_ref import fnvhash64

ITEM_COUNT = 10_000_000_000
ZIPFIAN_CONSTANT = 0.99
ZETAN = 26.46902820178302  # zeta(10^10, 0.99), YCSB's constant


def zipfian_ranks(u: np.ndarray, items: int = ITEM_COUNT,
                  theta: float = ZIPFIAN_CONSTANT,
                  zetan: float = ZETAN) -> np.ndarray:
    """``ZipfianGenerator.nextValue`` for uniform draws ``u`` in [0, 1)."""
    zeta2 = 1.0 + 0.5 ** theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / items) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    uz = u * zetan
    ranks = (items * (eta * u - eta + 1.0) ** alpha).astype(np.int64)
    ranks = np.where(uz < 1.0 + 0.5 ** theta, 1, ranks)
    return np.where(uz < 1.0, 0, ranks)


def fold(ranks: np.ndarray, records: int) -> np.ndarray:
    """Ranks onto the ``records + 1`` items of CoreWorkload's chooser."""
    return np.fmod(fnvhash64(ranks), records + 1)


def request_keys(gen: np.random.Generator, n: int, records: int):
    """``n`` record numbers as CoreWorkload's ``nextKeynum`` draws them."""
    out = np.empty(0, np.int64)
    while out.size < n:
        got = fold(zipfian_ranks(gen.random(2 * (n - out.size) + 16)),
                   records)
        out = np.concatenate([out, got[got < records]])
    return out[:n]


def hottest(records: int, count: int) -> np.ndarray:
    """The ``count`` record numbers the hottest ranks fold onto, hottest
    first (each record once)."""
    ranks = np.arange(4 * count + 64, dtype=np.int64)
    items = fold(ranks, records)
    items = items[items < records]
    _, first = np.unique(items, return_index=True)
    return items[np.sort(first)][:count]
