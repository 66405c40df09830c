"""Encoding-matrix generation (coding "models").

Capability parity with the reference's generator (``matrix.cu:752-759``:
``EM[i][j] = gf_pow((j+1) % 256, i)`` launched from ``encode.cu:134-141``,
CPU twin ``cpu-rs.c:446-463`` which stacks the identity on top).

The reference generates the Vandermonde block on the GPU with one thread per
entry; at (n-k) x k <= a few KB that is pure launch overhead, so the TPU build
generates it on host NumPy and ships it to the device as a constant folded
into the jitted encode (XLA hoists it).  A Cauchy generator is added as a
second coding model: unlike the plain (non-systematic-corrected) Vandermonde
the reference uses, every square submatrix of a Cauchy matrix is invertible,
which guarantees decodability for ANY k-subset of chunks.
"""

from __future__ import annotations

import numpy as np

from ..ops.gf import GaloisField, get_field
from .lrc import azure_lrc_12_2_2


def vandermonde_matrix(parity_num: int, native_num: int, gf: GaloisField | None = None) -> np.ndarray:
    """(parity_num, native_num) Vandermonde block: ``V[i, j] = (j+1)^i``.

    Bit-identical to the reference's ``gen_encoding_matrix``
    (``matrix.cu:752-759``), including the ``(j+1) % size`` wrap.
    """
    gf = gf or get_field(8)
    j = (np.arange(native_num, dtype=np.int64) + 1) % gf.size
    i = np.arange(parity_num, dtype=np.int64)
    return gf.pow(j[None, :], i[:, None]).astype(gf.dtype)


def total_matrix(parity_num: int, native_num: int, gf: GaloisField | None = None) -> np.ndarray:
    """(native_num + parity_num, native_num) total encoding matrix ``[I; V]``.

    Identity block first, Vandermonde block below — the exact row order the
    reference writes to .METADATA (``encode.cu:61-101``) and the CPU oracle
    regenerates deterministically (``cpu-rs.c:459-463``).
    """
    gf = gf or get_field(8)
    eye = np.eye(native_num, dtype=gf.dtype)
    return np.concatenate([eye, vandermonde_matrix(parity_num, native_num, gf)], axis=0)


def cauchy_matrix(parity_num: int, native_num: int, gf: GaloisField | None = None) -> np.ndarray:
    """(parity_num, native_num) Cauchy block: ``C[i, j] = 1 / (x_i ^ y_j)``
    with ``x_i = native_num + i``, ``y_j = j``.

    Every square submatrix of ``[I; C]`` is invertible, so any k survivors
    decode — a guarantee the reference's Vandermonde-over-GF construction does
    not actually provide for all (n, k).  Requires ``n <= 2^w``.
    """
    gf = gf or get_field(8)
    if native_num + parity_num > gf.size:
        raise ValueError(f"n = {native_num + parity_num} exceeds field size {gf.size}")
    x = np.arange(native_num, native_num + parity_num, dtype=np.int64)
    y = np.arange(native_num, dtype=np.int64)
    return gf.inv(x[:, None] ^ y[None, :]).astype(gf.dtype)


# Name -> (p, k) parity block.  A non-MDS code fixes its own structure in
# its name (``lrc-12-2-2``: k=12 in two local groups, two globals).
GENERATORS = {
    "vandermonde": vandermonde_matrix,
    "cauchy": cauchy_matrix,
    "lrc-12-2-2": azure_lrc_12_2_2,
}


def generator_matrix(kind: str, parity_num: int, native_num: int, gf: GaloisField | None = None) -> np.ndarray:
    try:
        fn = GENERATORS[kind]
    except KeyError:
        raise ValueError(f"unknown generator {kind!r}; choose from {sorted(GENERATORS)}") from None
    return fn(parity_num, native_num, gf)
