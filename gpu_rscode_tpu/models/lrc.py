"""Locally repairable codes: Windows Azure Storage's LRC (Huang et al.,
"Erasure Coding in Windows Azure Storage", USENIX ATC 2012, §2-3), and
what any total matrix allows a repair: its local groups and the losses it
is guaranteed to survive.

An LRC(k, l, r) splits the k data fragments into l equal local groups,
adds one local parity per group (the XOR of its fragments) and r global
parities over all of them.  A lost data or local-parity fragment is
rebuilt from its group alone (k/l reads where an MDS code reads k); any
r + 1 losses still decode, and so does every other pattern whose
surviving rows keep rank k.

Coefficients follow the paper's rule for two groups, carried from
GF(2^4) to GF(2^8): the first group's i-th fragment takes alpha_i =
0x10 * i (upper nibble only), the second group's beta_i = i (lower
nibble only), i = 1..k/2, and global parity j (j = 1..r) weighs each
fragment by its coefficient to the j-th power.  For LRC(12, 2, 2) over
GF(2^8) (polynomial 0x11D) this matrix is maximally recoverable: all 560
three-loss patterns of the 16 fragments decode, and 1,568 of the 1,820
four-loss patterns (86.2%, the paper's figure).
"""

from __future__ import annotations

import functools
import itertools

import numpy as np

from ..ops.gf import GaloisField, get_field


def azure_lrc_12_2_2(parity_num: int, native_num: int,
                     gf: GaloisField | None = None) -> np.ndarray:
    """The ``lrc-12-2-2`` generator's (4, 12) parity block ``[Lx; Ly; G1;
    G2]``: LRC(12, 2, 2), so k = 12, p = 4 and nothing else."""
    gf = gf or get_field(8)
    if (native_num, parity_num) != (12, 4):
        raise ValueError(
            f"generator lrc-12-2-2 is k=12, p=4 (n=16), got k={native_num}, "
            f"p={parity_num}")
    i = np.arange(1, 7, dtype=np.int64)
    coef = np.concatenate([0x10 * i, i])
    local = np.repeat(np.eye(2, dtype=np.int64), 6, axis=1)
    glob = np.stack([gf.pow(coef, 1), gf.pow(coef, 2)])
    return np.concatenate([local, glob]).astype(gf.dtype)


# -- what a total matrix allows: local rebuilds and guaranteed tolerance -----
#
# Both are read from the (n, k) total matrix alone, which .METADATA
# stores, so they hold for any archive whatever generator wrote it.


def _row_reduce(M: np.ndarray, gf: GaloisField) -> tuple[np.ndarray, list]:
    """Reduced row echelon form of ``M`` over ``gf`` and its pivot
    columns."""
    M = np.array(M, dtype=np.int64)
    pivots: list[int] = []
    r = 0
    for c in range(M.shape[1]):
        if r == M.shape[0]:
            break
        nz = np.nonzero(M[r:, c])[0]
        if nz.size == 0:
            continue
        p = r + int(nz[0])
        if p != r:
            M[[r, p]] = M[[p, r]]
        M[r] = gf.mul(M[r], int(gf.inv(M[r, c])))
        mask = M[:, c] != 0
        mask[r] = False
        if mask.any():
            M[mask] ^= gf.mul(M[mask, c][:, None], M[r][None, :])
        pivots.append(c)
        r += 1
    return M, pivots


def span_coefficients(A: np.ndarray, b: np.ndarray,
                      gf: GaloisField) -> np.ndarray | None:
    """``c`` with ``c . A == b`` over ``gf`` (the rows of ``A`` combined
    into ``b``), or None where ``b`` is not in the row span of ``A``."""
    s = A.shape[0]
    aug = np.concatenate([np.asarray(A, np.int64).T,
                          np.asarray(b, np.int64)[:, None]], axis=1)
    R, pivots = _row_reduce(aug, gf)
    if s in pivots:
        return None  # inconsistent: b is outside the span
    c = np.zeros(s, dtype=np.int64)
    for row, col in enumerate(pivots):
        c[col] = R[row, s]
    return c.astype(gf.dtype)


def local_plans(total_mat: np.ndarray, native_num: int,
                w: int = 8) -> dict[int, tuple[tuple[int, ...], np.ndarray]]:
    """For every chunk that a smaller set than k can rebuild: ``{chunk:
    (survivors, coefficients)}``, the smallest such set among the local
    groups the parity rows define (a parity row whose support has fewer
    than k data chunks, with that parity), and the coefficients that
    combine the survivors' rows into the chunk's row.

    Empty for an MDS code: every parity row of [I; V] covers all k."""
    return dict(_local_plans(np.ascontiguousarray(total_mat).tobytes(),
                             tuple(total_mat.shape), str(total_mat.dtype),
                             native_num, w))


@functools.lru_cache(maxsize=64)
def _local_plans(raw: bytes, shape: tuple, dtype: str, k: int, w: int):
    gf = get_field(w)
    T = np.frombuffer(raw, dtype=dtype).reshape(shape).astype(np.int64)
    plans: dict[int, tuple[tuple[int, ...], np.ndarray]] = {}
    for j in range(k, shape[0]):
        support = [int(c) for c in np.nonzero(T[j, :k])[0]]
        if len(support) >= k:
            continue
        group = support + [j]
        for t in group:
            rest = tuple(g for g in group if g != t)
            if t in plans and len(plans[t][0]) <= len(rest):
                continue
            coef = span_coefficients(T[list(rest)], T[t], gf)
            if coef is not None:
                coef.setflags(write=False)  # shared by every caller
                plans[t] = (rest, coef)
    return tuple(plans.items())


def erasure_tolerance(total_mat: np.ndarray, native_num: int,
                      w: int = 8) -> int:
    """Losses the code survives whatever chunks they hit.

    A parity block with no zero coefficient is taken as MDS, so n - k:
    the generator's own claim, which the file format has always assumed
    for Vandermonde and Cauchy archives.  A zero coefficient proves the
    code is not MDS (every square submatrix of an MDS parity block is
    invertible, each entry included), and the tolerance is then counted:
    the most losses s such that every pattern of s leaves rank k."""
    total_mat = np.asarray(total_mat)
    p = total_mat.shape[0] - native_num
    if np.all(total_mat[native_num:] != 0):
        return p
    return _counted_tolerance(np.ascontiguousarray(total_mat).tobytes(),
                              tuple(total_mat.shape), str(total_mat.dtype),
                              native_num, w)


@functools.lru_cache(maxsize=64)
def _counted_tolerance(raw: bytes, shape: tuple, dtype: str, k: int,
                       w: int) -> int:
    gf = get_field(w)
    T = np.frombuffer(raw, dtype=dtype).reshape(shape)
    n = shape[0]
    for s in range(1, n - k + 1):
        for lost in itertools.combinations(range(n), s):
            keep = [i for i in range(n) if i not in lost]
            if len(_row_reduce(T[keep], gf)[1]) < k:
                return s - 1
    return n - k
