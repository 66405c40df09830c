"""The plain reference for Windows Azure Storage's LRC(12, 2, 2) over
GF(2^8), written from the published description (Huang et al., "Erasure
Coding in Windows Azure Storage", USENIX ATC 2012, §2-3) and importing
nothing of the program.

* Field: ``rs_gf8``'s, GF(2^8) with the polynomial 0x11D.
* Fragments: data x0..x5 (group x) and y0..y5 (group y), then the local
  parities ``Lx = x0 ^ ... ^ x5`` and ``Ly = y0 ^ ... ^ y5``, then the
  globals ``G1 = sum a_i x_i ^ sum b_i y_i`` and ``G2 = sum a_i^2 x_i ^
  sum b_i^2 y_i``, with a_i = 0x10 * i (upper nibble only) and b_i = i
  (lower nibble only), i = 1..6: the paper's rule for choosing
  coefficients, carried from GF(2^4) to GF(2^8).  Sixteen fragments, in
  that order, are the archive's chunks 0..15.
* Row layout and ``.METADATA`` as ``rs_gf8``'s, with this total matrix
  ``[I12; Lx; Ly; G1; G2]`` in place of the Vandermonde one.

Parity is ``rs_gf8.DeviceParity``: doubling and XOR in column blocks.  A
lost data or local-parity fragment is the XOR of the other six members
of its group (every local coefficient is 1); a lost global parity is
recomputed from the twelve data fragments.
"""

from __future__ import annotations

import numpy as np

from . import rs_gf8

K, L, R = 12, 2, 2
P = L + R
N = K + P
GROUP = K // L


def coefficients() -> list[int]:
    """a_1..a_6 then b_1..b_6: the globals' weight of each data
    fragment."""
    return [0x10 * i for i in range(1, GROUP + 1)] + \
        list(range(1, GROUP + 1))


def parity_matrix() -> list[list[int]]:
    """(4, 12): Lx, Ly, G1, G2."""
    local = [[int(g * GROUP <= j < (g + 1) * GROUP) for j in range(K)]
             for g in range(L)]
    glob = [[rs_gf8.gf_pow(c, e) for c in coefficients()]
            for e in range(1, R + 1)]
    return local + glob


def total_matrix() -> list[list[int]]:
    eye = [[int(i == j) for j in range(K)] for i in range(K)]
    return eye + parity_matrix()


def group_of(i: int) -> list[int]:
    """The fragments of ``i``'s local group, its local parity included
    (data or local parity only)."""
    g = i // GROUP if i < K else i - K
    if not 0 <= g < L:
        raise ValueError(f"fragment {i} is a global parity: no local group")
    return list(range(g * GROUP, (g + 1) * GROUP)) + [K + g]


def metadata_text(size: int, crcs: dict[int, int]) -> str:
    lines = [f"{size}\n", f"{P} {K}\n"]
    lines += ["".join(f"{v} " for v in row) + "\n" for row in total_matrix()]
    lines += [f"# crc32 {i} {crcs[i] & 0xFFFFFFFF:08x}\n"
              for i in sorted(crcs)]
    return "".join(lines)


def fragments(data: np.ndarray, size: int) -> np.ndarray:
    """(16, chunk) fragments of ``data`` on the host (small sizes: the
    unit tests)."""
    chunk = rs_gf8.chunk_size(size, K)
    rows = np.zeros((K, chunk), np.uint8)
    for i in range(K):
        piece = data[i * chunk: min(size, (i + 1) * chunk)]
        rows[i, : piece.size] = piece
    return np.concatenate([rows, rs_gf8.parity_np(rows, parity_matrix())])


def local_rebuild(others: list[np.ndarray]) -> np.ndarray:
    """A data or local-parity fragment from the other six of its group."""
    out = np.zeros_like(others[0])
    for o in others:
        out ^= o
    return out


def global_rebuild(data_rows: np.ndarray, j: int,
                   block: int = 16 << 20) -> np.ndarray:
    """Global parity ``j`` (0 for G1, 1 for G2) from the twelve data
    fragments, on the device in column blocks."""
    dev = rs_gf8.DeviceParity([parity_matrix()[L + j]])
    return np.concatenate([dev(data_rows[:, lo: lo + block])[0]
                           for lo in range(0, data_rows.shape[1], block)])
