"""The plain LRC(12,2,2) reference against the program on seeded data at a
small size: the matrix, the fragments and ``.METADATA`` encode_file
writes, and the reference's own local and global rebuilds."""

import os

import numpy as np
import pytest

from rsbench.refs import lrc_gf8 as lrc
from rsbench.refs import rs_gf8

SIZE = 12 * 5000 + 37


@pytest.fixture
def extent(tmp_path):
    data = np.random.Generator(np.random.SFC64(2**31 + 27)).integers(
        0, 256, SIZE, dtype=np.uint8)
    path = str(tmp_path / "extent.bin")
    data.tofile(path)
    return path, data


def _fragment(path, i):
    d, base = os.path.split(path)
    return np.fromfile(os.path.join(d, f"_{i}_{base}"), np.uint8)


def test_matrix_is_the_programs():
    from gpu_rscode_tpu.codec import RSCodec

    want = lrc.total_matrix()
    assert want[12][:6] == [1] * 6 and want[12][6:] == [0] * 6
    assert want[14] == [0x10, 0x20, 0x30, 0x40, 0x50, 0x60, 1, 2, 3, 4, 5, 6]
    assert want[15][6:] == [rs_gf8.gf_mul(b, b) for b in range(1, 7)]
    got = RSCodec(12, 4, generator="lrc-12-2-2",
                  strategy="bitplane").total_matrix
    np.testing.assert_array_equal(got, np.array(want, np.uint8))


def test_encode_file_writes_the_references_archive(extent):
    from gpu_rscode_tpu import api

    path, data = extent
    api.encode_file(path, 12, 4, generator="lrc-12-2-2", checksums=True)
    want = lrc.fragments(data, SIZE)
    for i in range(lrc.N):
        np.testing.assert_array_equal(_fragment(path, i), want[i])
    np.testing.assert_array_equal(  # the parity the cell's check uses
        rs_gf8.DeviceParity(lrc.parity_matrix())(want[: lrc.K]),
        want[lrc.K:])
    crcs = {i: rs_gf8.crc32(want[i]) for i in range(lrc.N)}
    with open(path + ".METADATA") as fp:
        assert fp.read() == lrc.metadata_text(SIZE, crcs)


@pytest.mark.parametrize("lost", range(lrc.N))
def test_reference_rebuilds(extent, lost):
    _, data = extent
    frags = lrc.fragments(data, SIZE)
    if lost < lrc.K + lrc.L:
        others = [frags[j] for j in lrc.group_of(lost) if j != lost]
        assert len(others) == 6
        got = lrc.local_rebuild(others)
    else:
        got = lrc.global_rebuild(frags[: lrc.K], lost - lrc.K - lrc.L)
    np.testing.assert_array_equal(got, frags[lost])
