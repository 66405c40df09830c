"""The object store's plain model: its keys, its rule for what a GET may
return, its packing, and YCSB's key chooser."""

import numpy as np
import pytest

from rsbench import ycsb
from rsbench.refs import objstore_ref as ref

PRE = ref.PRELOAD


def fnv_plain(v: int) -> int:
    """YCSB's fnvhash64 as written: signed 64-bit FNV-1a, then abs."""
    h = 0xCBF29CE484222325
    for _ in range(8):
        h ^= v & 0xFF
        v >>= 8
        h = (h * 1099511628211) & ((1 << 64) - 1)
    if h >= 1 << 63:
        h -= 1 << 64
    return abs(h)


def test_keys_are_ycsbs_hashed_names():
    nums = [0, 1, 2, 255, 256, 262143, 10**10 - 1]
    assert ref.fnvhash64(nums).tolist() == [fnv_plain(v) for v in nums]
    assert ref.key_names([0, 1]) == [f"user{fnv_plain(0)}",
                                     f"user{fnv_plain(1)}"]


def test_a_get_before_any_put_returns_the_loaded_value():
    log = ref.Log()
    assert log.allowed(3, 1.0, 2.0) == {PRE}
    log.add(3, 7, sent=5.0, acked=6.0)
    assert log.allowed(3, 1.0, 2.0) == {PRE}


def test_a_get_that_races_a_put_may_return_either_value():
    log = ref.Log()
    log.add(3, 7, sent=1.0, acked=2.0)
    # PUT in flight when the GET was sent, or while it was answered.
    assert log.allowed(3, 1.5, 1.8) == {PRE, 7}
    assert log.allowed(3, 0.5, 1.2) == {PRE, 7}
    assert log.allowed(3, 1.9, 2.5) == {PRE, 7}
    # Acknowledged before the GET was sent: only the new value.
    assert log.allowed(3, 2.5, 3.0) == {7}


def test_a_later_acknowledged_put_supersedes_an_earlier_one():
    log = ref.Log()
    log.add(3, 7, sent=1.0, acked=2.0)
    log.add(3, 8, sent=3.0, acked=4.0)
    assert log.allowed(3, 5.0, 6.0) == {8}
    assert log.allowed(3, 2.5, 3.5) == {7, 8}
    assert log.final(3) == {8}


def test_overlapping_puts_leave_either_value():
    log = ref.Log()
    log.add(3, 7, sent=1.0, acked=3.0)
    log.add(3, 8, sent=2.0, acked=4.0)
    assert log.allowed(3, 5.0, 6.0) == {7, 8}
    assert log.final(3) == {7, 8}


def test_a_failed_put_may_or_may_not_have_landed():
    log = ref.Log()
    log.add(3, 7, sent=1.0, acked=None)
    assert log.allowed(3, 2.0, 3.0) == {PRE, 7}
    assert log.allowed(3, 0.1, 0.5) == {PRE}
    assert log.final(3) == {PRE, 7}
    assert log.written() == [3]


def test_bytes_wrong_takes_the_closest_allowed_value():
    a = np.arange(10, dtype=np.uint8)
    b = a.copy()
    b[:3] = 99
    assert ref.bytes_wrong(a.tobytes(), [a]) == 0
    assert ref.bytes_wrong(b.tobytes(), [a]) == 3
    assert ref.bytes_wrong(b.tobytes(), [a, b]) == 0
    assert ref.bytes_wrong(a[:6].tobytes(), [a]) == 4
    assert ref.bytes_wrong(a.tobytes(), []) == 10


def test_packing_seals_a_stripe_once_it_reaches_its_size():
    p = ref.pack(n=10, value_bytes=100, batch=3, stripe_bytes=500)
    assert p.sizes == [600, 400]
    assert p.stripe.tolist() == [0] * 6 + [1] * 4
    assert p.at.tolist() == [0, 100, 200, 300, 400, 500, 0, 100, 200, 300]
    full = ref.pack(n=262144, value_bytes=1000, batch=4096,
                    stripe_bytes=64 << 20)
    assert full.sizes == [69632000] * 3 + [53248000]


def test_the_lost_row_is_every_kth_byte_of_the_stripe():
    v = np.full(25, 7, np.uint8)
    out = ref.lost_row_zeroed(v, at=1003, k=10, lost=0)
    assert np.flatnonzero(out == 0).tolist() == [7, 17]
    assert (v == 7).all()


def test_parity_reference_compares_in_fixed_blocks():
    from rsbench.refs import rs_gf8

    rng = np.random.default_rng(1)
    nat = rng.integers(0, 256, (10, 37), dtype=np.uint8)
    mat = rs_gf8.vandermonde(4, 10)
    par = rs_gf8.parity_np(nat, mat)
    dev = rs_gf8.DeviceParity(mat)
    assert ref.parity_bytes_wrong(nat, par, dev, block=16) == 0
    par[2, 30] ^= 1
    par[0, 0] ^= 5
    assert ref.parity_bytes_wrong(nat, par, dev, block=16) == 2


def test_ycsb_zipfian_ranks_and_keys():
    u = np.array([0.0, 0.5 / ycsb.ZETAN, 1.2 / ycsb.ZETAN, 0.999999])
    r = ycsb.zipfian_ranks(u)
    assert r[0] == 0 and r[1] == 0 and r[2] == 1 and r[3] > 10**9
    gen = np.random.Generator(np.random.SFC64(5))
    keys = ycsb.request_keys(gen, 20000, 1000)
    assert keys.shape == (20000,) and 0 <= keys.min() and keys.max() < 1000
    # The hottest rank's record is the most requested one.
    hot = ycsb.hottest(1000, 50)
    assert len(set(hot.tolist())) == 50
    assert np.bincount(keys).argmax() == hot[0]
    assert hot[0] == ref.fnvhash64([0])[0] % 1001


@pytest.mark.parametrize("records", [1000, 262144])
def test_the_chooser_never_names_a_record_past_the_last(records):
    gen = np.random.Generator(np.random.SFC64(9))
    assert ycsb.request_keys(gen, 5000, records).max() < records
