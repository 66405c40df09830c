"""`correct` comes out false for the faults the store cell can have,
planted under the served path while the rest of the run is driven as
the benchmark drives it (set-up and warm-up stay sound)."""

import numpy as np
import pytest

from test_rsbench_control import _refused

CELL = "objstore_rs10_4.ycsb_a_degraded"


@pytest.fixture
def bucket_cls():
    from gpu_rscode_tpu.store.bucket import Bucket

    return Bucket


def test_a_degraded_answer_altered(monkeypatch, bucket_cls):
    real = bucket_cls.get

    def flipped(self, key, *, info=None):
        info = {} if info is None else info
        data = real(self, key, info=info)
        if info.get("path") == "degraded":
            data = bytes([data[0] ^ 0xFF]) + data[1:]
        return data

    doc = _refused(CELL, lambda d: monkeypatch.setattr(bucket_cls, "get",
                                                       flipped))
    assert doc["checks"]["get_bytes_wrong"]["value"] > 0


def test_a_put_acknowledged_and_not_stored(monkeypatch, bucket_cls):
    def nothing(self, items):
        return [{"arc": "none", "at": 0, "len": len(d), "crc": 0, "gen": 0}
                for _, d in items]

    doc = _refused(CELL, lambda d: monkeypatch.setattr(
        bucket_cls, "put_many", nothing))
    assert doc["checks"]["acked_puts_lost"]["value"] > 0


def test_half_of_a_put_group_left_out(monkeypatch, bucket_cls):
    real = bucket_cls.put_many

    def half(self, items):
        items = list(items)
        kept = real(self, items[: len(items) // 2]) if len(items) > 1 else []
        return kept + [dict(kept[-1]) if kept else {} for _ in
                       items[len(kept):]]

    doc = _refused(CELL, lambda d: monkeypatch.setattr(
        bucket_cls, "put_many", half))
    assert doc["checks"]["acked_puts_lost"]["value"] > 0


def test_the_parity_delta_left_unapplied(monkeypatch):
    from gpu_rscode_tpu.codec import RSCodec

    real = RSCodec.update

    def zero(self, parity_mat, delta):
        return np.zeros_like(np.asarray(real(self, parity_mat, delta)))

    doc = _refused(CELL, lambda d: monkeypatch.setattr(RSCodec, "update",
                                                       zero))
    assert doc["checks"]["parity_bytes_wrong"]["value"] > 0
