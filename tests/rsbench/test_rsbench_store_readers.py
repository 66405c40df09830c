"""The store cell's per-layer readers on hand-made counter deltas, and
with nothing to read."""

import pytest

from rsbench import harness, store_counters as sc

COUNTED = {
    sc.RANGE_READS: {"type": "counter", "values": {
        '{path="degraded"}': 30.0, '{path="fast"}': 10.0}},
    sc.CACHE_HITS: {"type": "counter", "values": {"": 120.0}},
    sc.CACHE_MISSES: {"type": "counter", "values": {"": 40.0}},
    sc.SCAN: {"type": "counter", "values": {"": 1.2}},
    sc.QUEUE_WAIT: {"type": "quantile", "values": {
        '{op="object_get"}': {"count": 160, "sum": 0.8},
        '{op="object_put"}': {"count": 100, "sum": 3.0}}},
}


def run(counters=COUNTED, busy_s=0.05):
    return {"trace": {"window_s": 30.0, "busy_s": busy_s, "kernel_s": 0.01},
            "counters": counters, "gemms": [], "peaks": None}


STORE = [("scan_ms.store", 40.0), ("degraded_share.store", 75.0),
         ("cache_hit_share.store", 75.0), ("queue_wait_ms.store", 5.0)]


@pytest.mark.parametrize("name,want", STORE)
def test_readers_compute_their_ratios(name, want):
    assert harness.load_reader(name)(run()) == pytest.approx(want)


@pytest.mark.parametrize("name", [n for n, _ in STORE]
                         + ["kernel_roofline.store"])
def test_readers_give_nothing_without_a_device_op(name):
    read = harness.load_reader(name)
    assert read(run(busy_s=0.0)) is None
    assert read({"trace": None, "counters": COUNTED, "gemms": [],
                 "peaks": None}) is None


@pytest.mark.parametrize("name", [n for n, _ in STORE])
def test_readers_give_nothing_without_the_counter(name):
    assert harness.load_reader(name)(run(counters={})) is None


def test_nothing_to_divide_by_gives_nothing():
    idle = {sc.RANGE_READS: {"type": "counter", "values": {}},
            sc.CACHE_HITS: {"type": "counter", "values": {"": 0.0}},
            sc.CACHE_MISSES: {"type": "counter", "values": {"": 0.0}},
            sc.SCAN: {"type": "counter", "values": {"": 0.0}},
            sc.QUEUE_WAIT: {"type": "quantile", "values": {
                '{op="object_put"}': {"count": 4, "sum": 0.1}}}}
    for name, _ in STORE:
        assert harness.load_reader(name)(run(idle)) is None


def test_only_fast_reads_is_a_zero_degraded_share():
    fast = {sc.RANGE_READS: {"type": "counter",
                             "values": {'{path="fast"}': 5.0}}}
    assert harness.load_reader("degraded_share.store")(run(fast)) == 0.0


def test_the_device_idle_share_reads_the_trace_alone():
    read = harness.load_reader("device_idle.store")
    assert read(run()) == pytest.approx(100.0 * (1 - 0.05 / 30.0))
    assert read(run(busy_s=0.0)) == 100.0


def test_kernel_roofline_needs_the_chips_peaks():
    from rsbench import peaks
    from rsbench.work import Gemm

    r = run()
    r["gemms"] = [(Gemm(4, 10, 100), 2000), (Gemm(1, 10, 100), 500)]
    read = harness.load_reader("kernel_roofline.store")
    assert read(r) is None
    r["peaks"] = peaks.peaks_for("TPU v5 lite")
    least = (14 * 100 * 2000 + 11 * 100 * 500) / 819e9
    assert read(r) == pytest.approx(100 * least / 0.01)
