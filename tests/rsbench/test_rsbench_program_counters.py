"""The file path's per-layer metrics, read from the program's counters:
each reader on hand-made runs, and traced CPU rehearsals of every cell
through the harness, once with the counters and once without them, as a
program from before they existed runs."""

import pytest

from rsbench import harness, program_counters as pc, trace_reduce

COUNTED = {
    pc.WAITS: {"type": "counter",
               "values": {'{wait="writer"}': 6.0, '{wait="segment"}': 0.5}},
    pc.WRITE_LANE: {"type": "counter",
                    "values": {'{lane="writer-0"}': 9.0}},
    pc.SCAN: {"type": "counter", "values": {"": 1.5}},
}


def run(counters=COUNTED, busy_s=0.5, ops=3):
    return {"trace": {"window_s": 10.0, "busy_s": busy_s, "kernel_s": 0.02},
            "counters": counters, "gemms": [("gemm", ops)], "peaks": None}


@pytest.mark.parametrize("name,want", [("wait_writer.encode", 60.0),
                                       ("wait_read.encode", 5.0),
                                       ("wait_read.repair", 5.0),
                                       ("write_lane_s.encode", 3.0),
                                       ("scan_s.repair", 0.5)])
def test_readers(name, want):
    read = harness.load_reader(name)
    assert read(run()) == pytest.approx(want)
    # A program without the counter (before it existed) gives nothing,
    # and so does a trace in which the device ran nothing.
    assert read(run(counters={})) is None
    assert read(run(busy_s=0.0)) is None


def test_a_wait_that_never_blocked_reads_zero():
    only_writer = {pc.WAITS: {"type": "counter",
                              "values": {'{wait="writer"}': 1.0}}}
    assert harness.load_reader("wait_read.encode")(run(only_writer)) == 0.0


def test_per_op_needs_ops():
    assert harness.load_reader("scan_s.repair")(run(ops=0)) is None


def test_write_lane_sums_every_lane():
    lanes = {pc.WRITE_LANE: {"type": "counter", "values": {
        '{lane="writer-0"}': 2.0, '{lane="drain-sync"}': 1.0}}}
    assert harness.load_reader("write_lane_s.encode")(run(lanes, ops=1)) \
        == pytest.approx(3.0)


# -- through the harness ------------------------------------------------------

CELLS = [w["name"] for w in harness.load_benchmark()["workloads"]]


def counter_metrics(cell) -> set:
    return {m["name"] for m in harness.resolve(cell)["per_layer"]
            if m["source"] == "program_counter"}


@pytest.fixture
def a_device_op(monkeypatch):
    """The CPU trace with one device op in the window, as every chip run
    has: the readers give nothing where the device ran nothing."""
    real = trace_reduce.read_xplane

    def read(path):
        tr = real(path)
        win = next(s for s in tr["spans"]
                   if s.name == trace_reduce.WINDOW_SPAN)
        op = trace_reduce.Event("%fusion = u8[1] fusion()", win.start, 1e6)
        tr["devices"]["/device:TPU:0"] = {"ops": [op], "modules": []}
        return tr

    monkeypatch.setattr(trace_reduce, "read_xplane", read)


def rehearse_traced(cell) -> dict:
    r = harness.resolve(cell)
    tiny = {"config": r["config"]["rehearsal"],
            "mix": r["mix"].get("rehearsal", {})}
    doc = harness.run_cell(cell, 2**31 + 23, 1.0, True, require_tpu=False,
                           overrides=tiny, log=lambda *a, **k: None)
    assert doc["correct"], doc["checks"]
    return doc


@pytest.mark.parametrize("cell", CELLS)
def test_traced_rehearsal_reads_the_counters(cell, a_device_op,
                                             monkeypatch):
    """The harness's counter labels are the ones the readers look up:
    with a one-deep writer queue behind slow writes, and slow reads, so
    that both waits block, every counter metric of the cell reads more
    than 0."""
    monkeypatch.setenv("RS_IO_WRITE_DEPTH", "1")
    monkeypatch.setenv("RS_FAULTS", "write:delay@ms=5;read:delay@ms=30")
    doc = rehearse_traced(cell)
    want = counter_metrics(cell)
    assert want and want <= set(doc["metrics"])
    for name in want:
        assert doc["metrics"][name]["value"] > 0, name


@pytest.mark.parametrize("cell", CELLS)
def test_traced_rehearsal_without_the_counters(cell, a_device_op,
                                               monkeypatch):
    """A program with neither the profiler sink nor the wait and scan
    counters (the one before them): the run is correct, the metrics that
    read those counters are left out, the others are there."""
    from gpu_rscode_tpu.obs import metrics, tracing

    monkeypatch.delattr(tracing, "profiler_sink")
    real = metrics.REGISTRY.snapshot

    def snapshot():
        return {n: m for n, m in real().items()
                if n not in (pc.WAITS, pc.SCAN)}

    monkeypatch.setattr(metrics.REGISTRY, "snapshot", snapshot)
    doc = rehearse_traced(cell)
    got = set(doc["metrics"])
    assert not {n for n in got if n.startswith(("wait_", "scan_s."))}
    assert any(n.startswith("device_idle.") for n in got)
