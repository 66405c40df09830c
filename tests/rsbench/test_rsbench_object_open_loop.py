"""The open-loop object driver's yardstick: its schedule from the seed, its
timing from due instants, and its nearest-rank percentiles."""

import contextlib
import math
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

from rsbench.drivers import object_open_loop as loop

MIX = {"rate_per_s": 140, "read_proportion": 0.5}


def test_the_schedule_repeats_exactly_for_a_seed():
    a = loop.window_plan(2**31 + 7, MIX, 30.0, 262144)
    assert a == loop.window_plan(2**31 + 7, MIX, 30.0, 262144)
    assert a != loop.window_plan(2**31 + 8, MIX, 30.0, 262144)
    # Every seed sends the same count, half of them GETs, inside the
    # window and in due order.
    b = loop.window_plan(12, MIX, 30.0, 262144)
    for plan in (a, b):
        kinds, recs, dues = map(np.array, zip(*plan))
        assert len(plan) == 4200 and (kinds == loop.GET).sum() == 2100
        assert np.all(np.diff(dues) >= 0) and 0 <= dues[0]
        assert dues[-1] < 30.0
        assert recs.min() >= 0 and recs.max() < 262144


def test_nearest_rank_counts_a_failed_op_as_infinitely_slow():
    ms = [float(i) for i in range(1, 101)]
    assert loop.nearest_rank(ms, 0.95) == 95.0
    assert loop.nearest_rank(ms, 0.5) == 50.0
    assert loop.nearest_rank([7.0], 0.95) == 7.0
    assert math.isnan(loop.nearest_rank([], 0.95))
    ops = []
    for i in range(100):
        op = loop.Op(i, loop.GET, 0, due=0.0)
        op.done, op.status = (i + 1) / 1000, 200
        ops.append(op)
    for op in ops[-5:]:
        op.status = 500
    # Five failures are the slowest five: the 95th is the 95th fastest.
    assert loop.nearest_rank(loop.latencies(ops, loop.GET, 0.0), 0.95) \
        == pytest.approx(0.095)
    ops[0].status = -1
    assert loop.nearest_rank(loop.latencies(ops, loop.GET, 0.0),
                             0.95) == math.inf
    assert loop.latencies(ops, loop.PUT, 0.0) == []


class _Slow(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    disable_nagle_algorithm = True
    hold = 0.3

    def log_message(self, *a):
        pass

    def do_GET(self):  # noqa: N802
        if self.path.endswith("/user0"):
            time.sleep(self.hold)
        body = b"x" * 10
        self.send_response(200)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)


@contextlib.contextmanager
def _server():
    srv = ThreadingHTTPServer(("127.0.0.1", 0), _Slow)
    srv.daemon_threads = True
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    try:
        yield srv.server_address[1]
    finally:
        srv.shutdown()
        srv.server_close()
        t.join()


def test_a_held_back_sender_makes_lateness_count():
    """One sender: the slow first reply holds the next two back.  They go
    out late, and their latency runs from their due instants, not from
    when they were sent."""
    with _server() as port:
        pool = loop.SenderPool(port, 1, "t", ["user0", "user1"],
                               lambda i: b"", contextlib.nullcontext)
        ops = [loop.Op(0, loop.GET, 0, 0.0), loop.Op(1, loop.GET, 1, 0.05),
               loop.Op(2, loop.GET, 1, 0.10)]
        t0 = time.perf_counter() + 0.02
        pool.run(ops, t0)
        pool.close()
    assert all(o.ok and o.body == b"x" * 10 for o in ops)
    lat = loop.latencies(ops, loop.GET, t0)
    assert lat[0] >= _Slow.hold
    for o, lt in zip(ops[1:], lat[1:]):
        late = o.sent - (t0 + o.due)
        assert late >= _Slow.hold - 0.1 - 0.02
        assert lt >= late and lt == pytest.approx(o.done - t0 - o.due)


def test_ops_are_sent_on_their_due_instants():
    with _server() as port:
        pool = loop.SenderPool(port, 4, "t", ["user1"], lambda i: b"",
                               contextlib.nullcontext)
        ops = [loop.Op(i, loop.GET, 0, 0.02 * i) for i in range(8)]
        t0 = time.perf_counter() + 0.02
        pool.run(ops, t0)
        pool.close()
    for o in ops:
        assert t0 + o.due <= o.sent < t0 + o.due + 0.05
