"""Open-loop object driver: a YCSB table in an RS(k, p) bucket behind the
program's daemon (``serve.daemon.ServeDaemon``, in this process), driven
over HTTP on localhost at a fixed offered rate.

Set-up, in order: the table is made from the seed and loaded through
``Bucket.put_many``; the object cache is filled with the hottest records,
as a store that has served the mix for a while holds them; native chunk
``lost_chunk`` of every sealed stripe is moved aside, as when one storage
node is down; then every program the window can dispatch is warmed (PUT
groups of every size up to the daemon's batch cap, degraded GETs in every
sealed stripe, and a few requests of each kind over HTTP).

The window sends a schedule drawn from the seed: ``round(rate *
seconds)`` arrivals of a Poisson process over ``seconds`` (the same count
for every seed), half of them GETs and half PUTs of the whole record, in
a seeded order, on keys drawn as YCSB draws them.  A fixed pool of sender
threads takes the arrivals in order, each waiting for its due instant;
an operation is timed from its due instant to its last byte, so a sender
held back makes the lateness count.  ``get_p95_ms`` and ``put_p95_ms``
are nearest-rank 95th percentiles over every operation of the kind, a
failed one counting as infinitely slow.

``correct`` compares, once the window has closed, every GET answer with
the values the plain model allows it, every acknowledged PUT with what a
freshly opened bucket reads back, and every stripe's parity with the
reference's (``rsbench/refs/objstore_ref.py``).
"""

from __future__ import annotations

import http.client
import json
import math
import os
import threading
import time

import numpy as np

from .. import ycsb
from ..refs import objstore_ref as ref
from ..refs import rs_gf8
from ..work import Gemm

GET, PUT = 0, 1
TENANT = "default"

# Streams of the seed (objstore_ref.rng): one per use, never shared.
S_VALUES, S_PUT_VALUES, S_KEYS, S_ARRIVALS, S_KINDS, S_WARM, S_SAMPLE = \
    range(7)


class Op:
    __slots__ = ("id", "kind", "rec", "due", "sent", "done", "status",
                 "body", "path", "stages", "error")

    def __init__(self, id: int, kind: int, rec: int, due: float):
        self.id, self.kind, self.rec, self.due = id, kind, rec, due
        self.sent = self.done = math.nan
        self.status = 0
        self.body = None
        self.path = None
        self.stages = None
        self.error = None

    @property
    def ok(self) -> bool:
        return self.status == 200


def nearest_rank(values, q: float) -> float:
    """The smallest value with at least ``q`` of ``values`` at or below
    it (nearest rank); NaN for no values."""
    v = sorted(values)
    if not v:
        return math.nan
    return float(v[max(0, math.ceil(q * len(v)) - 1)])


def latencies(ops, kind: int, t0: float) -> list[float]:
    """Due instant (``t0`` + due) to last byte, in seconds; a failed op
    is infinitely slow."""
    return [(o.done - t0 - o.due) if o.ok else math.inf
            for o in ops if o.kind == kind]


def window_plan(seed: int, mix: dict, seconds: float, records: int):
    """The window's ops, ``(kind, record, due)`` in due order:
    ``round(rate * seconds)`` arrivals of a Poisson process over [0,
    seconds) conditioned on that count (sorted uniform instants), the
    mix's share of them GETs in a seeded order, on keys YCSB draws."""
    n = round(mix["rate_per_s"] * seconds)
    n_get = round(n * mix["read_proportion"])
    kinds = np.array([GET] * n_get + [PUT] * (n - n_get))
    ref.rng(seed, S_KINDS).shuffle(kinds)
    keys = ycsb.request_keys(ref.rng(seed, S_KEYS), n, records)
    dues = np.sort(ref.rng(seed, S_ARRIVALS).random(n)) * seconds
    return list(zip(kinds.tolist(), keys.tolist(), dues.tolist()))


class SenderPool:
    """``size`` sender threads, each with its own keep-alive connection;
    :meth:`run` sends ``ops`` in order, each not before ``t0 + due``."""

    def __init__(self, port: int, size: int, table: str, keys, value_of,
                 span):
        self.table = table
        self.keys = keys
        self.value_of = value_of
        self.span = span
        self.conns = [http.client.HTTPConnection("127.0.0.1", port,
                                                 timeout=120)
                      for _ in range(size)]
        # One at a time, ahead of any burst: the listener's accept
        # backlog is short, and a connection refused mid-window would
        # read as a failed operation.
        for c in self.conns:
            c.connect()

    def run(self, ops: list[Op], t0: float) -> None:
        lock = threading.Lock()
        order = iter(ops)

        def sender(conn):
            while True:
                with lock:
                    op = next(order, None)
                if op is None:
                    return
                wait = t0 + op.due - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                self._send(conn, op)

        threads = [threading.Thread(target=sender, args=(c,),
                                    name=f"rsb-sender-{i}", daemon=True)
                   for i, c in enumerate(self.conns[: max(1, len(ops))])]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

    def _send(self, conn, op: Op) -> None:
        path = f"/o/{self.table}/{self.keys[op.rec]}"
        name = "rsb.get" if op.kind == GET else "rsb.put"
        op.sent = time.perf_counter()
        try:
            with self.span(name):
                if op.kind == GET:
                    conn.request("GET", path)
                else:
                    conn.request("PUT", path, body=self.value_of(op.id))
                resp = conn.getresponse()
                body = resp.read()
            op.done = time.perf_counter()
            op.status = resp.status
            if op.kind == GET and op.ok:
                op.body = body
                op.path = resp.getheader("X-RS-Read-Path")
                stages = resp.getheader("X-RS-Stages")
                op.stages = json.loads(stages) if stages else None
            elif not op.ok:
                op.error = body[:200].decode(errors="replace")
            if resp.will_close:
                conn.close()
        except Exception as e:  # a failed op is counted, never fatal
            op.done = time.perf_counter()
            op.status = -1
            op.error = f"{type(e).__name__}: {e}"
            conn.close()

    def close(self) -> None:
        for c in self.conns:
            c.close()


class Driver:
    def __init__(self, cfg: dict, mix: dict, seed: int, seconds: float,
                 workdir: str, span):
        self.k, self.p, self.w = cfg["k"], cfg["p"], cfg["w"]
        if self.w != 8:
            raise ValueError("the object reference covers w=8 only")
        self.cfg, self.mix = cfg, mix
        self.n = cfg["recordcount"]
        self.vbytes = cfg["fieldcount"] * cfg["fieldlength"]
        self.table = cfg["table"]
        self.lost = cfg["lost_chunk"]
        self.daemon_cfg = cfg["daemon"]
        self.seed = seed
        self.seconds = seconds
        self.span = span
        self.root = os.path.join(workdir, "serve")
        self.lost_dir = os.path.join(workdir, "lost")
        self.log = ref.Log()
        self.warm_ops: list[Op] = []
        self.window_ops: list[Op] = []
        self.server = None
        self.senders = None
        self.sealed_archives: list[str] = []

    # -- the server under test (the control puts the model here) -------------

    def _start_server(self) -> int:
        from gpu_rscode_tpu.serve.daemon import ServeDaemon

        d = self.daemon_cfg
        self.server = ServeDaemon(
            self.root, port=0, addr="127.0.0.1", depth=d["depth"],
            batch_ms=d["batch_ms"], max_batch=d["max_batch"],
            workers=d["workers"], slo_spec="",
            obj_cache_bytes=self.cfg["object_cache_bytes"], maint=False,
        ).start()
        return self.server.port

    def _stop_server(self) -> None:
        if self.server is not None:
            self.server.close()
            self.server = None

    def _put_many(self, items) -> None:
        self.bucket.put_many(items)

    def _warm_cache(self, recs) -> None:
        """Fill the daemon's object cache with ``recs``, coldest first:
        each record's loaded bytes under its live index entry, which is
        what the daemon's GET puts on a miss (every hit re-verifies the
        bytes against that entry's CRC32)."""
        cache = self.server.objcache
        for rec in recs[::-1]:
            key = self.keys[rec]
            cache.put(TENANT, self.table, key, self.bucket.entry_for(key),
                      self.values[rec].tobytes())

    # -- values ---------------------------------------------------------------

    def value_of(self, op_id: int) -> bytes:
        return self.put_values[op_id].tobytes()

    def _version_bytes(self, rec: int, version: int) -> np.ndarray:
        if version == ref.PRELOAD:
            return self.values[rec]
        return self.put_values[version]

    def _new_op(self, kind: int, rec: int, due: float, into) -> Op:
        op = Op(self.next_id, kind, int(rec), float(due))
        self.next_id += 1
        into.append(op)
        return op

    # -- set-up ---------------------------------------------------------------

    def setup(self) -> dict:
        from gpu_rscode_tpu import store

        parts = {}
        t = time.perf_counter()
        with self.span("rsb.setup.data"):
            self.values = ref.seeded_bytes(
                self.seed, S_VALUES, self.n * self.vbytes
            ).reshape(self.n, self.vbytes)
            self.keys = ref.key_names(np.arange(self.n))
            self.place = ref.pack(self.n, self.vbytes,
                                  self.cfg["load_batch"],
                                  self.cfg["stripe_bytes"])
            self._schedule()
        parts["data_s"], t = time.perf_counter() - t, time.perf_counter()

        with self.span("rsb.setup.load"):
            port = self._start_server()
            self.bucket = store.open_bucket(
                os.path.join(self.root, TENANT), self.table, create=True,
                k=self.k, p=self.p, w=self.w,
                stripe_bytes=self.cfg["stripe_bytes"])
            batch = self.cfg["load_batch"]
            for lo in range(0, self.n, batch):
                self.bucket.put_many(
                    [(self.keys[r], self.values[r].tobytes())
                     for r in range(lo, min(self.n, lo + batch))])
            self._check_packing()
        parts["load_s"], t = time.perf_counter() - t, time.perf_counter()

        with self.span("rsb.setup.cache"):
            self.cached = ycsb.hottest(self.n,
                                       self.cfg["cache_warm_records"])
            self._warm_cache(self.cached)
        parts["cache_s"], t = time.perf_counter() - t, time.perf_counter()

        with self.span("rsb.setup.lose"):
            os.makedirs(self.lost_dir)
            for arc in self.sealed_archives:
                os.rename(self._chunk(arc, self.lost),
                          os.path.join(self.lost_dir, arc))
        room = self._headroom()
        if room < self.n_puts + self.n_warm_puts:
            raise RuntimeError(
                f"the open stripe takes {room} more records before it "
                f"seals, warm-up and window send {self.n_warm_puts} and "
                f"{self.n_puts} PUTs: size the table so that it stays open")
        with self.span("rsb.setup.warm"):
            self.senders = SenderPool(port, self.mix["senders"], self.table,
                                      self.keys, self.value_of, self.span)
            self._warm()
        parts["warm_s"] = time.perf_counter() - t
        parts["open_stripe_room_records"] = room
        return parts

    def _schedule(self) -> None:
        """Every op the run sends, and the bytes of every PUT, from the
        seed: warm-up first, then the window."""
        max_batch = self.daemon_cfg["max_batch"]
        warm_http = self.mix["warm_http_ops"]
        self.window_plan = window_plan(self.seed, self.mix, self.seconds,
                                       self.n)
        n = len(self.window_plan)
        self.n_puts = sum(kind == PUT for kind, _, _ in self.window_plan)
        direct = max_batch * (max_batch + 1) // 2
        self.n_warm_puts = direct + warm_http
        degraded = (len(self.place.sizes) - 1) \
            * self.mix["warm_degraded_gets_per_stripe"]
        self.warm_keys = ycsb.request_keys(ref.rng(self.seed, S_WARM),
                                           direct + 2 * warm_http, self.n)
        # One row per op id (GETs' rows go unused): warm-up, then window.
        self.next_id = 0
        self.put_values = ref.seeded_bytes(
            self.seed, S_PUT_VALUES,
            (direct + degraded + 2 * warm_http + n) * self.vbytes,
        ).reshape(-1, self.vbytes)

    def _chunk(self, arc: str, i: int) -> str:
        return os.path.join(self.bucket.path, f"_{i}_{arc}")

    def _archives(self) -> list[str]:
        return sorted(f[: -len(".METADATA")]
                      for f in os.listdir(self.bucket.path)
                      if f.startswith("stripe-") and f.endswith(".METADATA"))

    def _check_packing(self) -> None:
        arcs = self.bucket.stats()["archives"]
        got = [arcs[a]["total_bytes"] for a in sorted(arcs)]
        if got != self.place.sizes:
            raise RuntimeError(f"the bucket packed the table into stripes "
                               f"of {got} bytes, the model into "
                               f"{self.place.sizes}")
        if got[-1] >= self.cfg["stripe_bytes"]:
            raise RuntimeError("the load filled its last stripe: size the "
                               "table so that one stays open")
        self.sealed_archives = sorted(arcs)[:-1]
        self.n_sealed = len(self.sealed_archives)

    def _headroom(self) -> int:
        arcs = self.bucket.stats()["archives"]
        open_bytes = arcs[sorted(arcs)[-1]]["total_bytes"]
        return (self.cfg["stripe_bytes"] - open_bytes) // self.vbytes

    def _warm(self) -> None:
        """Warm every program the window dispatches: PUT groups of every
        size up to the daemon's batch cap (through ``put_many``, as the
        daemon's write combining calls it), degraded GETs in every sealed
        stripe, and GETs and PUTs over HTTP.  Every op is logged and
        checked like the window's."""
        wk = iter(self.warm_keys.tolist())
        for size in range(1, self.daemon_cfg["max_batch"] + 1):
            group = [self._new_op(PUT, next(wk), 0.0, []) for _ in
                     range(size)]
            sent = time.perf_counter()
            self._put_many([(self.keys[o.rec], self.value_of(o.id))
                            for o in group])
            acked = time.perf_counter()
            for o in group:
                self.log.add(o.rec, o.id, sent, acked)
        gen = ref.rng(self.seed, S_SAMPLE)
        cached = set(self.cached.tolist())
        ops: list[Op] = []
        for s in range(self.n_sealed):
            recs = np.flatnonzero(self.place.stripe == s)
            recs = [r for r in gen.permutation(recs)[:64].tolist()
                    if r not in cached and r not in self.log.puts]
            for r in recs[: self.mix["warm_degraded_gets_per_stripe"]]:
                self._new_op(GET, r, 0.0, ops)
        for i in range(self.mix["warm_http_ops"]):
            self._new_op(GET, next(wk), 0.002 * i, ops)
            self._new_op(PUT, next(wk), 0.002 * i + 0.001, ops)
        self.senders.run(ops, time.perf_counter())
        self._log_puts(ops)
        self.warm_ops += ops

    def _log_puts(self, ops) -> None:
        for o in ops:
            if o.kind == PUT:
                self.log.add(o.rec, o.id, o.sent, o.done if o.ok else None)

    # -- window ---------------------------------------------------------------

    def window(self, seconds: float) -> dict:
        ops: list[Op] = []
        for kind, rec, due in self.window_plan:
            self._new_op(kind, rec, due, ops)
        stripes0 = len(self._archives())
        t0 = time.perf_counter() + 0.05  # the senders are up by then
        self.senders.run(ops, t0)
        end = max(o.done for o in ops)
        self._log_puts(ops)
        self.window_ops = ops
        failed = sum(not o.ok for o in ops)
        gets, puts = latencies(ops, GET, t0), latencies(ops, PUT, t0)
        late = sorted(o.sent - (t0 + o.due) for o in ops)
        return {
            "attempted": len(ops), "failed": failed,
            "metrics": {"get_p95_ms": 1e3 * nearest_rank(gets, 0.95),
                        "put_p95_ms": 1e3 * nearest_rank(puts, 0.95)},
            "notes": {
                "gets": len(gets), "puts": len(puts),
                "get_p50_ms": 1e3 * nearest_rank(gets, 0.5),
                "put_p50_ms": 1e3 * nearest_rank(puts, 0.5),
                "put_p95_ms": 1e3 * nearest_rank(puts, 0.95),
                "get_p99_ms": 1e3 * nearest_rank(gets, 0.99),
                "offered_per_s": len(ops) / seconds,
                "completed_per_s": (len(ops) - failed) / (end - t0),
                "window_s": end - t0,
                "late_p50_ms": 1e3 * nearest_rank(late, 0.5),
                "late_p95_ms": 1e3 * nearest_rank(late, 0.95),
                "late_max_ms": 1e3 * late[-1],
                "in_flight_first_quarter": self._in_flight(ops, t0, 0),
                "in_flight_last_quarter": self._in_flight(ops, t0, 3),
                "degraded_share_first_quarter": self._degraded(ops, 0),
                "degraded_share_last_quarter": self._degraded(ops, 3),
                "degraded_get_stages_p50_ms": self._stages(ops),
                "get_outside_daemon_p50_ms": self._outside(ops),
                "open_stripe_sealed": len(self._archives()) != stripes0,
                "objcache": self._cache_stats(),
                "errors": [o.error for o in ops if not o.ok][:3],
            },
            "gemms": self._gemms(ops),
        }

    def _in_flight(self, ops, t0: float, quarter: int) -> float:
        """Mean ops due but not answered, over one quarter of the
        schedule's span, sampled at each due instant in it."""
        due = np.array([t0 + o.due for o in ops])
        done = np.sort([o.done for o in ops])
        lo, hi = quarter * self.seconds / 4, (quarter + 1) * self.seconds / 4
        at = due[(due >= t0 + lo) & (due < t0 + hi)]
        if at.size == 0:
            return math.nan
        started = np.searchsorted(np.sort(due), at, side="right")
        finished = np.searchsorted(done, at, side="right")
        return float(np.mean(started - finished))

    def _degraded(self, ops, quarter: int) -> float:
        """Share of the quarter's GETs served by the degraded read path."""
        lo, hi = quarter * self.seconds / 4, (quarter + 1) * self.seconds / 4
        got = [o.path for o in ops
               if o.kind == GET and o.ok and lo <= o.due < hi]
        return (100.0 * got.count("degraded") / len(got)) if got \
            else math.nan

    @staticmethod
    def _stages(ops) -> dict:
        """Median stage offsets (ms since admission) of degraded GETs."""
        st = [o.stages for o in ops if o.path == "degraded" and o.stages]
        names = sorted({k for s in st for k in s})
        return {k: 1e3 * float(np.median([s[k] for s in st if k in s]))
                for k in names}

    @staticmethod
    def _outside(ops) -> float:
        """Median ms of a GET's wall, sent to last byte, outside the
        daemon's admission-to-completion stages: request and response on
        the wire and in the HTTP handler."""
        out = [(o.done - o.sent) * 1e3 - 1e3 * o.stages["drain_done"]
               for o in ops if o.stages and "drain_done" in o.stages]
        return float(np.median(out)) if out else math.nan

    def _cache_stats(self) -> dict | None:
        cache = getattr(self.server, "objcache", None)
        return cache.stats() if cache is not None else None

    def _gemms(self, ops) -> list:
        """The traffic's least GEMM work, from its own shapes: a p x k
        parity delta over every acknowledged PUT's columns, and a 1 x k
        rebuild over the columns of every distinct record the window
        reads while it still lies in a stripe with a lost chunk."""
        cols = -(-self.vbytes // self.k)
        puts = sum(o.ok for o in ops if o.kind == PUT)
        rebuilt = set()
        for o in ops:
            if (o.kind == GET and o.ok
                    and self.place.stripe[o.rec] < self.n_sealed
                    and not any(w.sent < o.done
                                for w in self.log.puts.get(o.rec, []))):
                rebuilt.add(o.rec)
        return [(Gemm(self.p, self.k, cols), puts),
                (Gemm(1, self.k, cols), len(rebuilt))]

    def release(self) -> None:
        if self.senders is not None:
            self.senders.close()
        self._stop_server()

    def close(self) -> None:
        self.release()

    # -- correctness ----------------------------------------------------------

    def check(self) -> dict:
        ops = self.warm_ops + self.window_ops
        wrong = 0
        for o in ops:
            if o.kind == GET and o.ok:
                wants = [self._version_bytes(o.rec, v)
                         for v in self.log.allowed(o.rec, o.sent, o.done)]
                wrong += ref.bytes_wrong(o.body, wants)
        return {"get_bytes_wrong": (wrong, 0),
                "acked_puts_lost": (self._acked_puts_lost(), 0),
                "parity_bytes_wrong": (self._parity_bytes_wrong(), 0),
                "ops_failed": (sum(not o.ok for o in ops), 0)}

    def _acked_puts_lost(self) -> int:
        """Records whose value read back by a bucket opened afresh from
        disk (the lost chunks still missing) is none the model allows:
        every record a PUT wrote, and a sample of loaded ones."""
        from gpu_rscode_tpu import store

        store.drop_cached(self.bucket.path)
        fresh = store.open_bucket(os.path.join(self.root, TENANT),
                                  self.table)
        gen = ref.rng(self.seed, S_SAMPLE + 1)
        sample = gen.choice(self.n, self.mix["readback_sample"],
                            replace=False).tolist()
        lost = 0
        try:
            for rec in sorted(set(self.log.written()) | set(sample)):
                wants = [self._version_bytes(rec, v)
                         for v in self.log.final(rec)]
                try:
                    got = fresh.get(self.keys[rec])
                except (store.ObjectStoreError, store.RangeReadError,
                        OSError):
                    lost += 1
                    continue
                lost += ref.bytes_wrong(got, wants) != 0
        finally:
            store.drop_cached(self.bucket.path)
        return lost

    def _parity_bytes_wrong(self) -> int:
        """Every stripe's parity chunks against the reference's parity of
        its native chunks (a sealed stripe's lost chunk read from where
        set-up moved it)."""
        dev = rs_gf8.DeviceParity(rs_gf8.vandermonde(self.p, self.k))
        wrong = 0
        for arc in self._archives():
            paths = [self._chunk(arc, i) for i in range(self.k + self.p)]
            if arc in self.sealed_archives:
                paths[self.lost] = os.path.join(self.lost_dir, arc)
            rows = [np.fromfile(p, np.uint8) if os.path.exists(p)
                    else None for p in paths]
            size = max(r.size for r in rows if r is not None)
            for i, r in enumerate(rows):
                if r is None or r.size != size:
                    wrong += size if i >= self.k else 0
                    rows[i] = np.zeros(size, np.uint8)
            wrong += ref.parity_bytes_wrong(
                np.stack(rows[: self.k]), np.stack(rows[self.k:]), dev,
                self.cfg["parity_block_cols"])
        return wrong
