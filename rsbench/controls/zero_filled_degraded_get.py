"""Control of the ``object_open_loop`` mixes: the plain model in the
daemon's place.  It answers a GET from the model's own copy of every
value, and a record still in a stripe with a lost chunk with the bytes
that chunk held set to zero (a rebuild skipped: the tempting shortcut).
PUTs go through the program's bucket, so the bucket on disk is what the
check reads as in a sound run."""

from __future__ import annotations

import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from rsbench.drivers import object_open_loop as loop
from rsbench.refs import objstore_ref as ref


class _Handler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    def log_message(self, fmt, *args):
        pass

    def _reply(self, code: int, body: bytes) -> None:
        self.send_response(code)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def do_GET(self):  # noqa: N802
        self._reply(200, self.server.model.get(self.path.rsplit("/", 1)[1]))

    def do_PUT(self):  # noqa: N802
        body = self.rfile.read(int(self.headers["Content-Length"]))
        self.server.model.put(self.path.rsplit("/", 1)[1], body)
        self._reply(200, b"{}")


class Model:
    def __init__(self, drv: "Driver"):
        self.drv = drv
        self.rec = {key: i for i, key in enumerate(drv.keys)}
        self.written: dict[str, bytes] = {}
        self.lock = threading.Lock()

    def get(self, key: str) -> bytes:
        with self.lock:
            if key in self.written:
                return self.written[key]
        d, r = self.drv, self.rec[key]
        if d.place.stripe[r] < d.n_sealed:
            return ref.lost_row_zeroed(d.values[r], int(d.place.at[r]), d.k,
                                       d.lost).tobytes()
        return d.values[r].tobytes()

    def put(self, key: str, body: bytes) -> None:
        with self.lock:
            self.drv.bucket.put_many([(key, body)])
            self.written[key] = body


class Driver(loop.Driver):
    def _start_server(self) -> int:
        self.server = ThreadingHTTPServer(("127.0.0.1", 0), _Handler)
        self.server.daemon_threads = True
        self.server.model = Model(self)
        self._thread = threading.Thread(target=self.server.serve_forever,
                                        daemon=True)
        self._thread.start()
        return self.server.server_address[1]

    def _stop_server(self) -> None:
        if self.server is not None:
            self.server.shutdown()
            self.server.server_close()
            self._thread.join()
            self.server = None

    def _put_many(self, items) -> None:
        for key, body in items:
            self.server.model.put(key, body)

    def _warm_cache(self, recs) -> None:
        pass  # the model keeps no cache
