"""Debug HTTP server: live status, task DAG, trace download, metrics.

Mirrors the reference's debug endpoints (exec/graph.go:15-100,
exec/session.go:376-389): ``/debug`` (index), ``/debug/status`` (live
per-op task counts), ``/debug/tasks`` (task DAG as JSON, the d3
force-graph data source), ``/debug/trace`` (Chrome trace JSON of the
session so far), ``/debug/resources`` (executor resource gauges),
``/debug/metrics`` (the telemetry hub's signals in Prometheus text
exposition format — task-state counters, per-op skew ratio and
duration quantiles, wave overlap-efficiency gauges — for scrape-based
production monitoring), ``/debug/device`` (the device-plane summary:
compile/cost/memory attribution, HBM watermarks, donation
effectiveness), and ``/debug/profile?seconds=N`` (a windowed on-demand
``jax.profiler`` trace of the live session, the program's spans in
it).

The request plumbing here — threaded HTTP server, GET/POST dispatch
through an overridable route method, in-flight tracking with a
draining ``close()`` — is shared with the serving plane:
``serve/server.py``'s ``ServeServer`` subclasses ``DebugServer`` and
adds the ``/serve/*`` invocation surface on the same listener, so a
production server exposes its debug endpoints for free.

``close()`` **drains**: it stops accepting new connections, then waits
(bounded) for in-flight request handlers to finish before tearing the
socket down — an operator curling ``/debug/metrics`` during shutdown
gets their response, and a mid-invocation ``/serve/invoke`` completes
instead of dying with a reset connection.
"""

from __future__ import annotations

import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import List, Optional
from urllib.parse import parse_qs, urlparse

# How long close() waits for in-flight handlers before giving up and
# closing the socket anyway (a wedged profile window must not hang
# process shutdown forever).
DRAIN_TIMEOUT_S = 10.0


class DebugServer:
    def __init__(self, session, port: int = 0):
        self.session = session
        self._roots: List = []
        self._lock = threading.Lock()
        # In-flight request accounting for the draining close(): every
        # do_GET/do_POST wraps itself in _enter/_exit; close() flips
        # _closing (new requests get 503) and waits for the count to
        # reach zero.
        self._inflight = 0
        self._inflight_cond = threading.Condition()
        self._closing = False

        server = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):  # quiet
                pass

            def do_GET(self):
                if not server._enter(self):
                    return
                try:
                    parsed = urlparse(self.path)
                    if not server.handle_get(self, parsed):
                        self._send(404, "text/plain", "not found\n")
                finally:
                    server._exit()

            def do_POST(self):
                if not server._enter(self):
                    return
                try:
                    parsed = urlparse(self.path)
                    if not server.handle_post(self, parsed):
                        self._send(404, "text/plain", "not found\n")
                finally:
                    server._exit()

            def _read_body(self, limit: int = 16 << 20):
                """Request body, or None when Content-Length exceeds
                the limit (the caller answers 413 — an oversized
                request must not masquerade as an empty one)."""
                n = int(self.headers.get("Content-Length") or 0)
                if n > limit:
                    return None
                if n <= 0:
                    return b""
                return self.rfile.read(n)

            def _send(self, code, ctype, body: str):
                data = body.encode()
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

            def _send_json(self, code, doc):
                self._send(code, "application/json",
                           json.dumps(doc, default=str))

        self.httpd = ThreadingHTTPServer(("127.0.0.1", port), Handler)
        self.port = self.httpd.server_address[1]
        self._thread = threading.Thread(
            target=self.httpd.serve_forever, daemon=True
        )
        self._thread.start()

    # -- in-flight accounting (the draining close) ------------------------

    def _enter(self, handler) -> bool:
        with self._inflight_cond:
            if self._closing:
                try:
                    handler._send(503, "text/plain",
                                  "shutting down\n")
                except Exception:
                    pass
                return False
            self._inflight += 1
        return True

    def _exit(self) -> None:
        with self._inflight_cond:
            self._inflight -= 1
            self._inflight_cond.notify_all()

    # -- route tables (ServeServer overrides/extends) ---------------------

    def index_lines(self) -> List[str]:
        return [
            "bigslice_tpu debug",
            "",
            "/debug/status  live task-state counts",
            "/debug/tasks   task DAG (json)",
            "/debug/trace   chrome trace (json)",
            "/debug/resources  HBM/RSS/combiner gauges (json)",
            "/debug/metrics  telemetry in Prometheus text format",
            "/debug/fleet   cross-rank merged telemetry (json;"
            " ?format=prom for rank-labelled series)",
            "/debug/device  device-plane summary: compile/cost/memory,"
            " HBM, donation (json)",
            "/debug/profile?seconds=N  windowed jax profiler trace of"
            " the live session (json)",
        ]

    def handle_get(self, handler, parsed) -> bool:
        """Serve one GET; return False for 'no such route' (the
        handler 404s). Subclasses extend by handling their own paths
        first and falling back to super()."""
        path = parsed.path
        session = self.session
        if path in ("/debug", "/debug/"):
            handler._send(200, "text/plain",
                          "\n".join(self.index_lines()) + "\n")
        elif path == "/debug/status":
            handler._send(200, "text/plain",
                          session.status.render() or "(idle)")
        elif path == "/debug/tasks":
            handler._send_json(200, self.task_graph())
        elif path == "/debug/resources":
            stats_fn = getattr(session.executor, "resource_stats",
                               None)
            handler._send_json(
                200, stats_fn() if stats_fn is not None else {}
            )
        elif path == "/debug/metrics":
            hub = getattr(session, "telemetry", None)
            text = hub.prometheus_text() if hub else ""
            handler._send(200, "text/plain; version=0.0.4", text)
        elif path == "/debug/fleet":
            self._fleet(handler, parse_qs(parsed.query))
        elif path == "/debug/device":
            hub = getattr(session, "telemetry", None)
            dev = getattr(hub, "device", None)
            handler._send_json(
                200, dev.summary() if dev is not None else {}
            )
        elif path == "/debug/profile":
            self._profile(handler, parse_qs(parsed.query))
        elif path == "/debug/trace":
            tracer = session.tracer
            events = tracer.events() if tracer else []
            handler._send_json(200, {"traceEvents": events})
        else:
            return False
        return True

    def handle_post(self, handler, parsed) -> bool:
        """No POST routes on the pure debug surface."""
        return False

    def _fleet(self, handler, query):
        """The fleet plane's scrape surface: the cross-rank merged
        telemetry summary (json), or rank-labelled ``bigslice_*{rank=}``
        Prometheus series with ``?format=prom``. Degrades to this
        process's own 1-rank fleet when no fleet exporter is configured
        — the endpoint shape never depends on deployment mode."""
        session = self.session
        fmt = (query.get("format") or ["json"])[0]
        if fmt in ("prom", "prometheus"):
            from bigslice_tpu.utils import fleettelemetry as fleet_mod

            fleet = getattr(session, "fleet", None)
            hub = getattr(session, "telemetry", None)
            try:
                if fleet is not None:
                    snaps = fleet.pull()
                elif hub is not None:
                    snaps = [hub.snapshot()]
                else:
                    snaps = []
                text = fleet_mod.prometheus_fleet_text(snaps)
            except Exception as e:  # noqa: BLE001 — report, not crash
                handler._send(500, "text/plain",
                              f"fleet scrape failed: {e!r}\n")
                return
            handler._send(200, "text/plain; version=0.0.4", text)
            return
        summary_fn = getattr(session, "telemetry_summary", None)
        try:
            doc = summary_fn(scope="fleet") if summary_fn else {}
        except Exception:
            doc = {}
        handler._send_json(200, doc)

    def _profile(self, handler, query):
        """Windowed on-demand profiling: blocks this request thread
        for the window (the server is threading, other endpoints stay
        live), responds with the trace dir + files. 409 when another
        window/evaluation trace holds the per-process profiler."""
        from bigslice_tpu.utils.xprof import ProfilerBusy

        profiler = getattr(self.session, "profiler", None)
        if profiler is None:
            handler._send(404, "text/plain",
                          "no profiler on this session\n")
            return
        try:
            seconds = float(query.get("seconds", ["1"])[0])
        except (TypeError, ValueError):
            handler._send(400, "text/plain",
                          "seconds must be a number\n")
            return
        try:
            result = profiler.window(seconds)
        except ProfilerBusy as e:
            handler._send(409, "text/plain", f"{e}\n")
            return
        except Exception as e:  # noqa: BLE001 — report, not 500-crash
            handler._send(500, "text/plain",
                          f"profiling failed: {e!r}\n")
            return
        handler._send_json(200, result)

    def register_roots(self, roots) -> None:
        with self._lock:
            self._roots.extend(roots)

    def task_graph(self) -> dict:
        from bigslice_tpu.exec.task import iter_tasks

        with self._lock:
            roots = list(self._roots)
        nodes, links = [], []
        for t in iter_tasks(roots):
            nodes.append({
                "id": str(t.name),
                "op": t.name.op,
                "shard": t.name.shard,
                "state": t.state.name,
            })
            for d in t.deps:
                for p in d.tasks:
                    links.append({
                        "source": str(p.name),
                        "target": str(t.name),
                        "partition": d.partition,
                    })
        return {"nodes": nodes, "links": links}

    def drain(self, timeout: float = DRAIN_TIMEOUT_S) -> bool:
        """Stop admitting new requests and wait (bounded) for in-flight
        handlers to finish. Returns True when fully drained."""
        deadline = time.monotonic() + max(0.0, timeout)
        with self._inflight_cond:
            self._closing = True
            while self._inflight > 0:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._inflight_cond.wait(remaining)
        return True

    def close(self, timeout: float = DRAIN_TIMEOUT_S) -> None:
        """Graceful shutdown: drain in-flight requests (bounded), then
        stop the accept loop and release the socket."""
        self.drain(timeout)
        self.httpd.shutdown()
        self.httpd.server_close()
