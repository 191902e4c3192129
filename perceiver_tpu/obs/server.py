"""Stdlib HTTP endpoint for the observability plane.

One ``ThreadingHTTPServer`` on loopback serving:

``/metrics``            Prometheus exposition (aggregated fleet text,
                        or a single registry's render — whatever
                        callable the owner wires in), and after it
                        ``obs_spans_dropped_total``: what this
                        process's trace buffer refused and its step
                        timeline overwrote
``/healthz``            JSON health snapshot (200 when the owner's
                        health callable says so, 503 otherwise)
``/traces``             JSON list of buffered trace ids
``/traces/<id>``        one trace's spans as JSON
``/profile?seconds=N``  on-demand ``jax.profiler`` capture into the
                        configured profile dir (returns the capture
                        path); 501 when no dir is configured

No dependency beyond the stdlib; all handlers are read-only except
``/profile``, which is bounded (one capture at a time, N clamped).
"""

from __future__ import annotations

import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Callable, Optional
from urllib.parse import parse_qs, urlparse

from perceiver_tpu.obs import trace as trace_mod

__all__ = ["ObsServer"]

_MAX_PROFILE_SECONDS = 30.0


class ObsServer:
    """Own one background HTTP server exposing metrics/health/traces.

    ``metrics_fn`` returns exposition text; ``health_fn`` returns a
    JSON-able dict with a truthy ``"ok"`` key when healthy.
    """

    def __init__(self, *, metrics_fn: Callable[[], str],
                 health_fn: Optional[Callable[[], dict]] = None,
                 trace_buffer: Optional[trace_mod.TraceBuffer] = None,
                 profile_dir: Optional[str] = None,
                 host: str = "127.0.0.1", port: int = 0):
        self._metrics_fn = metrics_fn
        self._health_fn = health_fn or (lambda: {"ok": True})
        self._buffer = (trace_buffer if trace_buffer is not None
                        else trace_mod.default_buffer())
        self._profile_dir = profile_dir
        self._profile_lock = threading.Lock()
        owner = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):  # quiet: tests hit this
                pass

            def do_GET(self):
                owner._route(self)

        self._httpd = ThreadingHTTPServer((host, port), Handler)
        self._httpd.daemon_threads = True
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="obs-http",
            daemon=True)
        self._thread.start()

    @property
    def port(self) -> int:
        return self._httpd.server_address[1]

    @property
    def url(self) -> str:
        host, port = self._httpd.server_address[:2]
        return f"http://{host}:{port}"

    def close(self) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()
        self._thread.join(2.0)

    # -- request routing ---------------------------------------------------

    def _route(self, handler: BaseHTTPRequestHandler) -> None:
        parsed = urlparse(handler.path)
        path = parsed.path.rstrip("/") or "/"
        try:
            if path == "/metrics":
                self._send(handler, 200,
                           self._metrics_fn() + self._dropped_spans(),
                           "text/plain; version=0.0.4; charset=utf-8")
            elif path == "/healthz":
                health = self._health_fn()
                code = 200 if health.get("ok") else 503
                self._send_json(handler, code, health)
            elif path == "/traces":
                self._send_json(handler, 200,
                                {"traces": self._buffer.trace_ids()})
            elif path.startswith("/traces/"):
                trace_id = path[len("/traces/"):]
                spans = self._buffer.get(trace_id)
                if spans is None:
                    self._send_json(handler, 404,
                                    {"error": "unknown trace",
                                     "trace_id": trace_id})
                else:
                    self._send_json(handler, 200,
                                    {"trace_id": trace_id,
                                     "spans": spans})
            elif path == "/profile":
                q = parse_qs(parsed.query)
                seconds = float(q.get("seconds", ["1"])[0])
                self._profile(handler, seconds)
            else:
                self._send_json(handler, 404, {"error": "not found",
                                               "path": path})
        except BrokenPipeError:
            pass  # client went away mid-reply — nothing to salvage
        except Exception as e:  # endpoint must answer, never hang
            try:
                self._send_json(handler, 500, {"error": str(e)})
            except OSError:
                pass  # connection already unusable

    def _dropped_spans(self) -> str:
        dropped = self._buffer.dropped_spans + trace_mod.timeline().dropped
        return ("# HELP obs_spans_dropped_total spans the trace buffer "
                "refused and the step timeline overwrote\n"
                "# TYPE obs_spans_dropped_total counter\n"
                f"obs_spans_dropped_total {dropped}\n")

    def _profile(self, handler: BaseHTTPRequestHandler,
                 seconds: float) -> None:
        if not self._profile_dir:
            self._send_json(handler, 501,
                            {"error": "no profile_dir configured"})
            return
        seconds = max(0.05, min(seconds, _MAX_PROFILE_SECONDS))
        if not self._profile_lock.acquire(blocking=False):
            self._send_json(handler, 409,
                            {"error": "capture already running"})
            return
        try:
            import jax

            jax.profiler.start_trace(self._profile_dir)
            time.sleep(seconds)
            jax.profiler.stop_trace()
        except Exception as e:  # profiler backend drift — report, don't die
            self._send_json(handler, 500, {"error": str(e)})
            return
        finally:
            self._profile_lock.release()
        self._send_json(handler, 200, {"ok": True,
                                       "dir": self._profile_dir,
                                       "seconds": seconds})

    # -- low-level senders -------------------------------------------------

    @staticmethod
    def _send(handler, code: int, body: str, content_type: str) -> None:
        data = body.encode("utf-8")
        handler.send_response(code)
        handler.send_header("Content-Type", content_type)
        handler.send_header("Content-Length", str(len(data)))
        handler.end_headers()
        handler.wfile.write(data)

    def _send_json(self, handler, code: int, obj: dict) -> None:
        self._send(handler, code, json.dumps(obj, sort_keys=True),
                   "application/json")
