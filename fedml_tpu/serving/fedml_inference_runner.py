"""HTTP inference runner: POST /predict, GET /ready.

Reference: python/fedml/serving/fedml_inference_runner.py:8-50 (FastAPI +
uvicorn). This environment has no FastAPI, so the same two routes are served
by a stdlib ThreadingHTTPServer; when FastAPI is importable the FastAPI app
is used instead (build_fastapi_app), keeping the reference's exact route
contract either way.
"""

from __future__ import annotations

import asyncio
import json
import logging
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Optional

from ..core import telemetry as tel
from ..core.telemetry import prom, slo, statusz, trace_context
from .admission import AdmissionError
from .fedml_predictor import FedMLPredictor

log = logging.getLogger(__name__)


class FedMLInferenceRunner:
    def __init__(self, client_predictor: FedMLPredictor, port: int = 2345, host: str = "127.0.0.1"):
        self.client_predictor = client_predictor
        self.port = port
        self.host = host
        self._server: Optional[ThreadingHTTPServer] = None
        self._thread: Optional[threading.Thread] = None
        self._slo: Optional[slo.SLOEngine] = None
        # a predictor over the continuous-batching engine interleaves its
        # concurrent requests itself (serving/continuous_batching.py): each
        # connection's thread calls predict and parks on its future
        self.engine = getattr(client_predictor, "engine", None)

    # -- stdlib path -------------------------------------------------------
    def _make_handler(self):
        predictor = self.client_predictor
        engine = self.engine

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):  # route to logging, not stderr
                log.debug("inference http: " + fmt, *args)

            def _send_json(self, obj: Any, code: int = 200) -> None:
                body = json.dumps(obj).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                if self.path == "/ready":
                    if predictor.ready():
                        self._send_json({"status": "Success"})
                    else:
                        self._send_json({"status": "Initializing"}, code=202)
                elif self.path == "/metrics":
                    gauges = [("predictor_ready", None, 1.0 if predictor.ready() else 0.0)]
                    if engine is not None:
                        # autoscaler/load-test signals: slot occupancy +
                        # queue depth (TTFT/TPOT ride along automatically
                        # as serving_cb_* histograms in the registry)
                        st = engine.stats()
                        gauges += [
                            ("serving_cb_slots_total", None, float(st["slots_total"])),
                            ("serving_cb_slots_active", None, float(st["slots_active"])),
                            ("serving_cb_slot_occupancy", None, float(st["slot_occupancy"])),
                            ("serving_cb_queue_depth", None, float(st["queue_depth"])),
                        ]
                        # KV page occupancy, prefix-cache size, per-tenant
                        # TTFT p99, admission burn/usage/budget
                        # (serving_kv_* / serving_tenant_*)
                        gauges += engine.prom_gauges()
                    body = prom.render(gauges=gauges).encode()
                    self.send_response(200)
                    self.send_header("Content-Type", prom.CONTENT_TYPE)
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                elif self.path == "/statusz":
                    doc = statusz.render(service="inference_runner", extra={
                        "predictor_ready": bool(predictor.ready()),
                        "continuous_batching": None if engine is None else engine.stats(),
                    })
                    self._send_json(doc)
                else:
                    self._send_json({"error": "not found"}, code=404)

            def do_POST(self):
                if self.path != "/predict":
                    self._send_json({"error": "not found"}, code=404)
                    return
                # the request's id: the gateway's (Endpoint.predict sends it
                # as a traceparent header), else minted here; active on this
                # thread so the predictor and engine.submit stamp their spans
                ctx = (trace_context.TraceContext.from_traceparent(
                           self.headers.get(trace_context.TRACEPARENT_HEADER))
                       or trace_context.TraceContext(trace_context.new_trace_id()))
                with trace_context.activated(ctx), \
                        tel.span("serving.http.request", request_id=ctx.trace_id):
                    self._predict()

            def _predict(self):
                try:
                    length = int(self.headers.get("Content-Length", 0))
                    input_json = json.loads(self.rfile.read(length) or b"{}")
                    try:
                        resp = predictor.predict(input_json)
                    except NotImplementedError:
                        # predictor implements only async_predict (allowed by
                        # the FedMLPredictor contract)
                        resp = predictor.async_predict(input_json)
                    if asyncio.iscoroutine(resp):
                        resp = asyncio.run(resp)
                    self._send_json(resp)
                except AdmissionError as e:
                    # shed at the front door (budget / SLO pressure /
                    # queue full): 429 tells the client to back off —
                    # this is policy working, not a server fault, so no
                    # error log and no 500
                    self._send_json(
                        {"error": "admission_rejected", "tenant": e.tenant,
                         "reason": e.reason}, code=429)
                except Exception as e:  # noqa: BLE001 - request boundary
                    log.exception("predict failed")
                    self._send_json({"error": repr(e)}, code=500)

        return Handler

    def start(self) -> int:
        """Non-blocking start; returns the bound port (0 picks a free one)."""

        class _Server(ThreadingHTTPServer):
            # socketserver's default listen backlog is 5: a 1k-stream load
            # burst overflows the accept queue and clients see connection
            # resets before the first byte is served
            request_queue_size = 1024
            daemon_threads = True

        self._server = _Server((self.host, self.port), self._make_handler())
        self.port = self._server.server_address[1]
        self._thread = threading.Thread(target=self._server.serve_forever, daemon=True)
        self._thread.start()
        # serving SLO pack (TTFT/TPOT p99 ceilings, error rate) evaluated on
        # a background ticker (FEDML_SLO_TICK_S) for the replica's lifetime
        self._slo = slo.activate(None, front="serving")
        return self.port

    def stop(self) -> None:
        slo.deactivate(getattr(self, "_slo", None))
        self._slo = None
        if self.engine is not None:
            self.engine.shutdown()
        if self._server is not None:
            self._server.shutdown()
            self._server.server_close()
            self._server = None

    def run(self) -> None:
        """Blocking serve (reference run() semantics)."""
        try:
            from .fastapi_app import run_fastapi

            run_fastapi(self.client_predictor, self.host, self.port)
            return
        except ImportError:
            pass
        self.start()
        assert self._thread is not None
        self._thread.join()
