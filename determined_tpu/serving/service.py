"""The generation service's HTTP surface.

Runs as a long-running task the master schedules (task_type SERVING,
entrypoint ``python -m determined_tpu.serving.service``); it registers its
port in the master's ProxyRegistry like any interactive task, so clients
hit ``<master>/proxy/<task_id>/api/v1/generate`` and token streams pass
through the (unbuffered) proxy.

Routes — every one flows through the single instrumented dispatch, so the
request histogram + span cover new routes by construction, the same
discipline as the master's API server (tests/test_metrics_discipline.py
sweeps these too):

- ``POST /api/v1/generate`` — body ``{"prompt": [ids]}`` (or ``"text"``,
  byte-tokenized) plus ``max_new_tokens`` / ``deadline_ms`` /
  ``temperature`` / ``stream``. ``stream: true`` (default) answers
  Server-Sent Events::

      event: token    data: {"token": 17, "index": 0}
      ...
      event: done     data: {"reason": "length", "ttft_ms": ..., ...}

  a mid-flight failure ends the stream with ``event: error``. Shed
  requests answer 503 with a ``Retry-After`` header; impossible ones
  (prompt exceeds the replica context) answer 400.
- ``GET /api/v1/stats`` — engine snapshot (queue/batch/pages/backend).
- ``GET /healthz`` — liveness.
- ``GET /metrics`` — the process-global registry, Prometheus text format.
"""
from __future__ import annotations

import json
import logging
import re
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Callable, Dict, List, Optional, Tuple
from urllib.parse import parse_qs, urlparse

from determined_tpu.common import trace as trace_mod
from determined_tpu.common.metrics import REGISTRY as METRICS
from determined_tpu.serving.engine import (
    GenerationEngine,
    PromptTooLong,
    Request,
    Shed,
)

logger = logging.getLogger("determined_tpu.serving")

SERVING_REQUESTS = METRICS.counter(
    "dtpu_serving_api_requests_total",
    "Serving HTTP requests by method, route pattern, and status.",
    labels=("method", "route", "status"),
)
SERVING_LATENCY = METRICS.histogram(
    "dtpu_serving_api_request_duration_seconds",
    "Serving HTTP latency by method and route pattern (SSE generate "
    "streams are observed at stream start, by design — their duration is "
    "the generation, not the route).",
    labels=("method", "route"),
)

#: generous default body cap — prompts are token lists, not uploads.
MAX_BODY_BYTES = 8 * 1024 * 1024

Handler = Callable[[Dict[str, Any], Dict[str, List[str]]], Any]


class _SSEGenerate(Exception):
    """Control-flow: answer with the request's SSE token stream."""

    def __init__(self, req: Request) -> None:
        super().__init__("sse stream")
        self.req = req


class _PlainText(Exception):
    def __init__(self, text: str, content_type: str) -> None:
        super().__init__("plaintext")
        self.text = text
        self.content_type = content_type


class _HttpError(Exception):
    def __init__(self, status: int, message: str,
                 headers: Optional[Dict[str, str]] = None) -> None:
        super().__init__(message)
        self.status = status
        self.headers = headers or {}


def tokenize(body: Dict[str, Any]) -> List[int]:
    """Prompt tokens from a request body: explicit ``prompt`` ids win;
    ``text`` falls back to byte-level ids (every model vocab here is
    >= 256, so bytes are always in-vocab — a demo tokenizer, not BPE)."""
    if "prompt" in body:
        prompt = body["prompt"]
        if not isinstance(prompt, list) or not all(
            isinstance(t, int) and not isinstance(t, bool) for t in prompt
        ):
            raise _HttpError(400, "prompt must be a list of token ids")
        return prompt
    if "text" in body:
        if not isinstance(body["text"], str):
            raise _HttpError(400, "text must be a string")
        return list(body["text"].encode("utf-8"))
    raise _HttpError(400, "body must carry prompt (token ids) or text")


def _num_field(body: Dict[str, Any], key: str) -> Optional[float]:
    """Optional numeric body field; a non-numeric value is a 400 client
    error, never a 500 (float("soon") must not read as a server fault)."""
    v = body.get(key)
    if v is None:
        return None
    if isinstance(v, bool) or not isinstance(v, (int, float)):
        raise _HttpError(400, f"{key} must be a number")
    return float(v)


def build_serving_routes(
    engine: GenerationEngine,
) -> List[Tuple[str, re.Pattern, Handler]]:
    def generate(body: Dict[str, Any], query: Dict[str, List[str]]):
        prompt = tokenize(body)
        deadline_ms = _num_field(body, "deadline_ms")
        max_new = _num_field(body, "max_new_tokens")
        temperature = _num_field(body, "temperature")
        try:
            req = engine.submit(
                prompt,
                max_new_tokens=int(max_new) if max_new is not None else None,
                deadline_s=(
                    deadline_ms / 1e3 if deadline_ms is not None else None
                ),
                temperature=temperature or 0.0,
                trace=trace_mod.current(),
            )
        except PromptTooLong as e:
            raise _HttpError(400, str(e))
        except Shed as e:
            # Load shedding IS the contract under saturation: the client
            # backs off for Retry-After seconds instead of queueing into
            # a deadline it can no longer make.
            raise _HttpError(
                503, str(e),
                headers={"Retry-After": f"{e.retry_after:g}"},
            )
        if body.get("stream", True):
            raise _SSEGenerate(req)
        return req.result()

    def stats(body, query):
        return engine.stats()

    def healthz(body, query):
        return {"status": "ok", **engine.stats()}

    def metrics(body, query):
        # exemplars ride as comment lines; the master's scrape sweep
        # harvests them so p99 TTFT answers can name the slow trace.
        raise _PlainText(
            METRICS.render(exemplars=True), "text/plain; version=0.0.4"
        )

    R = lambda method, pat, h: (method, re.compile(f"^{pat}$"), h)  # noqa: E731
    return [
        R("POST", r"/api/v1/generate", generate),
        R("GET", r"/api/v1/stats", stats),
        R("GET", r"/healthz", healthz),
        R("GET", r"/metrics", metrics),
    ]


class GenerationServer:
    """stdlib ThreadingHTTPServer front end over a GenerationEngine.

    Same shape as the master's ApiServer: one dispatch path carries the
    metrics/span instrumentation; SSE responses own their socket and
    close it when the stream ends.
    """

    def __init__(self, engine: GenerationEngine, host: str = "127.0.0.1",
                 port: int = 0) -> None:
        routes = build_serving_routes(engine)

        class _Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"
            # Nagle × delayed-ACK stalls small writes ~40 ms — fatal to
            # SSE token TTFT on a keep-alive socket (same fix as the
            # master's ApiServer).
            disable_nagle_algorithm = True

            def log_message(self, fmt: str, *args: Any) -> None:
                logger.debug("serving http: " + fmt, *args)

            def _dispatch(self, method: str) -> None:
                parsed = urlparse(self.path)
                length = int(self.headers.get("Content-Length") or 0)
                if length > MAX_BODY_BYTES:
                    self._send(413, {"error": "request body too large"},
                               close=True)
                    return
                body: Dict[str, Any] = {}
                if length:
                    raw = self.rfile.read(length)
                    try:
                        body = json.loads(raw or b"{}")
                    except json.JSONDecodeError:
                        self._send(400, {"error": "bad json"})
                        return
                    if not isinstance(body, dict):
                        self._send(400, {"error": "body must be an object"})
                        return
                for m_, pat, handler in routes:
                    if m_ != method:
                        continue
                    if not pat.match(parsed.path):
                        continue
                    t_start = time.monotonic()
                    finished = False

                    def finish(status: int) -> None:
                        # ONE observation per request wherever it
                        # completes — including at SSE stream START
                        # (stream lifetime is generation time, not
                        # route latency).
                        nonlocal finished
                        if finished:
                            return
                        finished = True
                        SERVING_LATENCY.labels(method, pat.pattern).observe(
                            time.monotonic() - t_start
                        )
                        SERVING_REQUESTS.labels(
                            method, pat.pattern, str(status)
                        ).inc()

                    status_code = 200
                    try:
                        with trace_mod.span(
                            f"http {method} {pat.pattern}",
                            {"http.method": method,
                             "http.target": parsed.path},
                            parent=trace_mod.parse_traceparent(
                                self.headers.get("traceparent")
                            ),
                        ):
                            # Expected outcomes (SSE handoff, plaintext,
                            # client errors/sheds) resolve INSIDE the
                            # span so they export as normal spans — only
                            # a real handler crash escapes the `with` and
                            # marks the http span errored.
                            try:
                                outcome = (
                                    "json",
                                    handler(body, parse_qs(parsed.query)),
                                )
                            except _SSEGenerate as es:
                                outcome = ("sse", es.req)
                            except _PlainText as pt:
                                outcome = ("plain", pt)
                            except _HttpError as e:
                                outcome = ("http_error", e)
                        kind, payload = outcome
                        if kind == "sse":
                            finish(200)
                            self._stream_sse(payload)
                            return
                        if kind == "json":
                            self._send(
                                200, payload if payload is not None else {}
                            )
                        elif kind == "plain":
                            data = payload.text.encode()
                            self.send_response(200)
                            self.send_header(
                                "Content-Type", payload.content_type
                            )
                            self.send_header(
                                "Content-Length", str(len(data))
                            )
                            self.end_headers()
                            self.wfile.write(data)
                        else:
                            status_code = payload.status
                            self._send(
                                payload.status, {"error": str(payload)},
                                headers=payload.headers,
                            )
                    except (BrokenPipeError, ConnectionResetError):
                        status_code = 0
                    except Exception as e:  # noqa: BLE001
                        status_code = 500
                        logger.exception(
                            "serving handler error %s %s", method, parsed.path
                        )
                        self._send(500, {"error": str(e)})
                    finally:
                        finish(status_code)
                    return
                self._send(404, {"error": f"no route {method} {parsed.path}"})

            def _stream_sse(self, req: Request) -> None:
                """Token events as they leave the engine; the stream owns
                the socket (no keep-alive reuse after an open-ended
                response)."""
                self.send_response(200)
                self.send_header("Content-Type", "text/event-stream")
                self.send_header("Cache-Control", "no-cache")
                self.send_header("Connection", "close")
                self.close_connection = True
                self.end_headers()
                try:
                    for i, (kind, payload) in enumerate(req.stream()):
                        if kind == "token":
                            data = json.dumps({"token": payload, "index": i})
                        elif kind == "done":
                            data = json.dumps(payload)
                        else:
                            data = json.dumps({"error": payload})
                        self.wfile.write(
                            f"id: {i}\nevent: {kind}\ndata: {data}\n\n"
                            .encode()
                        )
                        self.wfile.flush()
                except (BrokenPipeError, ConnectionResetError, OSError):
                    pass  # client went away; the engine finishes regardless

            def _send(self, status: int, payload: Dict[str, Any],
                      close: bool = False,
                      headers: Optional[Dict[str, str]] = None) -> None:
                data = json.dumps(payload).encode()
                self.send_response(status)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(data)))
                for k, v in (headers or {}).items():
                    self.send_header(k, v)
                if close:
                    self.send_header("Connection", "close")
                    self.close_connection = True
                self.end_headers()
                self.wfile.write(data)

            def do_GET(self) -> None:  # noqa: N802
                self._dispatch("GET")

            def do_POST(self) -> None:  # noqa: N802
                self._dispatch("POST")

        self._httpd = ThreadingHTTPServer((host, port), _Handler)
        self._httpd.daemon_threads = True
        self.port = self._httpd.server_address[1]
        self.url = f"http://{host}:{self.port}"
        self._thread: Optional[threading.Thread] = None

    def start(self) -> None:
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="serving-http", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()


def build_engine(serving_cfg: Dict[str, Any]) -> GenerationEngine:
    """Model + engine from a config's `serving:` section. Params come
    from DTPU_SERVING_CHECKPOINT (a manifest-verified checkpoint
    directory in the trainer's save_pytree layout) when set, otherwise
    random init — the dev/test default."""
    import dataclasses
    import os

    import jax

    from determined_tpu.models import gpt as gpt_mod
    from determined_tpu.serving.config import ServingConfig

    from determined_tpu.serving.fixture import fixture_model_config

    cfg = ServingConfig.from_dict(serving_cfg or {})
    config_builder = {"tiny": gpt_mod.tiny, "small": gpt_mod.small,
                      "medium": gpt_mod.medium,
                      "fixture": fixture_model_config}[cfg.model]
    model = gpt_mod.GPT(config_builder())
    if cfg.prefill_seq > model.config.seq_len:
        # A small model with the default prefill geometry must come up
        # serving (shorter prompts), not refuse to start.
        cfg = dataclasses.replace(cfg, prefill_seq=model.config.seq_len)
    ckpt_dir = os.environ.get("DTPU_SERVING_CHECKPOINT", "")
    if ckpt_dir:
        # Manifest verification BEFORE the weights go live: a torn or
        # bit-flipped checkpoint is a named refusal at startup, not a
        # silently-wrong model serving traffic.
        from determined_tpu.storage.base import verify_checkpoint_dir
        from determined_tpu.trainer import _checkpoint as ckpt

        verify_checkpoint_dir(ckpt_dir)
        like = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        params = ckpt.load_pytree(ckpt_dir, like)
        logger.info("serving params restored from %s", ckpt_dir)
    else:
        params = model.init(jax.random.PRNGKey(0))
    return GenerationEngine(model, params, cfg)


def main(argv: Optional[List[str]] = None) -> int:
    """Task entrypoint: `python -m determined_tpu.serving.service`.

    Reads the serving section from DTPU_SERVING_CONFIG (JSON, injected by
    the master's SERVING task launch), serves on an OS-assigned port, and
    registers it through the allocation's proxy route so the master
    fronts the traffic.
    """
    import argparse
    import os

    parser = argparse.ArgumentParser()
    parser.add_argument("--host", default="0.0.0.0")
    parser.add_argument("--port", type=int, default=0)
    parser.add_argument("--config", default="", help="serving config JSON")
    args = parser.parse_args(argv)
    raw = args.config or os.environ.get("DTPU_SERVING_CONFIG", "") or "{}"
    from determined_tpu.common import compile_cache

    compile_cache.enable()
    engine = build_engine(json.loads(raw))
    # Compiles prefill/decode/verify before the port opens; a program the
    # compiler refuses raises here and the process exits non-zero.
    engine.start()
    server = GenerationServer(engine, host=args.host, port=args.port)
    server.start()
    logger.info("generation service on %s", server.url)
    from determined_tpu.exec.proxy_util import register_proxy

    register_proxy(server.port)
    # Continuous-profiling plane: sample this replica's threads (decode
    # loop, SSE writers) when the master enabled it for the task env.
    from determined_tpu.common import logship as logship_mod
    from determined_tpu.common import profiling as profiling_mod

    task_id = os.environ.get("DTPU_TASK_ID") or "serving"
    profiling_mod.maybe_start_from_env(target=f"serving:{task_id}")
    # Structured log plane: this replica's records (admission decisions,
    # preemption drain, capture runs) ship as structured lines under the
    # serving identity when the master enabled the plane in the task env.
    logship_mod.maybe_start_from_env(
        target=f"serving:{task_id}", labels={"task": task_id},
    )
    # The idle loop doubles as the replica's control channel: poll the
    # allocation's preemption signal (short timeout — a capture directive
    # rides back on poll RETURN, so the timeout bounds its latency) and
    # run operator-triggered bounded XLA captures in place.
    master = os.environ.get("DTPU_MASTER")
    alloc = os.environ.get("DTPU_ALLOCATION_ID")
    session = None
    if master and alloc:
        from determined_tpu.common.api_session import Session

        session = Session(
            master, token=os.environ.get("DTPU_SESSION_TOKEN", ""),
            max_retries=1,
        )
    try:
        while True:
            if session is None:
                time.sleep(3600)
                continue
            try:
                resp = session.get(
                    f"/api/v1/allocations/{alloc}/signals/preemption",
                    params={"timeout_seconds": 5}, timeout=15,
                ) or {}
            except Exception:  # noqa: BLE001 — master away; keep serving
                time.sleep(5)  # resilience-ok: fixed-cadence signal poll, not a retry
                continue
            cap = resp.get("profile_capture")
            if cap:
                from determined_tpu.profiler import run_bounded_capture

                run_bounded_capture(session, cap)
            if resp.get("preempt"):
                logger.info("preemption signal; draining and exiting")
                break
    except KeyboardInterrupt:
        pass
    finally:
        profiling_mod.flush_profiler()
        logship_mod.flush_shipping()
        server.stop()
        engine.stop()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
