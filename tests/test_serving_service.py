"""Generation service behind the master: the devcluster-style serving
drill (concurrent SSE streams through the proxy with mid-flight batch
composition changes, asserted via the serving metrics), load shedding
over HTTP, and the proxy's unbuffered streaming pass-through."""
import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest
import requests

from determined_tpu.common import faults
from determined_tpu.common.metrics import (
    REGISTRY,
    parse_exposition,
    sample_value,
)
from determined_tpu.master.api_server import ApiServer
from determined_tpu.master.core import Master
from determined_tpu.serving.loadgen import _iter_sse_lines, drive
from determined_tpu.serving.service import GenerationServer
from tests.test_serving import make_engine


@pytest.fixture()
def cluster():
    """Master + API + one serving replica registered in the proxy (the
    in-process devcluster shape: same wiring as a SERVING task that
    registered its port, without the subprocess)."""
    master = Master()
    api = ApiServer(master)
    api.start()
    engine = make_engine(
        max_batch_size=8, prefill_rows=4, prefill_seq=64,
        num_pages=65, max_pages_per_request=4,
        # the whole drill burst may sit queued while the first prefill
        # compiles — the queue bound must admit it (shedding is exercised
        # separately, deterministically, via the admission fault site)
        max_queue_depth=32,
    )
    engine.start()
    server = GenerationServer(engine)
    server.start()
    master.alloc_service.create(
        "serve.1.0", task_id="serving-1", trial_id=None,
        num_processes=1, slots=0,
    )
    requests.post(
        f"{api.url}/api/v1/allocations/serve.1.0/proxy",
        json={"host": "127.0.0.1", "port": server.port}, timeout=10,
    ).raise_for_status()
    yield master, api, engine, f"{api.url}/proxy/serving-1"
    server.stop()
    engine.stop()
    api.stop()
    master.shutdown()


def _counter(name, **labels):
    fam = REGISTRY.get(name)
    child = fam.labels(**labels) if labels else fam
    return child.value


class TestServingDrill:
    def test_concurrent_streams_through_master_proxy(self, cluster):
        """The acceptance drill: >= 8 concurrent streaming requests
        through the master proxy, iteration-level batch composition
        changing mid-flight, asserted via the serving metrics."""
        from determined_tpu.serving.engine import BATCH_JOINS, REQUESTS

        master, api, engine, proxy_url = cluster
        ok_before = REQUESTS.labels("ok").value
        joins_before = BATCH_JOINS.value
        # The engine is warm when it starts accepting (start() compiles),
        # so the overlap has to come from the work itself: 31 decode
        # iterations a request, arrivals 10 ms apart.
        report = drive(
            proxy_url, n_requests=10, concurrency=10,
            prompt_len=6, max_new_tokens=32, stagger_s=0.01,
        )
        assert report.completed == 10, [t.error for t in report.traces]
        assert report.total_tokens == 320
        assert report.tokens_per_sec > 0
        assert report.ttft_percentile_ms(99) > 0
        # batch composition changed mid-flight: the staggered tail joined
        # a non-empty batch (late join) and early finishers left while
        # others decoded — all pages back afterwards.
        assert BATCH_JOINS.value > joins_before
        assert REQUESTS.labels("ok").value == ok_before + 10
        assert engine.pool.pages_in_use == 0
        # the serving metrics are scrapeable THROUGH the proxy, and the
        # decode ran the flash kv_offset path (Pallas on TPU; this CPU
        # suite runs the blockwise reference of the same kernel math —
        # `chip_smoke.py` asserts "pallas" on the chip).
        text = requests.get(f"{proxy_url}/metrics", timeout=10).text
        samples = parse_exposition(text)
        assert sample_value(samples, "dtpu_serving_tokens_total") >= 320
        stats = requests.get(f"{proxy_url}/api/v1/stats", timeout=10).json()
        import jax

        expect = "pallas" if jax.default_backend() == "tpu" else "reference"
        assert stats["decode_backend"] == expect

    def test_late_join_completes_while_early_stream_open(self, cluster):
        """Mid-flight composition, observed from the client side: a late
        SHORT request is submitted after a LONG stream's first token and
        its `done` arrives while the long stream is still emitting."""
        master, api, engine, proxy_url = cluster
        long_resp = requests.post(
            f"{proxy_url}/api/v1/generate",
            json={"prompt": [3, 1, 4, 1, 5], "max_new_tokens": 30},
            stream=True, timeout=120,
        )
        assert long_resp.status_code == 200
        long_lines = _iter_sse_lines(long_resp)
        first = next(
            ln for ln in long_lines if ln.startswith("event: token")
        )
        assert first  # long request is mid-decode
        short = requests.post(
            f"{proxy_url}/api/v1/generate",
            json={"prompt": [9, 8], "max_new_tokens": 2, "stream": False},
            timeout=120,
        )
        assert short.status_code == 200
        body = short.json()
        assert body["reason"] == "length" and len(body["tokens"]) == 2
        # the long stream is still live: more tokens then a clean done
        events = [ln for ln in long_lines if ln.startswith("event: ")]
        long_resp.close()
        assert any(e == "event: token" for e in events)
        assert events[-1] == "event: done"

    def test_shed_is_503_with_retry_after(self, cluster):
        master, api, engine, proxy_url = cluster
        plan = faults.FaultPlan(
            {"serving.admission": faults.FaultSpec(failures=1)}
        )
        with faults.plan_active(plan):
            resp = requests.post(
                f"{proxy_url}/api/v1/generate",
                json={"prompt": [1, 2], "max_new_tokens": 1}, timeout=30,
            )
        assert resp.status_code == 503
        assert float(resp.headers["Retry-After"]) > 0
        assert "shed" in resp.json()["error"]

    def test_client_errors_are_400(self, cluster):
        master, api, engine, proxy_url = cluster
        r = requests.post(
            f"{proxy_url}/api/v1/generate",
            json={"prompt": list(range(100))}, timeout=30,
        )
        assert r.status_code == 400
        r = requests.post(
            f"{proxy_url}/api/v1/generate", json={"nope": 1}, timeout=30
        )
        assert r.status_code == 400
        r = requests.post(
            f"{proxy_url}/api/v1/generate",
            json={"prompt": ["a"]}, timeout=30,
        )
        assert r.status_code == 400
        # malformed numeric fields are client errors too, never 500s
        for bad in (
            {"prompt": [1], "deadline_ms": "soon"},
            {"prompt": [1], "max_new_tokens": "many"},
            {"prompt": [1], "temperature": "warm"},
        ):
            r = requests.post(
                f"{proxy_url}/api/v1/generate", json=bad, timeout=30
            )
            assert r.status_code == 400, (bad, r.status_code)
            assert "must be a number" in r.json()["error"]

    def test_text_prompt_and_healthz(self, cluster):
        master, api, engine, proxy_url = cluster
        r = requests.post(
            f"{proxy_url}/api/v1/generate",
            json={"text": "hi", "max_new_tokens": 2, "stream": False},
            timeout=120,
        )
        assert r.status_code == 200
        assert len(r.json()["tokens"]) == 2
        h = requests.get(f"{proxy_url}/healthz", timeout=10).json()
        assert h["status"] == "ok"


class TestServingTaskShape:
    def test_create_command_serving_defaults_and_validates(self):
        """task_type SERVING: entrypoint defaults to the generation
        service, the serving section is validated at create with named
        errors, and it rides into the task env for the service to read."""
        master = Master()
        try:
            tid = master.create_command(
                {"task_type": "SERVING", "serving": {"page_size": 64}}
            )
            cmd = master._commands[tid]
            assert cmd["config"]["entrypoint"] == (
                "python -m determined_tpu.serving.service"
            )
            env = cmd["config"]["environment"]["variables"]
            assert json.loads(env["DTPU_SERVING_CONFIG"]) == {"page_size": 64}
            with pytest.raises(ValueError, match="unknown key 'bogus'"):
                master.create_command(
                    {"task_type": "SERVING", "serving": {"bogus": 1}}
                )
        finally:
            master.shutdown()


def _slow_sse_backend(n_events: int = 4, gap_s: float = 0.25):
    class H(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def do_GET(self):
            self.send_response(200)
            self.send_header("Content-Type", "text/event-stream")
            self.send_header("Connection", "close")
            self.close_connection = True
            self.end_headers()
            for i in range(n_events):
                self.wfile.write(f"data: {i}\n\n".encode())
                self.wfile.flush()
                time.sleep(gap_s)

        def do_POST(self):
            n = int(self.headers.get("Content-Length", 0))
            data = self.rfile.read(n)
            self.send_response(200)
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)

        def log_message(self, *a):
            pass

    srv = ThreadingHTTPServer(("127.0.0.1", 0), H)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv


class TestProxyStreamingPassThrough:
    def test_sse_passes_through_unbuffered(self):
        """Satellite: the master proxy must NOT buffer a streaming
        response — the first event of a slow 1 s stream must reach the
        client in well under the stream's total duration (a buffering
        proxy turns TTFT into total latency)."""
        master = Master()
        api = ApiServer(master)
        api.start()
        srv = _slow_sse_backend(n_events=4, gap_s=0.25)  # ~0.75 s total
        try:
            master.alloc_service.create(
                "sse.1.0", task_id="sse-task", trial_id=None,
                num_processes=1, slots=0,
            )
            requests.post(
                f"{api.url}/api/v1/allocations/sse.1.0/proxy",
                json={"host": "127.0.0.1", "port": srv.server_address[1]},
                timeout=10,
            ).raise_for_status()
            t0 = time.time()
            resp = requests.get(
                f"{api.url}/proxy/sse-task/stream", stream=True, timeout=30
            )
            first_line = next(
                ln for ln in _iter_sse_lines(resp) if ln.startswith("data:")
            )
            t_first = time.time() - t0
            rest = list(_iter_sse_lines(resp))
            t_total = time.time() - t0
            resp.close()
            assert first_line == "data: 0"
            assert sum(1 for ln in rest if ln.startswith("data:")) == 3
            # first event promptly, and well before the stream finished
            assert t_first < 0.5 * t_total, (t_first, t_total)
            assert t_total > 0.6  # the stream really was slow
        finally:
            srv.shutdown()
            api.stop()
            master.shutdown()

    def test_buffered_forward_surfaces_truncation_as_502(self):
        """A backend that advertises Content-Length then dies mid-body
        must not come back from the BUFFERED forward() API as a silently
        truncated 200 (streaming callers compare sent-vs-advertised
        bytes themselves; buffered callers cannot)."""

        class H(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def do_GET(self):
                self.send_response(200)
                self.send_header("Content-Length", "100")
                self.end_headers()
                self.wfile.write(b"hello")   # 5 of the promised 100 bytes
                self.wfile.flush()
                self.connection.close()

            def log_message(self, *a):
                pass

        srv = ThreadingHTTPServer(("127.0.0.1", 0), H)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        master = Master()
        try:
            master.proxy.register(
                "trunc-task", "127.0.0.1", srv.server_address[1]
            )
            status, headers, body = master.proxy.forward(
                "trunc-task", "GET", "/thing", "", {}, b""
            )
            assert status == 502
            assert b"mid-response" in body
        finally:
            srv.shutdown()
            master.shutdown()

    def test_buffered_bodies_keep_content_length(self):
        """Plain responses still pass through with their length (and the
        connection stays usable for the next request)."""
        master = Master()
        api = ApiServer(master)
        api.start()
        srv = _slow_sse_backend()
        try:
            master.alloc_service.create(
                "echo.1.0", task_id="echo-task", trial_id=None,
                num_processes=1, slots=0,
            )
            requests.post(
                f"{api.url}/api/v1/allocations/echo.1.0/proxy",
                json={"host": "127.0.0.1", "port": srv.server_address[1]},
                timeout=10,
            ).raise_for_status()
            with requests.Session() as s:
                for payload in (b"hello", b"world"):
                    r = s.post(
                        f"{api.url}/proxy/echo-task/echo", data=payload,
                        timeout=30,
                    )
                    assert r.status_code == 200
                    assert r.content == payload
                    assert r.headers.get("Content-Length") == str(
                        len(payload)
                    )
        finally:
            srv.shutdown()
            api.stop()
            master.shutdown()
