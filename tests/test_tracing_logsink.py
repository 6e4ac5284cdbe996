"""OTel-semantics tracing + Elasticsearch-compatible log sink
(VERDICT r1 missing #10 and #8; ref master/pkg/opentelemetry/otel.go and
master/internal/elastic/elastic_task_logs.go)."""
import json
import threading
import time
from http.server import BaseHTTPRequestHandler, HTTPServer

import pytest
import requests

from determined_tpu.master.api_server import ApiServer
from determined_tpu.master.core import Master
from determined_tpu.master.tracing import JsonlExporter, Tracer


class TestTracer:
    def test_span_nesting_and_export(self, tmp_path):
        path = str(tmp_path / "spans.jsonl")
        tracer = Tracer(JsonlExporter(path), flush_interval_s=0.1)
        with tracer.span("outer", {"k": "v"}) as outer:
            with tracer.span("inner") as inner:
                assert inner.trace_id == outer.trace_id
                assert inner.parent_span_id == outer.span_id
        tracer.stop()
        spans = [json.loads(l) for l in open(path)]
        by_name = {s["name"]: s for s in spans}
        assert set(by_name) == {"outer", "inner"}
        assert by_name["inner"]["parentSpanId"] == by_name["outer"]["spanId"]
        assert by_name["outer"]["attributes"] == [
            {"key": "k", "value": {"stringValue": "v"}}
        ]
        assert by_name["outer"]["endTimeUnixNano"] >= by_name["outer"]["startTimeUnixNano"]

    def test_error_status(self, tmp_path):
        path = str(tmp_path / "spans.jsonl")
        tracer = Tracer(JsonlExporter(path))
        try:
            with tracer.span("boom"):
                raise RuntimeError("x")
        except RuntimeError:
            pass
        tracer.stop()
        (span,) = [json.loads(l) for l in open(path)]
        assert span["status"]["code"] == 2  # OTLP ERROR

    def test_api_and_allocation_spans(self, tmp_path):
        """The master traces every API request and allocation lifecycle."""
        path = str(tmp_path / "spans.jsonl")
        master = Master(trace_file=path)
        api = ApiServer(master)
        api.start()
        try:
            requests.get(f"{api.url}/api/v1/experiments", timeout=10)
            master.alloc_service.create(
                "a.1.0", task_id="t1", trial_id=None, num_processes=1, slots=1
            )
            master.enqueue_start_actions(
                alloc_id="a.1.0", task_id="t1", task_type="COMMAND",
                entrypoint="true", assignment={"agent-x": 1}, slots=1,
                config={},
            )
            master.alloc_service.complete("a.1.0", exit_code=1, reason="test")
        finally:
            api.stop()
            master.shutdown()  # stops tracer -> final flush
        spans = [json.loads(l) for l in open(path)]
        names = [s["name"] for s in spans]
        assert any("http GET" in n and "experiments" in n for n in names)
        alloc = next(s for s in spans if s["name"] == "allocation")
        attrs = {a["key"]: a["value"] for a in alloc["attributes"]}
        assert attrs["alloc.id"]["stringValue"] == "a.1.0"
        assert attrs["exit_code"]["intValue"] == "1"
        assert alloc["status"]["code"] == 2

    def test_size_trigger_never_blocks_caller(self, tmp_path):
        """Filling a batch wakes the flush thread; end_span must not export
        inline (a slow collector would stall the API thread)."""
        import threading

        release = threading.Event()

        class SlowExporter:
            def __init__(self):
                self.exported = 0

            def export(self, spans):
                release.wait(timeout=10)
                self.exported += len(spans)

        exp = SlowExporter()
        tracer = Tracer(exp, batch_size=2, flush_interval_s=30)
        t0 = time.monotonic()
        for i in range(4):  # two full batches
            s = tracer.start_span(f"s{i}")
            tracer.end_span(s)
        assert time.monotonic() - t0 < 1.0, "end_span blocked on export"
        release.set()
        tracer.stop()
        assert exp.exported == 4

    @pytest.mark.parametrize("traces_config", [None, {"enabled": False}])
    def test_null_tracer_default(self, traces_config):
        """The trace plane is on by default (`masterconf.TRACES_DEFAULTS`):
        a default master's `Tracer` feeds its own `TraceStore`; only
        `traces: {enabled: false}` with no other sink gives `NullTracer`."""
        from determined_tpu.master.tracing import NullTracer

        master = Master(traces_config=traces_config)
        try:
            if traces_config is None:
                assert isinstance(master.tracer, Tracer)
                with master.tracer.span("plane.default"):
                    pass
                master.tracer.flush()
                assert master.tracestore.stats()["spans"] == 1
            else:
                assert isinstance(master.tracer, NullTracer)
                with master.tracer.span("plane.off"):
                    pass
                assert master.tracestore.stats()["spans"] == 0
        finally:
            master.shutdown()


class _BulkCapture(BaseHTTPRequestHandler):
    captured = []

    def do_POST(self):
        n = int(self.headers.get("Content-Length", 0))
        body = self.rfile.read(n).decode()
        type(self).captured.append((self.path, body))
        self.send_response(200)
        self.send_header("Content-Length", "2")
        self.end_headers()
        self.wfile.write(b"{}")

    def log_message(self, *a):
        pass


class TestLogSink:
    def test_bulk_shipping_through_master(self):
        _BulkCapture.captured = []
        srv = HTTPServer(("127.0.0.1", 0), _BulkCapture)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        sink_url = f"http://127.0.0.1:{srv.server_address[1]}"
        master = Master(log_sink_url=sink_url)
        api = ApiServer(master)
        api.start()
        try:
            requests.post(
                f"{api.url}/api/v1/task_logs",
                json={"task_id": "trial-7", "logs": [
                    {"log": "hello", "level": "INFO"},
                    {"log": "world", "level": "ERROR"},
                ]},
                timeout=10,
            ).raise_for_status()
            deadline = time.time() + 15
            while time.time() < deadline and not _BulkCapture.captured:
                time.sleep(0.1)
            assert _BulkCapture.captured, "sink never received a bulk"
            path, body = _BulkCapture.captured[0]
            assert path == "/_bulk?refresh=wait_for"  # NRT parity for the search read path
            lines = [json.loads(l) for l in body.strip().split("\n")]
            # NDJSON action/doc pairs
            assert lines[0] == {"index": {"_index": "dtpu-task-logs"}}
            assert lines[1]["task_id"] == "trial-7"
            assert lines[1]["log"] == "hello"
            assert lines[3]["level"] == "ERROR"
            # SQLite copy still serves the API reads
            logs = requests.get(
                f"{api.url}/api/v1/task_logs?task_id=trial-7", timeout=10
            ).json()["logs"]
            assert [l["log"] for l in logs] == ["hello", "world"]
        finally:
            api.stop()
            master.shutdown()
            srv.shutdown()

    def test_sink_down_never_blocks_ingest(self):
        # Point at a closed port: POSTs must still return instantly.
        master = Master(log_sink_url="http://127.0.0.1:9")  # discard port
        api = ApiServer(master)
        api.start()
        try:
            t0 = time.monotonic()
            for i in range(5):
                requests.post(
                    f"{api.url}/api/v1/task_logs",
                    json={"task_id": "t", "logs": [{"log": f"l{i}"}]},
                    timeout=10,
                ).raise_for_status()
            assert time.monotonic() - t0 < 5.0
            logs = requests.get(
                f"{api.url}/api/v1/task_logs?task_id=t", timeout=10
            ).json()["logs"]
            assert len(logs) == 5  # system of record unaffected
        finally:
            api.stop()
            master.shutdown()
