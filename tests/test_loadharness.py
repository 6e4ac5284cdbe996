"""Control-plane load harness (PR 15): open-loop arrival timing and
coordinated-omission safety (no server needed), SLO self-verdict
known-answers against canned alert surfaces, the two-lane overload
drills — admission shed counted + Retry-After honored while a healthy
neighbor route stays responsive, shippers backing off and RECOVERING
without loss, the master.overload / client.ingest_backoff fault sites —
and a smoke-scale drive of the full scenario mix against a live master
with the verdict read off the real /api/v1/alerts surface. Soak-scale
drives are marked `slow` (tier-1 runs the bounded smoke)."""
import time

import pytest
import requests

from determined_tpu.common import faults, loadharness
from determined_tpu.common import logship
from determined_tpu.common import trace as trace_mod
from determined_tpu.common.api_session import Session
from determined_tpu.common.faults import FaultPlan, FaultSpec
from determined_tpu.common.metrics import REGISTRY
from determined_tpu.master.api_server import ApiServer
from determined_tpu.master.core import Master


def _counter(name: str, **labels) -> float:
    fam = REGISTRY.get(name)
    if fam is None:
        return 0.0
    child = fam.labels(**labels) if labels else fam
    return child.value


@pytest.fixture()
def live_master():
    master = Master(
        overload_config={"max_inflight": 64, "retry_after_s": 0.05},
    )
    api = ApiServer(master)
    api.start()
    yield master, api
    api.stop()
    master.shutdown()


class _NoHTTPHarness(loadharness.LoadHarness):
    """Open-loop engine under test with the wire removed: the control
    scenario records WHEN each arrival actually fired (and optionally
    how long its 'service' took), nothing talks HTTP."""

    def __init__(self, *a, service_s: float = 0.0, **kw):
        super().__init__(*a, **kw)
        self.fired = []
        self._service_s = service_s

    def _new_session(self):
        return None

    def _fire_control(self, session, i):
        self.fired.append((i, time.monotonic()))
        if self._service_s:
            time.sleep(self._service_s)


class TestOpenLoopTiming:
    def test_constant_arrival_rate_holds(self):
        h = _NoHTTPHarness(
            "http://unused", mix={"control": 50.0}, duration_s=1.0,
            workers_per_scenario=4,
        )
        rep = h.run()
        s = rep["scenarios"]["control"]
        # ~50 arrivals offered in 1s, one per grid slot, no misses: the
        # pool may overshoot by at most one in-flight arrival per worker.
        assert 45 <= s["sent"] <= 55
        assert abs(s["achieved_qps"] - 50.0) < 6.0
        assert s["error"] == 0 and s["shed"] == 0
        # Fast no-op service: every latency stays near its scheduled
        # arrival (the grid is being honored, not drifted).
        assert s["p99_ms"] < 250.0
        # Arrivals fire in index order per the shared grid index.
        indices = [i for i, _ in sorted(h.fired, key=lambda x: x[1])]
        assert sorted(i for i, _ in h.fired) == list(range(s["sent"]))
        assert indices[0] == 0

    def test_coordinated_omission_counted_not_hidden(self):
        # Offered 20/s but the pool can only serve 2 workers / 0.2s
        # = 10/s: a CLOSED loop would slow its offered rate and record
        # ~200ms everywhere; the OPEN loop keeps the grid and the queue
        # delay lands in the recorded numbers.
        h = _NoHTTPHarness(
            "http://unused", mix={"control": 20.0}, duration_s=1.5,
            workers_per_scenario=2, service_s=0.2,
        )
        rep = h.run()
        s = rep["scenarios"]["control"]
        assert s["max_ms"] > 400.0  # queueing >> one service time
        assert s["p50_ms"] > 200.0  # the backlog is in the median too

    def test_unknown_scenario_named(self):
        with pytest.raises(ValueError, match="bogus"):
            loadharness.LoadHarness("http://unused", mix={"bogus": 1.0})

    def test_zero_rate_scenario_dropped(self):
        h = loadharness.LoadHarness(
            "http://unused", mix={"control": 0.0, "query": 1.0},
        )
        assert set(h.mix) == {"query"}


class _CannedSession:
    """verdict() consumer contract: .get(path, params=None) → dict."""

    def __init__(self, alerts=None, history=None, rules=(),
                 segments=(), exemplars=()):
        self.docs = {
            "/api/v1/alerts": {
                "alerts": list(alerts or []),
                "history": list(history or []),
                "rules": list(rules),
            },
            "dtpu_lifecycle_segment_seconds": {
                "result": [
                    {"labels": {"segment": seg}, "value": val}
                    for seg, val in segments
                ],
            },
            "dtpu_api_request_duration_seconds": {
                "exemplars": [
                    {"trace_id": tid, "value": val, "ts": 0.0}
                    for tid, val in exemplars
                ],
            },
        }

    def get(self, path, params=None):
        if path == "/api/v1/alerts":
            return self.docs[path]
        return self.docs[params["name"]]


class TestVerdict:
    def test_green_surface_passes(self):
        v = loadharness.verdict(_CannedSession(rules=["a", "b"]))
        assert v["pass"] is True
        assert v["violated_rules"] == []
        assert v["rules_watched"] == ["a", "b"]
        assert "slow_segment" not in v  # no enrichment on a pass

    def test_firing_rule_fails_by_name_with_enrichment(self):
        sess = _CannedSession(
            alerts=[{"rule": "ingest_shed_sustained", "state": "firing",
                     "severity": "warning", "value": 0.4}],
            segments=[("queue_wait", 1.5), ("image_pull", 9.25)],
            exemplars=[("a" * 32, 0.2), ("b" * 32, 2.0), ("b" * 32, 2.0)],
        )
        v = loadharness.verdict(sess)
        assert v["pass"] is False
        assert v["violated_rules"] == ["ingest_shed_sustained"]
        # names the SLOW lifecycle segment, not just "slow"
        assert v["slow_segment"] == {"segment": "image_pull",
                                     "p99_s": 9.25}
        # exemplar trace ids, slowest first, deduped
        assert v["exemplar_trace_ids"] == ["b" * 32, "a" * 32]

    def test_watched_rules_filter(self):
        sess = _CannedSession(
            alerts=[{"rule": "other_rule", "state": "firing"}],
        )
        assert loadharness.verdict(sess, rules=["mine"])["pass"] is True
        assert loadharness.verdict(sess, rules=["other_rule"])[
            "pass"] is False

    def test_resolved_but_fired_since_start_still_fails(self):
        sess = _CannedSession(
            history=[{"rule": "stall_kills", "fired_at": 100.0}],
        )
        assert loadharness.verdict(sess, fired_since=50.0)["pass"] is False
        # fired BEFORE the drive: not this run's problem
        assert loadharness.verdict(sess, fired_since=200.0)["pass"] is True

    def test_pending_counts_as_violation(self):
        sess = _CannedSession(
            alerts=[{"rule": "r", "state": "pending"}],
        )
        assert loadharness.verdict(sess)["pass"] is False


class TestOverloadControl:
    def test_shed_answers_429_retry_after_neighbor_responsive(
        self, live_master,
    ):
        master, api = live_master
        master.admission.per_plane = {"traces": 0}
        before = _counter("dtpu_ingest_shed_total", plane="traces")
        r = requests.post(
            api.url + "/api/v1/traces/ingest", json={"spans": []},
            timeout=10,
        )
        assert r.status_code == 429
        # the header the shippers and RetryPolicy pace on
        assert float(r.headers["Retry-After"]) == 0.05
        assert r.json()["plane"] == "traces"
        assert _counter(
            "dtpu_ingest_shed_total", plane="traces"
        ) == before + 1
        # observed like any request: the alert ratio rule's numerator.
        # The status counter lands in the dispatcher's finally AFTER the
        # response bytes reach the client — poll past that tiny window.
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            if _counter(
                "dtpu_api_requests_total", method="POST",
                route=r"^/api/v1/traces/ingest$", status="429",
            ) >= 1:
                break
            time.sleep(0.02)
        assert _counter(
            "dtpu_api_requests_total", method="POST",
            route=r"^/api/v1/traces/ingest$", status="429",
        ) >= 1
        # Two lanes: the flood-lane shed must not take the neighbors
        # with it — queries and control beats answer normally mid-shed.
        sess = Session(api.url)
        doc = sess.get(
            "/api/v1/metrics/query",
            params={"name": "dtpu_api_requests_total", "func": "rate"},
        )
        assert doc["name"] == "dtpu_api_requests_total"
        assert sess.get(
            "/api/v1/allocations/drill.0/signals/preemption",
            params={"timeout_seconds": 0},
        )["preempt"] is False

    def test_admission_releases_inflight(self, live_master):
        master, api = live_master
        sess = Session(api.url)
        for i in range(5):
            sess.post("/api/v1/logs/ingest", json_body={"lines": [
                {"target": "t", "message": f"m{i}"},
            ]})
        # acquire/release stays balanced through real dispatch (the
        # handler releases after its response is written, so the last
        # release may trail the client's return by a scheduling slice)
        deadline = time.monotonic() + 5.0
        while (master.admission.inflight("logs")
               and time.monotonic() < deadline):
            time.sleep(0.01)
        assert master.admission.inflight("logs") == 0

    def test_disabled_admission_never_sheds(self):
        master = Master(overload_config={"enabled": False,
                                         "max_inflight": 0})
        try:
            assert master.admission.try_acquire("traces") is True
            master.admission.release("traces")
        finally:
            master.shutdown()

    def test_master_overload_fault_forces_shed(self, live_master):
        master, api = live_master
        before = _counter("dtpu_ingest_shed_total", plane="logs")
        with faults.plan_active(FaultPlan({
            "master.overload": FaultSpec(error_rate=1.0),
        })):
            r = requests.post(
                api.url + "/api/v1/logs/ingest", json={"lines": []},
                timeout=10,
            )
            assert r.status_code == 429
            assert "Retry-After" in r.headers
        assert _counter(
            "dtpu_ingest_shed_total", plane="logs"
        ) == before + 1
        # plan cleared: the lane admits again
        r = requests.post(
            api.url + "/api/v1/logs/ingest", json={"lines": []},
            timeout=10,
        )
        assert r.status_code == 200


class TestShipperBackoffDrills:
    def test_span_shipper_backs_off_and_recovers_no_loss(
        self, live_master,
    ):
        master, api = live_master
        master.admission.per_plane = {"traces": 0}
        shipper = trace_mod.SpanShipper(
            api.url, flush_interval_s=3600.0, batch_size=64,
        )
        try:
            now_ns = int(time.time() * 1e9)
            for i in range(8):
                shipper.enqueue({
                    "traceId": trace_mod.new_trace_id(),
                    "spanId": trace_mod.new_span_id(),
                    "name": f"drill {i}",
                    "startTimeUnixNano": now_ns,
                    "endTimeUnixNano": now_ns + 1000,
                    "status": {"code": 1},
                })
            before_backoff = _counter("dtpu_trace_ship_backoffs_total")
            before_failed = _counter(
                "dtpu_trace_spans_dropped_total", reason="ship_failed"
            )
            before_shipped = _counter("dtpu_trace_spans_shipped_total")
            shipper.flush()
            # shed is BACKOFF, not loss: batch re-queued, pause armed
            assert _counter(
                "dtpu_trace_ship_backoffs_total"
            ) == before_backoff + 1
            assert _counter(
                "dtpu_trace_spans_dropped_total", reason="ship_failed"
            ) == before_failed
            assert len(shipper._buffer) == 8
            assert shipper._paused_until > time.monotonic()
            # flush during the pause is a no-op (absorbing, not hammering)
            shipper.flush()
            assert len(shipper._buffer) == 8
            # recovery: master lifts the bound, pause expires, all ship
            master.admission.per_plane = {}
            shipper._paused_until = 0.0
            shipper.flush()
            assert len(shipper._buffer) == 0
            assert _counter(
                "dtpu_trace_spans_shipped_total"
            ) == before_shipped + 8
        finally:
            shipper.stop(flush=False)

    def test_log_shipper_client_backoff_fault_drill(self, live_master):
        master, api = live_master
        shipper = logship.LogShipper(
            api.url, flush_interval_s=3600.0, batch_size=64,
        )
        try:
            for i in range(5):
                shipper.enqueue({"target": "drill", "message": f"m{i}"})
            before_backoff = _counter("dtpu_log_ship_backoffs_total")
            before_shipped = _counter("dtpu_log_lines_shipped_total")
            with faults.plan_active(FaultPlan({
                "client.ingest_backoff": FaultSpec(error_rate=1.0),
            })):
                shipper.flush()
            assert _counter(
                "dtpu_log_ship_backoffs_total"
            ) == before_backoff + 1
            assert len(shipper._buffer) == 5  # re-queued, not lost
            # drill over: recovery ships everything
            shipper._paused_until = 0.0
            shipper.flush()
            assert len(shipper._buffer) == 0
            assert _counter(
                "dtpu_log_lines_shipped_total"
            ) == before_shipped + 5
        finally:
            shipper.stop(flush=False)

    def test_profile_shipper_shed_requeues_in_order(self, live_master):
        from determined_tpu.common import profiling

        master, api = live_master
        master.admission.per_plane = {"profiles": 0}
        shipper = profiling.ProfileShipper(
            api.url, flush_interval_s=3600.0, batch_size=64,
        )
        try:
            now = time.time()
            for i in range(3):
                shipper.enqueue({
                    "target": f"drill.{i}", "start": now - 1, "end": now,
                    "hz": 19.0, "samples": [],
                })
            before = _counter("dtpu_profile_ship_backoffs_total")
            shipper.flush()
            assert _counter(
                "dtpu_profile_ship_backoffs_total"
            ) == before + 1
            # FRONT re-queue preserves window order for the retry
            assert [w["target"] for w in shipper._buffer] == \
                ["drill.0", "drill.1", "drill.2"]
        finally:
            shipper.stop(flush=False)

    def test_stop_counts_undeliverable_leftovers(self):
        # Master gone AND still shedding at exit: the final drain fails
        # and every leftover is counted loss — nothing vanishes silently.
        shipper = logship.LogShipper(
            "http://127.0.0.1:1", flush_interval_s=3600.0, batch_size=2,
        )
        for i in range(3):
            shipper.enqueue({"target": "t", "message": f"m{i}"})
        before = _counter(
            "dtpu_log_lines_dropped_total", reason="ship_failed"
        )
        shipper.stop(flush=True)
        assert _counter(
            "dtpu_log_lines_dropped_total", reason="ship_failed"
        ) == before + 3


class TestSmokeDrive:
    def test_devcluster_scale_drive_and_verdict(self, live_master):
        master, api = live_master
        h = loadharness.LoadHarness(
            api.url,
            mix={"metric_report": 10, "span_ingest": 5, "log_ingest": 5,
                 "profile_ingest": 2, "query": 2, "control": 5},
            duration_s=1.5, workers_per_scenario=2,
        )
        rep = h.run()
        for name, s in rep["scenarios"].items():
            assert s["error"] == 0, (name, s)
            assert s["ok"] > 0, (name, s)
        # the drive's own numbers are on the metrics surface (TSDB-bound
        # via self-scrape when the harness runs inside a scrape target)
        text = REGISTRY.render()
        assert "dtpu_loadharness_request_duration_seconds" in text
        assert 'dtpu_loadharness_requests_total{outcome="ok"' in text \
            or "dtpu_loadharness_requests_total" in text
        v = loadharness.verdict(
            Session(api.url), fired_since=rep["started_at"],
        )
        assert v["pass"] is True, v


@pytest.mark.slow
class TestSoakDrive:
    def test_four_plane_soak_then_overload(self, live_master):
        master, api = live_master
        rep = loadharness.LoadHarness(
            api.url,
            mix={"metric_report": 40, "span_ingest": 15, "log_ingest": 15,
                 "profile_ingest": 4, "submit_churn": 2, "query": 4,
                 "control": 10},
            duration_s=6.0, workers_per_scenario=4,
        ).run()
        v = loadharness.verdict(
            Session(api.url), fired_since=rep["started_at"],
        )
        assert v["pass"] is True, v
        for name in ("metric_report", "span_ingest", "log_ingest",
                     "profile_ingest"):
            s = rep["scenarios"][name]
            assert s["error"] == 0
            assert s["achieved_qps"] > 0.8 * s["target_qps"], (name, s)
        # above capacity: bulk sheds with Retry-After, control lane holds
        master.admission.per_plane = {
            "metrics": 1, "traces": 0, "logs": 0, "profiles": 0,
        }
        rep2 = loadharness.LoadHarness(
            api.url,
            mix={"metric_report": 60, "span_ingest": 30, "log_ingest": 30,
                 "profile_ingest": 10, "control": 10},
            duration_s=4.0, workers_per_scenario=4,
        ).run()
        scen = rep2["scenarios"]
        assert sum(s["shed"] for s in scen.values()) > 0
        assert any(s["retry_after_seen"] for s in scen.values())
        assert scen["control"]["error"] == 0
        assert scen["control"]["p99_ms"] < 1000.0, scen["control"]
