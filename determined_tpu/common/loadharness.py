"""Open-loop control-plane load harness: the master as its own k6.

The reference platform ships k6 scripts that drive its REST surface at
heavy-traffic numbers; this is that idea folded into the platform
itself. `LoadHarness` drives the REAL HTTP paths — experiment
submit/lifecycle churn, sustained metric/span/log/profile-window ingest,
read-side queries, and the latency-critical control routes — at a
**constant arrival rate** per scenario, and the master judges the run
with its own SLO machinery (`verdict` below reads /api/v1/alerts).

Open-loop, coordinated-omission-safe: request *i* of a scenario is
scheduled at ``start + i/rate`` regardless of how long earlier requests
took, and its latency is measured FROM THAT SCHEDULED ARRIVAL — a
stalled server accrues the stall into every queued request's number
instead of silently slowing the offered load (the closed-loop mistake
k6's constant-arrival-rate executor and wrk2 exist to fix). A worker
pool per scenario shares one arrival index; workers fire whichever
arrival is next due, so the offered rate holds until every worker is
stuck in a request.

Results land twice: precise per-scenario quantiles in the returned
report (for the CLI and bench rung), and
``dtpu_loadharness_request_duration_seconds{scenario}`` /
``dtpu_loadharness_requests_total{scenario,outcome}`` in the process
registry — when the harness runs inside a scrape target (the master's
devcluster, the bench rung) the numbers flow into the TSDB and the
alert rules see the drive like any other traffic.

Overload interplay: harness Sessions run with max_retries=0 — no
transparent retry — so an admission shed (429 + Retry-After,
master/overload.py) is COUNTED as outcome="shed" rather than absorbed,
and ``retry_after_seen`` in the report proves the header contract.

CLI: `dtpu loadtest run|report` (cli/cli.py). Scenario-mix config and
verdict semantics:
docs/operations.md "Load harness & overload control".
"""
from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional

from determined_tpu.common import trace as trace_mod
from determined_tpu.common.metrics import REGISTRY as METRICS

HARNESS_LATENCY = METRICS.histogram(
    "dtpu_loadharness_request_duration_seconds",
    "Load-harness operation latency per scenario, measured from the "
    "OPEN-LOOP SCHEDULED arrival time (coordinated-omission-safe: server "
    "stalls accrue into every queued arrival).",
    labels=("scenario",),
)
HARNESS_REQUESTS = METRICS.counter(
    "dtpu_loadharness_requests_total",
    "Load-harness operations per scenario by outcome: ok, shed (the "
    "master's 429 admission answer — deliberate, counted, not an error), "
    "or error.",
    labels=("scenario", "outcome"),
)

#: Default scenario mix (name → target arrivals/second). Ingest planes
#: dominate — that is what a training fleet offers the master — with a
#: trickle of lifecycle churn, read-side queries, and the control-lane
#: beats whose latency the two-lane overload design protects.
DEFAULT_MIX: Dict[str, float] = {
    "metric_report": 40.0,
    "span_ingest": 15.0,
    "log_ingest": 15.0,
    "profile_ingest": 4.0,
    "submit_churn": 1.0,
    "query": 4.0,
    "control": 10.0,
}

#: Minimal submittable experiment config for submit_churn (expconf
#: pipeline validates it like any user submission; no agents need to
#: exist — queued experiments are exactly the lifecycle-churn load).
_EXP_CONFIG: Dict[str, Any] = {
    "name": "loadharness-churn",
    "entrypoint": "determined_tpu.exec.builtin_trials:SyntheticTrial",
    "searcher": {"name": "random", "max_trials": 1, "max_length": 2},
    "hyperparameters": {
        "lr": {"type": "log", "minval": -4, "maxval": -2},
    },
    "resources": {"slots_per_trial": 1},
}


def _quantile(sorted_vals: List[float], q: float) -> float:
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, int(q * len(sorted_vals)))
    return sorted_vals[idx]


class _ScenarioRun:
    """One scenario's shared open-loop state: the arrival index its
    worker pool races over, and the outcome/latency tallies."""

    def __init__(self, name: str, rate: float) -> None:
        self.name = name
        self.rate = float(rate)
        self.lock = threading.Lock()
        self.next_arrival = 0
        self.latencies: List[float] = []
        self.outcomes: Dict[str, int] = {"ok": 0, "shed": 0, "error": 0}
        self.retry_after_seen = False

    def record(self, latency_s: float, outcome: str,
               retry_after: bool = False) -> None:
        HARNESS_LATENCY.labels(self.name).observe(latency_s)
        HARNESS_REQUESTS.labels(self.name, outcome).inc()
        with self.lock:
            self.latencies.append(latency_s)
            self.outcomes[outcome] = self.outcomes.get(outcome, 0) + 1
            if retry_after:
                self.retry_after_seen = True

    def report(self, elapsed_s: float) -> Dict[str, Any]:
        with self.lock:
            lats = sorted(self.latencies)
            outcomes = dict(self.outcomes)
            retry_after = self.retry_after_seen
        sent = len(lats)
        return {
            "target_qps": self.rate,
            "achieved_qps": round(sent / elapsed_s, 2) if elapsed_s else 0.0,
            "sent": sent,
            **outcomes,
            "retry_after_seen": retry_after,
            "p50_ms": round(_quantile(lats, 0.50) * 1e3, 2),
            "p95_ms": round(_quantile(lats, 0.95) * 1e3, 2),
            "p99_ms": round(_quantile(lats, 0.99) * 1e3, 2),
            "max_ms": round((lats[-1] if lats else 0.0) * 1e3, 2),
        }


class LoadHarness:
    """Drive a master with a constant-arrival-rate scenario mix.

    `mix` maps scenario name → arrivals/second (DEFAULT_MIX keys; a rate
    of 0 drops the scenario). `run()` blocks for `duration_s`, then
    returns the per-scenario report. Every worker uses its own Session
    with max_retries=0 so shed answers surface as outcomes, not silent
    retries.
    """

    SCENARIOS = (
        "metric_report", "span_ingest", "log_ingest", "profile_ingest",
        "submit_churn", "query", "control",
    )

    def __init__(
        self,
        master_url: str,
        token: str = "",
        *,
        mix: Optional[Dict[str, float]] = None,
        duration_s: float = 10.0,
        workers_per_scenario: int = 4,
        spans_per_request: int = 8,
        lines_per_request: int = 16,
        trial_pool: int = 4,
        churn_keep: int = 4,
        timeout_s: float = 10.0,
    ) -> None:
        self.master_url = master_url
        self.token = token
        self.duration_s = float(duration_s)
        self.workers_per_scenario = max(1, int(workers_per_scenario))
        self.spans_per_request = max(1, int(spans_per_request))
        self.lines_per_request = max(1, int(lines_per_request))
        self.trial_pool = max(1, int(trial_pool))
        self.churn_keep = max(1, int(churn_keep))
        self.timeout_s = float(timeout_s)
        mix = dict(DEFAULT_MIX) if mix is None else dict(mix)
        unknown = sorted(set(mix) - set(self.SCENARIOS))
        if unknown:
            raise ValueError(
                f"unknown scenario(s) {', '.join(unknown)} "
                f"(one of: {', '.join(self.SCENARIOS)})"
            )
        self.mix = {
            name: float(rate) for name, rate in mix.items() if rate > 0
        }
        self._stop = threading.Event()
        # submit_churn's experiment-id pool (kill+delete past churn_keep).
        self._churn_lock = threading.Lock()
        self._churn_ids: List[int] = []
        self._query_rotation = (
            ("/api/v1/metrics/query",
             {"name": "dtpu_api_requests_total", "func": "rate"}),
            ("/api/v1/experiments", {"limit": 50}),
            ("/api/v1/traces", {"limit": 10}),
            ("/api/v1/logs/query", {"limit": 10}),
            ("/api/v1/alerts", None),
        )

    def _new_session(self):
        from determined_tpu.common.api_session import Session

        return Session(
            self.master_url, token=self.token,
            max_retries=0, timeout=self.timeout_s,
        )

    # -- scenario operations (one call = one scheduled arrival) -----------

    def _fire_metric_report(self, session, i: int) -> None:
        trial_id = (i % self.trial_pool) + 1
        session.post(
            f"/api/v1/trials/{trial_id}/metrics",
            json_body={
                "group": "training",
                "metrics": {"loss": 1.0 / (1 + i % 100),
                            "batches": float(i)},
                "steps_completed": i,
                "trial_run_id": 1,
                "report_time": time.time(),
            },
        )

    def _fire_span_ingest(self, session, i: int) -> None:
        now_ns = int(time.time() * 1e9)
        spans = []
        for k in range(self.spans_per_request):
            spans.append({
                "traceId": trace_mod.new_trace_id(),
                "spanId": trace_mod.new_span_id(),
                "name": f"loadharness op {k}",
                "startTimeUnixNano": now_ns - 1_000_000,
                "endTimeUnixNano": now_ns,
                "status": {"code": 1},
            })
        session.post("/api/v1/traces/ingest", json_body={"spans": spans})

    def _fire_log_ingest(self, session, i: int) -> None:
        ts = time.time()
        lines = [
            {"target": "loadharness", "level": "INFO",
             "message": f"open-loop line {i}.{k}", "ts": ts}
            for k in range(self.lines_per_request)
        ]
        session.post("/api/v1/logs/ingest", json_body={"lines": lines})

    def _fire_profile_ingest(self, session, i: int) -> None:
        now = time.time()
        window = {
            "target": f"loadharness.w{i % self.workers_per_scenario}",
            "start": now - 1.0, "end": now, "hz": 19.0,
            "samples": [{
                "thread": "MainThread", "phase": "step",
                "stack": "loadharness.py:_fire;api_session.py:post",
                "count": 19,
            }],
        }
        session.post(
            "/api/v1/profiles/ingest", json_body={"windows": [window]}
        )

    def _fire_submit_churn(self, session, i: int) -> None:
        exp_id = session.post(
            "/api/v1/experiments", json_body={"config": dict(_EXP_CONFIG)}
        )["id"]
        victim = None
        with self._churn_lock:
            self._churn_ids.append(exp_id)
            if len(self._churn_ids) > self.churn_keep:
                victim = self._churn_ids.pop(0)
        if victim is not None:
            # Lifecycle churn is the point; a raced kill/delete (another
            # worker, a terminal state) is not a scenario failure.
            try:
                session.post(f"/api/v1/experiments/{victim}/kill")
                session.delete(f"/api/v1/experiments/{victim}")
            except Exception:  # noqa: BLE001 — churn, not correctness
                pass

    def _fire_query(self, session, i: int) -> None:
        path, params = self._query_rotation[i % len(self._query_rotation)]
        session.get(path, params=params)

    def _fire_control(self, session, i: int) -> None:
        # The control lane the overload design protects: preemption polls
        # and progress beats on a synthetic allocation (both routes answer
        # immediately for unknown allocations — no cluster setup needed).
        alloc = f"loadharness.{i % 4}"
        if i % 2 == 0:
            session.get(
                f"/api/v1/allocations/{alloc}/signals/preemption",
                params={"timeout_seconds": 0},
            )
        else:
            session.post(
                f"/api/v1/allocations/{alloc}/progress",
                json_body={"rank": 0, "step": i},
            )

    def _fire(self, name: str) -> Callable[[Any, int], None]:
        return getattr(self, f"_fire_{name}")

    # -- the open loop ------------------------------------------------------

    def _worker(self, run: _ScenarioRun, fire: Callable[[Any, int], None],
                start: float, end: float) -> None:
        session = self._new_session()
        while not self._stop.is_set():
            with run.lock:
                i = run.next_arrival
                run.next_arrival += 1
            t_i = start + i / run.rate
            if t_i >= end:
                return
            delay = t_i - time.monotonic()
            if delay > 0:
                # Pacing against the SCHEDULED grid — interruptible, and
                # never a literal sleep (tests/test_no_adhoc_retries.py).
                self._stop.wait(delay)
            if self._stop.is_set():
                return
            outcome, retry_after = "ok", False
            try:
                fire(session, i)
            except Exception as e:  # noqa: BLE001 — every outcome counted
                outcome, retry_after = _classify(e)
            # Coordinated-omission-safe latency: from the scheduled
            # arrival, not the actual send — queueing delay behind a
            # stalled server is part of the number.
            run.record(time.monotonic() - t_i, outcome, retry_after)

    def run(self) -> Dict[str, Any]:
        """Drive the mix for duration_s; returns the per-scenario report
        plus wall-clock bounds (unix seconds, for verdict windows)."""
        runs = {
            name: _ScenarioRun(name, rate)
            for name, rate in self.mix.items()
        }
        self._stop.clear()
        wall_start = time.time()
        start = time.monotonic()
        end = start + self.duration_s
        threads: List[threading.Thread] = []
        for name, run in runs.items():
            fire = self._fire(name)
            for w in range(self.workers_per_scenario):
                t = threading.Thread(
                    target=self._worker, args=(run, fire, start, end),
                    name=f"loadharness-{name}-{w}", daemon=True,
                )
                t.start()
                threads.append(t)
        for t in threads:
            t.join(timeout=self.duration_s + 4 * self.timeout_s)
        self._stop.set()
        elapsed = time.monotonic() - start
        return {
            "duration_s": round(elapsed, 3),
            "started_at": wall_start,
            "ended_at": time.time(),
            "scenarios": {
                name: run.report(min(elapsed, self.duration_s))
                for name, run in runs.items()
            },
        }

    def stop(self) -> None:
        self._stop.set()


def _classify(e: BaseException) -> tuple:
    """(outcome, retry_after_seen) for a failed operation: the master's
    429 admission answer is 'shed' — deliberate pacing, tallied apart
    from real errors — and we note whether it honored the Retry-After
    header contract."""
    resp = getattr(e, "response", None)
    if getattr(resp, "status_code", None) == 429:
        try:
            retry_after = resp.headers.get("Retry-After") is not None
        except Exception:  # noqa: BLE001 — header shape is server's call
            retry_after = False
        return "shed", retry_after
    return "error", False


# -- self-verdict: the master's SLO machinery judges the drive -------------

def verdict(
    session,
    rules: Optional[List[str]] = None,
    fired_since: float = 0.0,
) -> Dict[str, Any]:
    """Ask the master whether its SLO rules stayed green.

    Pass iff no watched rule is pending/firing now and none FIRED since
    `fired_since` (unix seconds; resolved-then-gone violations still
    fail the run). `rules=None` watches every loaded rule. On violation
    the verdict names the violated rules, the slowest lifecycle
    critical-path segment (p99 of dtpu_lifecycle_segment_seconds), and
    exemplar trace ids from the API-latency histogram — the concrete
    slow traces behind the number.
    """
    data = session.get("/api/v1/alerts")
    watched = None if rules is None else set(rules)

    def _watch(rule_name: str) -> bool:
        return watched is None or rule_name in watched

    active = [
        a for a in data.get("alerts", [])
        if _watch(a.get("rule", "")) and a.get("state") in (
            "pending", "firing",
        )
    ]
    fired = [
        h for h in data.get("history", [])
        if _watch(h.get("rule", ""))
        and float(h.get("fired_at") or 0.0) >= fired_since
    ]
    violated = sorted(
        {a.get("rule", "") for a in active}
        | {h.get("rule", "") for h in fired}
    )
    out: Dict[str, Any] = {
        "pass": not violated,
        "violated_rules": violated,
        "active": active,
        "fired": fired,
        "rules_watched": (
            sorted(watched) if watched is not None
            else list(data.get("rules", []))
        ),
    }
    if violated:
        out["slow_segment"] = _slowest_segment(session)
        out["exemplar_trace_ids"] = _latency_exemplars(session)
    return out


def _slowest_segment(session) -> Optional[Dict[str, Any]]:
    """p99 per lifecycle critical-path segment (tracestore publishes
    dtpu_lifecycle_segment_seconds), slowest first — names WHERE the
    lifecycle got slow, not just that it did."""
    try:
        result = session.get(
            "/api/v1/metrics/query",
            params={"name": "dtpu_lifecycle_segment_seconds",
                    "func": "quantile", "q": 0.99},
        ).get("result", [])
    except Exception:  # noqa: BLE001 — verdict must not fail on enrich
        return None
    best = None
    for entry in result:
        value = entry.get("value")
        if value is None:
            continue
        if best is None or value > best["p99_s"]:
            best = {
                "segment": entry.get("labels", {}).get("segment", ""),
                "p99_s": round(float(value), 4),
            }
    return best


def _latency_exemplars(session, limit: int = 5) -> List[str]:
    """Exemplar trace ids off the API-latency histogram: the actual slow
    requests a violated latency rule is complaining about."""
    try:
        exemplars = session.get(
            "/api/v1/metrics/query",
            params={"name": "dtpu_api_request_duration_seconds",
                    "func": "quantile", "q": 0.99, "exemplars": 1},
        ).get("exemplars", [])
    except Exception:  # noqa: BLE001 — verdict must not fail on enrich
        return []
    exemplars.sort(key=lambda e: e.get("value", 0.0), reverse=True)
    out: List[str] = []
    for e in exemplars:
        tid = e.get("trace_id")
        if tid and tid not in out:
            out.append(tid)
        if len(out) >= limit:
            break
    return out


def format_report(report: Dict[str, Any],
                  verdict_doc: Optional[Dict[str, Any]] = None) -> str:
    """Human-readable drive summary for the CLI and bench output."""
    lines = [
        f"drive: {report.get('duration_s', 0)}s",
        f"{'scenario':<16}{'target':>8}{'qps':>8}{'sent':>7}"
        f"{'ok':>7}{'shed':>6}{'err':>5}{'p50ms':>8}{'p99ms':>8}",
    ]
    for name in sorted(report.get("scenarios", {})):
        s = report["scenarios"][name]
        lines.append(
            f"{name:<16}{s['target_qps']:>8.1f}{s['achieved_qps']:>8.1f}"
            f"{s['sent']:>7}{s.get('ok', 0):>7}{s.get('shed', 0):>6}"
            f"{s.get('error', 0):>5}{s['p50_ms']:>8.1f}{s['p99_ms']:>8.1f}"
        )
    if verdict_doc is not None:
        lines.append(
            "verdict: PASS" if verdict_doc.get("pass")
            else "verdict: FAIL "
            f"(violated: {', '.join(verdict_doc.get('violated_rules', []))})"
        )
        seg = verdict_doc.get("slow_segment")
        if seg:
            lines.append(
                f"slow segment: {seg['segment']} p99={seg['p99_s']}s"
            )
        tids = verdict_doc.get("exemplar_trace_ids")
        if tids:
            lines.append("exemplar traces: " + ", ".join(tids))
    return "\n".join(lines)
