"""REST API server over the Master.

Rebuild of the reference's gRPC/REST surface (`internal/api_*.go`, 206 RPCs
behind grpc-gateway) scaled to the routes the harness/CLI/agents actually
call; same resource nouns and long-poll semantics (searcher operation,
preemption signal, rendezvous — ref api.proto:861,917,942,971-1007).

stdlib ThreadingHTTPServer: each long-poll occupies one request thread,
which is the same model as the reference's long-poll handlers; no external
web framework is needed for a control plane at this rate.
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
from determined_tpu.master.core import (
    EXPERIMENT_GOODPUT,
    SENTINEL_DIVERGENCE,
    STEP_FLOPS,
    Master,
)
from determined_tpu.master.db import TERMINAL_STATES

logger = logging.getLogger("determined_tpu.master")

Handler = Callable[["ApiRequest"], Any]

# -- observability plane (common/metrics.py; ref internal/prom) --------------
# Request metrics live on the ONE dispatch path every route flows through,
# so coverage is structural: a new route is instrumented by existing
# (tests/test_metrics_discipline.py asserts it stays that way). The route
# label is the route PATTERN, not the raw path — bounded cardinality, the
# same rule the request spans follow.
API_REQUESTS = METRICS.counter(
    "dtpu_api_requests_total",
    "API requests by method, route pattern, and response status.",
    labels=("method", "route", "status"),
)
API_LATENCY = METRICS.histogram(
    "dtpu_api_request_duration_seconds",
    "API request latency by method and route pattern (SSE streams are "
    "observed at stream start — their open-ended duration is not latency).",
    labels=("method", "route"),
)
# Cluster-state gauges (ref internal/prom/det_state_metrics.go:91),
# refreshed from pool snapshots at scrape time.
POOL_AGENTS = METRICS.gauge(
    "dtpu_agents", "Registered agents per pool.", labels=("pool",))
POOL_SLOTS_TOTAL = METRICS.gauge(
    "dtpu_slots_total", "Total slots per pool.", labels=("pool",))
POOL_SLOTS_USED = METRICS.gauge(
    "dtpu_slots_used", "Slots in use per pool.", labels=("pool",))
POOL_ALLOCS_PENDING = METRICS.gauge(
    "dtpu_allocations_pending", "Queued allocations per pool.",
    labels=("pool",))
POOL_ALLOCS_RUNNING = METRICS.gauge(
    "dtpu_allocations_running", "Running allocations per pool.",
    labels=("pool",))
EXPERIMENTS_BY_STATE = METRICS.gauge(
    "dtpu_experiments", "Experiments by state.", labels=("state",))
# Sentinel events (PR 3) as they reach the control plane: the trainer
# reports cumulative steps_skipped/rollbacks in its training metrics;
# the master folds the per-trial deltas into cluster counters.
SENTINEL_STEPS_SKIPPED = METRICS.counter(
    "dtpu_sentinel_steps_skipped_total",
    "Optimizer updates skipped by the non-finite guard, cluster-wide.",
)
SENTINEL_ROLLBACKS = METRICS.counter(
    "dtpu_sentinel_rollbacks_total",
    "Sentinel rollback-and-skip events, cluster-wide.",
)
# dtpu_experiment_goodput_pct lives in master/core.py (EXPERIMENT_GOODPUT):
# the terminal-state hook there prunes an experiment's series when it ends,
# keeping the per-experiment label set bounded on a long-lived master.

#: hard cap on any request body (context uploads are the largest legitimate
#: payload; their own cap is slightly smaller so the error is specific).
MAX_BODY_BYTES = 128 * 1024 * 1024

#: Routes a `task:` principal (DTPU_SESSION_TOKEN injected into a launched
#: task) may call — the harness-facing surface only. Everything else
#: (experiment/model/workspace admin, agent registration, queue moves,
#: webhooks) returns 403 for task tokens.
TASK_TOKEN_ROUTES = re.compile(
    r"^/api/v1/("
    r"trials/\d+(/.*)?"
    r"|checkpoints"
    r"|checkpoints/[0-9a-f-]+"
    r"|allocations/[\w.\-]+/.*"
    r"|task_logs"
    r"|files/[0-9a-f]+"
    r"|experiments/\d+"            # GET-only routes: config echo (harness)
    r"|experiments/\d+/trials"     # and trial discovery (TensorBoard task)
    r"|proxies"
    r"|master"
    r"|auth/logout"
    r"|traces/ingest"              # span shipper (trial/serving processes)
    r"|profiles/ingest"            # profile sampler (trial/serving processes)
    r"|profiles/captures/[\w\-]+/complete"  # capture artifact registration
    r"|logs/ingest"                # log shipper (trial/serving processes)
    r")$"
)

#: Routes an `agent:` principal (token issued to a master-provisioned agent)
#: may call: registration/long-poll/event reporting + task-log shipping.
AGENT_TOKEN_ROUTES = re.compile(
    r"^/api/v1/("
    r"agents(/[\w.\-]+/(actions|events))?"
    r"|task_logs"
    r"|master"
    r"|auth/logout"
    r"|traces/ingest"              # span shipper (agent launch spans)
    r"|profiles/ingest"            # profile sampler (agent daemon)
    r"|logs/ingest"                # log shipper (agent daemon)
    r")$"
)


#: Cluster-administration surface: role `admin` only. Users/groups manage
#: authorization itself; queue moves reorder other users' jobs; webhooks
#: exfiltrate cluster events to external URLs; user-driven agent
#: registration adds capacity (agents themselves use agent: tokens).
ADMIN_ROUTES = re.compile(
    r"^/api/v1/(users|groups)(/.*)?$"
    r"|^/api/v1/queues/move$"
    r"|^/api/v1/webhooks(/\d+)?$"
    r"|^/api/v1/audit$"            # who-did-what is reconnaissance too
    r"|^/api/v1/master/logs$"      # master internals likewise
    # Agent control plane: GET /actions destructively drains the agent's
    # action queue (and refreshes its liveness), POST /events forges task
    # exits. Agents authenticate with agent: tokens (class allowlist);
    # user sessions touching these must be cluster admins.
    r"|^/api/v1/agents/[\w.\-]+/(actions|events)$"
    # Enable/disable/drain and slot-level variants reshape cluster
    # capacity (and plain disable kills running work): admins only.
    # Agent tokens can't reach these (not in AGENT_TOKEN_ROUTES) — an
    # agent must not disable its peers.
    r"|^/api/v1/agents/[\w.\-]+/(enable|disable)$"
    r"|^/api/v1/agents/[\w.\-]+/slots/\d+/(enable|disable)$"
)


def principal_allowed(principal: str, path: str) -> bool:
    """Authorization by principal class (ref: the reference gates admin
    RPCs on user sessions; task/allocation tokens only reach the trial
    surface — internal/api_trials.go auth interceptors)."""
    if principal.startswith("task:"):
        return TASK_TOKEN_ROUTES.match(path) is not None
    if principal.startswith("agent:"):
        return AGENT_TOKEN_ROUTES.match(path) is not None
    return True  # users: per-role checks in user_allowed


def user_allowed(role: str, method: str, path: str) -> bool:
    """Role-based authorization for user principals (RBAC capability of
    internal/rbac/api_rbac.go, scaled to three cluster roles).

    GETs on the admin surface stay admin-gated too: group membership maps
    users to capabilities, and the user list is reconnaissance."""
    if ADMIN_ROUTES.match(path):
        return role == "admin"
    if method == "GET" or path in (
        "/api/v1/auth/logout",
        "/api/v1/auth/password",  # own-account change; handler re-checks
    ):
        return True  # viewer floor
    if path == "/api/v1/agents":
        return role == "admin"  # user-driven capacity changes
    return role in ("editor", "admin")


def task_identity_violation(
    master: Master, principal: str, method: str, path: str,
    body: Dict[str, Any],
) -> Optional[str]:
    """Identity-level checks for `task:` principals, beyond the class-level
    allowlist: a task token must not WRITE another principal's state
    (fabricated metrics steer the victim's searcher; a spoofed checkpoint
    report overwrites its latest_checkpoint; a foreign rendezvous arrive
    corrupts its address table). Reads stay class-level until RBAC.
    Trial task ids are `trial-<id>` (core.py), which gives the mapping."""
    task_id = principal[len("task:"):]
    am = re.match(r"^/api/v1/allocations/([\w.\-]+)/", path)
    if am:
        alloc = master.alloc_service.get(am.group(1))
        if alloc is not None and alloc.task_id != task_id:
            return "token does not own this allocation"
    if method == "GET":
        return None
    if re.match(r"^/api/v1/experiments/\d+", path):
        # The experiments rows in TASK_TOKEN_ROUTES exist for config echo
        # and trial discovery only; a task token must never mutate
        # experiment state (PATCH metadata rewrites the stored config).
        return "task token may only read experiments"
    tm = re.match(r"^/api/v1/trials/(\d+)(/|$)", path)
    if tm and task_id != f"trial-{tm.group(1)}":
        return "task token may only write its own trial"
    if path == "/api/v1/checkpoints":
        trial_id = body.get("trial_id")
        if trial_id is not None and task_id != f"trial-{trial_id}":
            return "task token may only report checkpoints for its own trial"
    if path == "/api/v1/task_logs":
        claimed = body.get("task_id")
        if claimed and claimed != task_id:
            return "task token may only ship its own logs"
    return None


#: Denied-request audit budget: at most N rows per minute across all
#: unauthenticated/unauthorized callers; overflow is counted and logged
#: once per window instead of written (the ALLOWED mutations' audit is
#: never limited). 120/min is ample for human-scale incident forensics and
#: useless for a disk-filling attack.
_DENIED_AUDIT_PER_MINUTE = 120


class _DeniedAuditLimiter:
    """Per-ApiServer-instance rate limiter: module-level state would make
    every master in one process (devcluster tests, embedded multi-master)
    share a single budget — each instance's denials depleting the others'
    and attributing suppression warnings to the wrong master."""

    def __init__(self) -> None:
        self._state = {"window": 0, "count": 0, "dropped": 0}
        self._lock = threading.Lock()

    def allowed(self) -> bool:
        import time as _time

        window = int(_time.time() // 60)
        with self._lock:
            st = self._state
            if st["window"] != window:
                if st["dropped"]:
                    logger.warning(
                        "audit: suppressed %d denied-request rows last "
                        "minute (rate limit %d/min)", st["dropped"],
                        _DENIED_AUDIT_PER_MINUTE,
                    )
                st["window"] = window
                st["count"] = 0
                st["dropped"] = 0
            if st["count"] < _DENIED_AUDIT_PER_MINUTE:
                st["count"] += 1
                return True
            st["dropped"] += 1
            return False


class _IdempotencyCache:
    """Recent mutation results keyed by X-Request-Id (common/api_session.py
    stamps one id per logical POST/PATCH/DELETE and reuses it across
    retries): a retry whose first attempt landed — but whose response was
    lost to a timeout — replays the stored response instead of
    double-applying the mutation (double-created experiment, double-counted
    searcher op completion).

    Only 200s are stored: a failed attempt (including 503 restore-pending)
    must re-execute on retry. Bounded LRU; per-ApiServer instance for the
    same reason as _DeniedAuditLimiter."""

    MAX_ENTRIES = 4096

    def __init__(self) -> None:
        from collections import OrderedDict

        self._entries: "OrderedDict[str, Any]" = OrderedDict()
        self._lock = threading.Lock()

    def get(self, request_id: str) -> Optional[Any]:
        with self._lock:
            if request_id not in self._entries:
                return None
            self._entries.move_to_end(request_id)
            return self._entries[request_id]

    def put(self, request_id: str, payload: Any) -> None:
        with self._lock:
            self._entries[request_id] = payload
            self._entries.move_to_end(request_id)
            while len(self._entries) > self.MAX_ENTRIES:
                self._entries.popitem(last=False)


class ApiError(Exception):
    def __init__(
        self, status: int, message: str,
        payload: Optional[Dict[str, Any]] = None,
        headers: Optional[Dict[str, str]] = None,
    ) -> None:
        super().__init__(message)
        self.status = status
        #: extra structured fields merged into the error body (e.g. the
        #: generation-fence 409 carries the resize directive so a fenced
        #: straggler can re-sync from the rejection itself).
        self.payload = payload or {}
        #: extra response headers (e.g. the admission-shed 429 carries
        #: Retry-After so shippers pace instead of hammering).
        self.headers = headers or {}


def _q_num(raw: Any, conv: Callable[[Any], Any], name: str) -> Any:
    """Numeric query-param parse that answers 400, not a 500 from a bare
    int()/float(): `?rank=junk` is the caller's mistake, not ours. An
    absent/empty param is None (caller applies its own default)."""
    if raw in (None, ""):
        return None
    try:
        return conv(raw)
    except (TypeError, ValueError):
        raise ApiError(400, f"query param {name!r} must be a number")


class _PlainText(Exception):
    """Control-flow: handler responds with a non-JSON body (Prometheus
    scrape, WebUI HTML)."""

    def __init__(self, text, content_type: str = "text/plain; version=0.0.4") -> None:
        super().__init__("plaintext response")
        self.text = text  # str or bytes
        self.content_type = content_type


class _EventStream(Exception):
    """Control-flow: handler responds with a Server-Sent-Events stream.

    `gen` yields JSON strings (sent as `data:` events) or None
    (keepalive comment — holds proxies/browsers open through quiet
    periods). The dispatcher owns the socket/headers; the generator owns
    WHAT to stream and when to stop (master shutdown, follow budget)."""

    def __init__(self, gen) -> None:
        super().__init__("event stream")
        self.gen = gen


class _RawStream(Exception):
    """Control-flow: handler responds with a verbatim streamed body from
    a backend (the router's generate pass-through — SSE or JSON alike).
    Unlike _EventStream the dispatcher does not frame events; `chunks`
    are raw bytes relayed unbuffered, status/headers are the backend's."""

    def __init__(
        self, status: int, headers: Dict[str, str], chunks: Any
    ) -> None:
        super().__init__("raw stream")
        self.status = status
        self.headers = headers
        self.chunks = chunks


class ApiRequest:
    def __init__(
        self,
        groups: Tuple[str, ...],
        body: Dict[str, Any],
        query: Dict[str, List[str]],
        token: Optional[str] = None,
        client_ip: str = "",
        raw: bytes = b"",
        headers: Optional[Dict[str, str]] = None,
    ):
        self.groups = groups
        self.body = body
        self.query = query
        self.token = token  # Bearer token from the Authorization header
        self.client_ip = client_ip
        self.raw = raw      # non-JSON request body (file uploads)
        # Lowercased keys: header names are case-insensitive on the wire
        # and HTTP/2-terminating proxies lowercase them.
        self.headers = {
            k.lower(): v for k, v in (headers or {}).items()
        }  # SSE resume (Last-Event-ID)

    def q(self, name: str, default: Optional[str] = None) -> Optional[str]:
        vals = self.query.get(name)
        return vals[0] if vals else default

    def qfloat(self, name: str, default: float) -> float:
        v = self.q(name)
        return float(v) if v is not None else default


#: The BULK lane: high-volume loss-tolerant telemetry ingest routes, by
#: (method, compiled-pattern) → plane label. Requests matching these pass
#: through `master.admission` (master/overload.py) and answer 429 +
#: Retry-After when the plane is saturated; every other route — all of
#: control (rendezvous, progress beats, preemption polls, resize) — is
#: never queued behind them. Keys must match build_routes() patterns
#: verbatim (pinned by tests/test_metrics_discipline.py, so a route
#: rename cannot silently take its plane out from under admission).
BULK_INGEST_PLANES: Dict[Tuple[str, str], str] = {
    ("POST", r"^/api/v1/trials/(\d+)/metrics$"): "metrics",
    ("POST", r"^/api/v1/traces/ingest$"): "traces",
    ("POST", r"^/api/v1/logs/ingest$"): "logs",
    ("POST", r"^/api/v1/profiles/ingest$"): "profiles",
}


def build_routes(m: Master) -> List[Tuple[str, re.Pattern, Handler]]:
    def exp_of_trial(trial_id: int):
        row = m.db.get_trial(trial_id)
        if row is None:
            raise ApiError(404, f"no trial {trial_id}")
        exp = m.get_experiment(row["experiment_id"])
        if exp is None:
            raise ApiError(404, f"experiment {row['experiment_id']} not loaded")
        return exp

    # Per-trial last-seen cumulative sentinel counters, for delta-folding
    # into the cluster counters (trainers report lifetime totals; a
    # counter must only ever go up by the increment). True LRU: overflow
    # evicts the least-recently-reporting trial (usually finished) — a
    # wholesale clear would re-count every live trial's full history on
    # its next report.
    from collections import OrderedDict as _OrderedDict

    sentinel_seen: "_OrderedDict[int, Tuple[float, float]]" = _OrderedDict()
    sentinel_lock = threading.Lock()
    SENTINEL_SEEN_CAP = 8192

    def _ingest_sentinel(trial_id: int, metrics: Dict[str, Any]) -> None:
        skips = metrics.get("steps_skipped")
        rollbacks = metrics.get("rollbacks")
        if not isinstance(skips, (int, float)) and not isinstance(
            rollbacks, (int, float)
        ):
            return
        def delta(cur: float, prev: float) -> float:
            # Standard counter-reset handling: trainer counters are
            # process-lifetime (not persisted), so a restarted trial
            # reports from 0 again under the same trial id — a drop means
            # reset, and the whole new value is fresh increment.
            if cur >= prev:
                return cur - prev
            return cur

        with sentinel_lock:
            prev_s, prev_r = sentinel_seen.get(trial_id, (0.0, 0.0))
            s = float(skips) if isinstance(skips, (int, float)) else prev_s
            rb = (
                float(rollbacks)
                if isinstance(rollbacks, (int, float)) else prev_r
            )
            d_s, d_r = delta(s, prev_s), delta(rb, prev_r)
            sentinel_seen[trial_id] = (s, rb)
            sentinel_seen.move_to_end(trial_id)
            while len(sentinel_seen) > SENTINEL_SEEN_CAP:
                sentinel_seen.popitem(last=False)
        if d_s > 0:
            SENTINEL_STEPS_SKIPPED.inc(d_s)
        if d_r > 0:
            SENTINEL_ROLLBACKS.inc(d_r)

    # trial -> experiment resolution cache for the goodput gauge: the
    # mapping is immutable for a trial's lifetime, and a DB lookup per
    # profiling report would ride the hot metrics-ingest path otherwise.
    goodput_exp_cache: Dict[int, str] = {}

    def _experiment_of(trial_id: int) -> Optional[str]:
        exp = goodput_exp_cache.get(trial_id)
        if exp is None:
            row = m.db.get_trial(trial_id)
            if row is None:
                return None
            exp = str(row["experiment_id"])
            with sentinel_lock:
                if len(goodput_exp_cache) > SENTINEL_SEEN_CAP:
                    goodput_exp_cache.clear()  # id map: cheap to rebuild
                goodput_exp_cache[trial_id] = exp
        return exp

    # -- harness: metrics/progress/status -----------------------------------
    def post_metrics(r: ApiRequest):
        trial_id = int(r.groups[0])
        group = r.body.get("group", "training")
        metrics = r.body.get("metrics", {})
        m.db.add_metrics(
            trial_id,
            group,
            int(r.body.get("steps_completed", 0)),
            metrics,
            trial_run_id=int(r.body.get("trial_run_id", 0)),
            report_time=r.body.get("report_time"),
        )
        if group == "training":
            _ingest_sentinel(trial_id, metrics)
        if group == "profiling":
            # Surface the trainer timeline's goodput per experiment on the
            # master's own /metrics (the ledger travels as a profiling
            # metric; the gauge shows the experiment's latest report).
            gp = metrics.get("goodput_pct")
            if isinstance(gp, (int, float)):
                exp_label = _experiment_of(trial_id)
                # Live experiments only: a report in flight across the
                # terminal transition (or a resilience-layer replay) must
                # not resurrect the series the terminal-state hook pruned
                # — that would leak one labeled series per race, forever.
                live = (
                    m.get_experiment(int(exp_label))
                    if exp_label is not None else None
                )
                if live is not None and live.state not in TERMINAL_STATES:
                    EXPERIMENT_GOODPUT.labels(exp_label).set(float(gp))
                    if live.state in TERMINAL_STATES:
                        # The experiment went terminal between the check
                        # and the set — the prune hook may have already
                        # fired, so undo our own write (check-then-set
                        # alone would leak the series forever).
                        EXPERIMENT_GOODPUT.remove(exp_label)
            # Per-step FLOPs from the trainer's compiled-step
            # cost_analysis: the MFU numerator, scraped into the TSDB
            # next to the phase fractions. Same live-experiment +
            # undo-on-race discipline as the goodput gauge above.
            sf = metrics.get("step_flops")
            if isinstance(sf, (int, float)) and sf > 0:
                exp_label = _experiment_of(trial_id)
                live = (
                    m.get_experiment(int(exp_label))
                    if exp_label is not None else None
                )
                if live is not None and live.state not in TERMINAL_STATES:
                    STEP_FLOPS.labels(exp_label).set(float(sf))
                    if live.state in TERMINAL_STATES:
                        STEP_FLOPS.remove(exp_label)
            # Feed device HBM utilization to profiling-driven searchers
            # (autotune's microbatch-jump heuristic; experiment.report_hbm
            # no-ops for every other method).
            utils = [
                float(v) for k, v in metrics.items()
                if k.endswith("_hbm_util") and isinstance(v, (int, float))
            ]
            if utils:
                try:
                    exp_of_trial(trial_id).report_hbm(trial_id, max(utils))
                except (ApiError, KeyError):
                    pass  # unmanaged/foreign trial: nothing to feed
        return {}

    def get_metrics(r: ApiRequest):
        return {
            "metrics": m.db.get_metrics(
                int(r.groups[0]), r.q("group"),
                after_id=int(r.q("after") or 0),
            )
        }

    def post_progress(r: ApiRequest):
        trial_id = int(r.groups[0])
        exp_of_trial(trial_id).report_progress(
            trial_id, float(r.body.get("progress", 0.0))
        )
        return {}

    def post_status(r: ApiRequest):
        # Doubles as the unmanaged-trial heartbeat (core_v2._Heartbeat).
        m.record_heartbeat(int(r.groups[0]))
        if r.body.get("event") == "divergence":
            # The harness names a replica-divergence audit failure here on
            # its way down (exec/harness.py) — the agent's exit report only
            # carries the exit CODE, and the replica_divergence alert rule
            # watches this counter.
            SENTINEL_DIVERGENCE.inc()
            logger.warning(
                "trial %s reported replica divergence: %s",
                r.groups[0], r.body.get("detail", ""),
            )
        return {}

    def best_validation(r: ApiRequest):
        trial_id = int(r.groups[0])
        exp = exp_of_trial(trial_id)
        scfg = exp.config.get("searcher", {})
        return {
            "best": m.db.best_validation(
                trial_id,
                scfg.get("metric", "loss"),
                bool(scfg.get("smaller_is_better", True)),
            )
        }

    # -- harness: searcher ops ----------------------------------------------
    def searcher_operation(r: ApiRequest):
        trial_id = int(r.groups[0])
        return exp_of_trial(trial_id).current_searcher_op(
            trial_id, timeout=r.qfloat("timeout_seconds", 60.0)
        )

    def searcher_completed(r: ApiRequest):
        trial_id = int(r.groups[0])
        length = int(r.body["length"])
        metric = float(r.body["metric"])
        exp_of_trial(trial_id).op_completed(trial_id, length, metric)
        # Emitted under the request's dispatch span, which parents from
        # the trial's traceparent: the master-class log line that lands
        # in the SAME trace as the trial's own lines (log plane e2e).
        logger.info(
            "trial %d searcher op completed: length=%d metric=%s",
            trial_id, length, metric,
        )
        return {}

    def searcher_progress(r: ApiRequest):
        return {}

    # -- harness: checkpoints -------------------------------------------------
    def post_checkpoint(r: ApiRequest):
        b = r.body
        m.db.add_checkpoint(
            b["uuid"],
            trial_id=b.get("trial_id"),
            task_id=b.get("task_id", ""),
            allocation_id=b.get("allocation_id", ""),
            resources=b.get("resources", []),
            metadata=b.get("metadata", {}),
            state=b.get("state", "COMPLETED"),
        )
        if b.get("trial_id") is not None:
            m.db.update_trial(int(b["trial_id"]), latest_checkpoint=b["uuid"])
        return {}

    def get_checkpoint(r: ApiRequest):
        ckpt = m.db.get_checkpoint(r.groups[0])
        if ckpt is None:
            raise ApiError(404, "no such checkpoint")
        return ckpt

    # -- harness: allocation signals -----------------------------------------
    def preemption_signal(r: ApiRequest):
        # `generation` (elastic gangs) turns this long-poll into the
        # low-latency resize channel too: it returns early the moment a
        # resize leaves the caller's generation behind, with the pending
        # directive attached.
        gen = r.q("generation")
        gen_i = int(gen) if gen is not None else None
        resp = {
            "preempt": m.alloc_service.should_preempt(
                r.groups[0], timeout=r.qfloat("timeout_seconds", 60.0),
                generation=gen_i,
            )
        }
        resize = m.alloc_service.pending_resize(r.groups[0], gen_i)
        if resize is not None:
            resp["resize"] = resize
        # Task-kind capture directives (serving replicas) ride the
        # preemption poll — the only channel a serving replica drives.
        capture = m.pop_profile_capture(r.groups[0], kinds=("task",))
        if capture is not None:
            resp["profile_capture"] = capture
        return resp

    def ack_preemption(r: ApiRequest):
        m.alloc_service.ack_preempt(r.groups[0])
        return {}

    def preempt_from_task(r: ApiRequest):
        # A task saw SIGTERM (cloud TPU preemption notice) and asks to be
        # preempted gracefully (ref: exec/launch.py:16 SLURM handler).
        # When the notice names a RANK and the trial is elastic, only that
        # rank is reclaimed: the master resizes the gang in place instead
        # of checkpoint-and-requeueing everyone (resize_cost_s, not
        # restart_cost_s).
        rank = r.body.get("rank") if isinstance(r.body, dict) else None
        if rank is not None and m.reclaim_rank(r.groups[0], int(rank)):
            return {"resized": True}
        m.alloc_service.signal_preempt(r.groups[0])
        return {}

    def register_proxy(r: ApiRequest):
        alloc = m.alloc_service.get(r.groups[0])
        if alloc is None:
            raise ApiError(404, "no such allocation")
        # Ownership (task token ↔ its own allocation) is enforced for all
        # /allocations/ routes in _dispatch via task_identity_violation.
        # SSRF guard: a task may only expose itself — the caller's own
        # address or the allocation's rendezvous addresses. No hardcoded
        # loopback: 127.0.0.1 here is the MASTER's loopback (only valid
        # when the task itself is local, i.e. client_ip is loopback).
        allowed = {r.client_ip}
        allowed.update(a.split(":")[0] for a in alloc.addrs.values())
        host = r.body.get("host") or r.client_ip
        if host not in allowed:
            raise ApiError(403, f"proxy host {host!r} is not this allocation")
        m.proxy.register(alloc.task_id, host, int(r.body["port"]))
        return {"url": f"/proxy/{alloc.task_id}/"}

    def list_proxies(r: ApiRequest):
        return {
            "proxies": {
                task_id: {"host": h, "port": p}
                for task_id, (h, p) in m.proxy.list().items()
            }
        }

    def alloc_progress(r: ApiRequest):
        # Gang-progress beat (stall watchdog): every rank posts its
        # last-completed step; the master tick kills the gang when the
        # counter stops advancing within health.stall_timeout_s. The beat
        # doubles as the elastic resize channel: a rank whose generation
        # is stale gets the pending directive back (its beat is NOT
        # recorded — old rank numbering) and must re-sync.
        gen = r.body.get("generation")
        directive = m.alloc_service.record_progress(
            r.groups[0],
            int(r.body.get("rank", 0)),
            int(r.body.get("step", 0)),
            generation=int(gen) if gen is not None else None,
        )
        if directive is not None:
            return {"resize": directive}
        resp: Dict[str, Any] = {}
        if int(r.body.get("rank", 0)) == 0:
            # Trial-kind capture directives ride the chief's beat: one
            # rank owns the jax.profiler session, and the chief is the
            # rank that already does the window's reporting sync.
            capture = m.pop_profile_capture(r.groups[0], kinds=("trial",))
            if capture is not None:
                resp["profile_capture"] = capture
        return resp

    def rendezvous_arrive(r: ApiRequest):
        from determined_tpu.master.allocation import StaleGenerationError

        try:
            m.alloc_service.rendezvous_arrive(
                r.groups[0], int(r.body["rank"]), r.body["addr"],
                generation=int(r.body.get("generation", 0)),
            )
        except StaleGenerationError as e:
            # Terminal fence, not a retry: a straggler that missed the
            # resize must never write into the new gang's rendezvous
            # table. The directive rides the 409 so it can re-sync (or
            # exit, when its rank was dropped) from the rejection itself.
            raise ApiError(
                409, str(e),
                payload={
                    "resync": True,
                    "generation": e.current_gen,
                    "resize": e.directive,
                },
            )
        return {}

    def rendezvous_info(r: ApiRequest):
        from determined_tpu.master.allocation import StaleGenerationError

        try:
            info = m.alloc_service.rendezvous_info(
                r.groups[0], timeout=r.qfloat("timeout_seconds", 600.0),
                generation=int(r.q("generation", "0") or 0),
            )
        except StaleGenerationError as e:
            raise ApiError(
                409, str(e),
                payload={
                    "resync": True,
                    "generation": e.current_gen,
                    "resize": e.directive,
                },
            )
        if info is None:
            raise ApiError(408, "rendezvous timeout")
        return info

    def allgather(r: ApiRequest):
        data = m.alloc_service.allgather(
            r.groups[0], int(r.body["rank"]), r.body.get("data"),
            timeout=r.qfloat("timeout_seconds", 600.0),
        )
        if data is None:
            raise ApiError(408, "allgather timeout")
        return {"data": data}

    # -- task logs -------------------------------------------------------------
    # -- config templates (ref: internal/template/, api_templates.go) ---------
    def set_template(r: ApiRequest):
        name = r.body.get("name", "")
        if not re.fullmatch(r"[\w.\-]+", name or ""):
            # Must stay addressable by the GET/DELETE routes — a name the
            # route pattern can't match would be creatable but undeletable.
            raise ApiError(
                400, "template name must match [A-Za-z0-9_.-]+"
            )
        cfg = r.body.get("config")
        if not isinstance(cfg, dict):
            raise ApiError(400, "template config must be an object")
        m.db.set_template(name, cfg)
        return {"name": name}

    def list_templates(r: ApiRequest):
        return {"templates": m.db.list_templates()}

    def get_template(r: ApiRequest):
        tpl = m.db.get_template(r.groups[0])
        if tpl is None:
            raise ApiError(404, f"no such template {r.groups[0]}")
        return tpl

    def delete_template(r: ApiRequest):
        m.db.delete_template(r.groups[0])
        return {}

    # -- audit log (ref: internal/audit.go) -----------------------------------
    def list_audit(r: ApiRequest):
        return {
            "audit": m.db.list_audit(
                limit=int(r.q("limit", "1000") or 1000),
                username=r.q("username", "") or None,
            )
        }

    def post_task_logs(r: ApiRequest):
        m.db.add_task_logs(r.body["task_id"], r.body.get("logs", []))
        if m.log_sink is not None:
            m.log_sink.ship(r.body["task_id"], r.body.get("logs", []))
        return {}

    def search_task_logs(r: ApiRequest):
        """Filtered log search (ref elastic_trial_logs.go query surface):
        substring/level/time-range/rank. Served from Elasticsearch when the
        sink is configured (the fleet-scale read path), SQLite otherwise —
        same filters, same result shape either way."""
        task_id = r.q("task_id", "")
        kw = dict(
            substring=r.q("search", "") or None,
            level=r.q("level", "") or None,
            since=_q_num(r.q("since"), float, "since") or None,
            until=_q_num(r.q("until"), float, "until") or None,
            rank=_q_num(r.q("rank"), int, "rank"),
            limit=_q_num(r.q("limit"), int, "limit"),
        )
        if kw["limit"] is None:
            kw["limit"] = 1000
        backend = "sqlite"
        want = r.q("backend", "")  # operators may force the SQLite system
        if m.log_sink is not None and want != "sqlite":
            try:
                # Bound the ship lag: drain what's queued before querying —
                # but only when something IS queued; an already-settled
                # sink must not charge every search the barrier round-trip.
                if not m.log_sink.settled():
                    m.log_sink.flush(timeout=2.0)
                logs = m.log_sink.search(
                    task_id,
                    substring=kw["substring"] or "",
                    level=kw["level"] or "",
                    since=kw["since"] or 0.0,
                    until=kw["until"] or 0.0,
                    rank=kw["rank"],
                    limit=kw["limit"],
                )
                backend = "elastic"
            except Exception:  # noqa: BLE001 — ES down: the system of
                # record still has every line (the sink is additive).
                logger.exception("ES log search failed; serving SQLite")
                logs = m.db.search_task_logs(task_id, **kw)
        else:
            logs = m.db.search_task_logs(task_id, **kw)
        return {"logs": logs, "backend": backend}

    def get_task_logs(r: ApiRequest):
        return {
            "logs": m.db.get_task_logs(
                r.q("task_id", ""), int(r.q("after", "0") or 0)
            )
        }

    #: SSE follow streams poll the indexed cursor server-side at this
    #: cadence and push rows down ONE connection — the client holds no
    #: timer and re-requests nothing (the WebUI's log/metric panes).
    SSE_POLL_S = 0.3
    #: Idle keepalive comment cadence: browsers/proxies only need a few
    #: per minute to hold the connection; the cursor still polls at
    #: SSE_POLL_S so rows flow promptly.
    SSE_KEEPALIVE_S = 10.0
    SSE_MAX_S = 6 * 3600.0

    def _sse_start(r: ApiRequest, param: str = "after") -> int:
        """Stream resume cursor: EventSource reconnects carry the last
        `id:` we sent as Last-Event-ID — honoring it means a reconnect
        continues instead of replaying (and duplicating) the history."""
        last = r.headers.get("last-event-id", "")
        if last.isdigit():
            return int(last)
        return int(r.q(param, "0") or 0)

    def _sse_follow(fetch):
        """Generator: stream `fetch(cursor) -> rows` as (id, json) events
        until master shutdown or the follow budget."""
        def gen():
            import json as _json

            deadline = time.time() + SSE_MAX_S
            cursor = None
            last_write = time.time()
            while not m._stop.is_set() and time.time() < deadline:
                rows, cursor = fetch(cursor)
                if rows:
                    for row in rows:
                        yield row["id"], _json.dumps(row)
                    last_write = time.time()
                else:
                    if time.time() - last_write >= SSE_KEEPALIVE_S:
                        yield None  # keepalive comment
                        last_write = time.time()
                    time.sleep(SSE_POLL_S)
        return gen()

    def stream_task_logs(r: ApiRequest):
        """GET /api/v1/task_logs/stream?task_id=X&after=N — SSE follow of
        a task's log lines (the WebUI's live log pane; replaces 1 s
        polling with one held connection)."""
        task_id = r.q("task_id", "")
        start = _sse_start(r)

        def fetch(cursor):
            cursor = start if cursor is None else cursor
            rows = m.db.get_task_logs(task_id, after_id=cursor, limit=500)
            if rows:
                cursor = rows[-1]["id"]
            return rows, cursor

        raise _EventStream(_sse_follow(fetch))

    def stream_trial_metrics(r: ApiRequest):
        """GET /api/v1/trials/{id}/metrics/stream?after=N — SSE follow of
        a trial's metric rows (same cursor contract as the incremental
        /metrics endpoint)."""
        trial_id = int(r.groups[0])
        start = _sse_start(r)

        def fetch(cursor):
            cursor = start if cursor is None else cursor
            rows = m.db.get_metrics(trial_id, after_id=cursor)
            if rows:
                cursor = rows[-1]["id"]
            return rows, cursor

        raise _EventStream(_sse_follow(fetch))

    # -- agents ---------------------------------------------------------------
    def register_agent(r: ApiRequest):
        # Scrape-target registration rides the normal register: the agent
        # names its health PORT; the host is this connection's source
        # address (the agent may not know its own externally-reachable
        # name, but the address it dialed us from is it).
        metrics_port = r.body.get("metrics_port")
        metrics_addr = None
        if metrics_port:
            try:
                port_num = int(metrics_port)
            except (TypeError, ValueError):
                raise ApiError(
                    400, f"metrics_port must be an integer, got {metrics_port!r}"
                )
            host = r.client_ip or "127.0.0.1"
            if ":" in host:  # IPv6 literal needs brackets in a URL
                host = f"[{host}]"
            metrics_addr = f"{host}:{port_num}"
        res = m.agent_registered(
            r.body["agent_id"],
            int(r.body.get("slots", 0)),
            r.body.get("pool", "default"),
            r.body.get("running_allocs") or [],
            r.body.get("exiting_allocs") or [],
            devices=r.body.get("devices") or [],
            metrics_addr=metrics_addr,
        )
        res["cluster_id"] = m.cluster_id
        # Profiling-plane opt-in rides the register ack: the agent daemon
        # has no launch env to read DTPU_PROFILE from, so the master tells
        # it directly whether (and how fast) to sample itself.
        if m._profiling_cfg["enabled"]:
            res["profiling"] = {
                "sample_hz": m._profiling_cfg["sample_hz"],
                "window_s": m._profiling_cfg["window_s"],
            }
        return res

    def agent_actions(r: ApiRequest):
        return {
            "actions": m.agent_hub.poll(
                r.groups[0], timeout=r.qfloat("timeout_seconds", 30.0)
            )
        }

    def agent_events(r: ApiRequest):
        if m.agent_event(r.groups[0], r.body) is False:
            # Experiment restore hasn't caught up with this exit report;
            # 503 keeps it pending on the agent (retryable) instead of
            # swallowing it.
            raise ApiError(503, "restore in progress; retry")
        return {}

    def list_agents(r: ApiRequest):
        return {"agents": m.agent_hub.list()}

    def agent_enable(r: ApiRequest):
        if r.groups[0] not in m.agent_hub.list():
            raise ApiError(404, "no such agent")
        return m.set_agent_enabled(r.groups[0], True)

    def agent_disable(r: ApiRequest):
        """EnableAgent/DisableAgent parity (ref api_agents.go:140,149):
        {"drain": true} lets running allocations finish; without it they
        are killed and requeued (infra — no restart-budget charge)."""
        if r.groups[0] not in m.agent_hub.list():
            raise ApiError(404, "no such agent")
        return m.set_agent_enabled(
            r.groups[0], False, drain=bool(r.body.get("drain"))
        )

    def slot_state(r: ApiRequest):
        agent_id, slot, verb = r.groups
        info = m.agent_hub.list().get(agent_id)
        if info is None:
            raise ApiError(404, "no such agent")
        if int(slot) >= int(info.get("slots", 0)):
            raise ApiError(404, f"agent {agent_id} has no slot {slot}")
        return m.set_slot_enabled(agent_id, int(slot), verb == "enable")

    # -- job queue --------------------------------------------------------------
    def queue_list(r: ApiRequest):
        out = {}
        for name, pool in m.rm.pools.items():
            snap = pool.queue_snapshot()
            out[name] = {
                "pending": snap["pending"],
                "running": snap["running"],
                "pending_slots": snap["pending_slots"],
            }
        return {"queues": out}

    def queue_move(r: ApiRequest):
        pool = m.rm.pool(r.body.get("pool"))
        try:
            pool.reorder(
                r.body["alloc_id"], ahead_of=r.body.get("ahead_of")
            )
        except KeyError as e:
            raise ApiError(404, str(e))
        return {}

    # -- experiments (user/CLI) -------------------------------------------------
    def _submit_trace(r: ApiRequest):
        """The submitting request's trace context: passed INTO experiment
        creation so allocation spans and launched-task env
        (DTPU_TRACEPARENT) parent back to it — recorded before the first
        scheduler tick can launch anything, so one trace id spans submit →
        schedule → launch → first trial step with no race."""
        return trace_mod.parse_traceparent(r.headers.get("traceparent"))

    def create_experiment(r: ApiRequest):
        try:
            exp_id = m.create_experiment(
                r.body["config"], traceparent=_submit_trace(r)
            )
        except ValueError as e:
            raise ApiError(400, str(e))
        return {"id": exp_id}

    def list_experiments(r: ApiRequest):
        """Paginated + archived-filtered (ref: GetExperiments pagination,
        api_experiment.go). Archived experiments are hidden unless
        ?include_archived=1 (the SDK sends it by default so scripts keep
        seeing everything; the WebUI hides them). Omitting limit returns
        the full (filtered) list."""
        include_archived = r.q("include_archived", "") in ("1", "true")
        limit = r.q("limit", "")
        label = r.q("label", "") or None
        kw: Dict[str, Any] = {"include_archived": include_archived}
        kw["newest_first"] = r.q("order", "") == "desc"
        kw["label"] = label
        try:
            if limit:
                kw["limit"] = max(1, min(int(limit), 500))
                kw["offset"] = max(0, int(r.q("offset", "0") or 0))
        except ValueError:
            raise ApiError(400, "limit/offset must be integers")
        return {
            "experiments": m.db.list_experiments(**kw),
            "total": m.db.count_experiments(
                include_archived=include_archived, label=label
            ),
        }

    def exp_move(r: ApiRequest):
        """MoveExperiment (ref: api_experiment.go MoveExperiment): re-home
        an experiment under another project."""
        exp_id = int(r.groups[0])
        if m.db.get_experiment(exp_id) is None:
            raise ApiError(404, "no such experiment")
        try:
            project_id = int(r.body["project_id"])
        except (KeyError, TypeError, ValueError):
            raise ApiError(400, "body must carry integer project_id")
        if not any(p["id"] == project_id for p in m.db.list_projects()):
            raise ApiError(404, f"no such project {project_id}")
        m.db.set_experiment_project(exp_id, project_id)
        return {"project_id": project_id}

    def trial_kill(r: ApiRequest):
        """KillTrial (ref: api_trials.go KillTrial): stop one trial; the
        experiment's other trials keep running."""
        trial_id = int(r.groups[0])
        row = m.db.get_trial(trial_id)
        if row is None:
            raise ApiError(404, "no such trial")
        exp = m.get_experiment(int(row["experiment_id"]))
        if exp is None:
            # experiment already terminal: the trial can't be running
            return {"killed": False}
        try:
            return {"killed": exp.kill_trial(trial_id)}
        except KeyError as e:
            raise ApiError(404, str(e))

    def exp_patch(r: ApiRequest):
        """PatchExperiment (ref: api_experiment.go PatchExperiment,
        experiment.proto PatchExperiment): partial update of
        name/description/labels/notes. Omitted fields are untouched."""
        exp_id = int(r.groups[0])
        if m.db.get_experiment(exp_id) is None:
            raise ApiError(404, "no such experiment")
        fields = {}
        for key in ("name", "description", "notes"):
            if key in r.body:
                if not isinstance(r.body[key], str):
                    raise ApiError(400, f"{key} must be a string")
                fields[key] = r.body[key]
        if "labels" in r.body:
            labels = r.body["labels"]
            if not isinstance(labels, list) or not all(
                isinstance(x, str) for x in labels
            ):
                raise ApiError(400, "labels must be a list of strings")
            # dedupe, order-preserving
            fields["labels"] = list(dict.fromkeys(labels))
        m.db.patch_experiment_meta(exp_id, **fields)
        return {"experiment": m.db.get_experiment(exp_id)}

    def exp_archive(r: ApiRequest):
        exp_id = int(r.groups[0])
        row = m.db.get_experiment(exp_id)
        if row is None:
            raise ApiError(404, "no such experiment")
        want = r.groups[1] == "archive"
        if want:
            live = m.get_experiment(exp_id)
            state = live.state if live is not None else row["state"]
            if state not in ("COMPLETED", "CANCELED", "ERRORED"):
                # Archiving running work would hide it from every default
                # listing while it still consumes chips (the reference
                # archives terminal experiments only).
                raise ApiError(400, f"cannot archive experiment in {state}")
        m.db.set_experiment_archived(exp_id, want)
        return {"archived": want}

    def exp_fork(r: ApiRequest):
        """New experiment from a stored config (+ overrides), optionally
        warm-started from a checkpoint (ref: api_experiment.go fork /
        continue flows). checkpoint_uuid="best"/"latest" resolves against
        the source experiment's trials."""
        from determined_tpu.master import expconf

        src = m.db.get_experiment(int(r.groups[0]))
        if src is None:
            raise ApiError(404, "no such experiment")
        config = dict(src["config"])
        # The stored config is the MERGED one; drop bookkeeping keys that
        # must be re-derived on the fork.
        config.pop("warm_start_checkpoint", None)
        overrides = r.body.get("config") or {}
        if overrides:
            config = dict(expconf.merge(overrides, config))
        ckpt = r.body.get("checkpoint_uuid")
        if ckpt in ("best", "latest"):
            ckpt = _resolve_source_checkpoint(src, ckpt)
            if ckpt is None:
                raise ApiError(400, "source experiment has no checkpoints")
        if ckpt:
            row = m.db.get_checkpoint(str(ckpt))
            if row is None:
                raise ApiError(404, f"no such checkpoint {ckpt}")
            if row.get("state") != "COMPLETED":
                # GC'd/deleted: the storage files are gone; warm-starting
                # from it would crash the fork's first trial at restore.
                raise ApiError(400, f"checkpoint {ckpt} is {row.get('state')}")
            config["warm_start_checkpoint"] = str(ckpt)
        try:
            new_id = m.create_experiment(
                config, traceparent=_submit_trace(r)
            )
        except ValueError as e:
            raise ApiError(400, str(e))
        return {"id": new_id, "forked_from": src["id"],
                "warm_start_checkpoint": config.get("warm_start_checkpoint")}

    def _resolve_source_checkpoint(src: Dict[str, Any], which: str):
        # "best" honors searcher.smaller_is_better (default True), like
        # best_validation and checkpoint GC — resolving with a hardcoded
        # minimize would warm-start accuracy-metric forks from the WORST
        # trial.
        smaller = bool(
            (src["config"].get("searcher") or {}).get("smaller_is_better", True)
        )

        def _live(uuid):
            row = m.db.get_checkpoint(uuid) if uuid else None
            return uuid if row and row.get("state") == "COMPLETED" else None

        best_uuid, best_metric, latest_uuid, latest_ts = None, None, None, -1.0
        for t in m.db.list_trials(src["id"]):
            for c in m.db.list_checkpoints(t["id"]):  # COMPLETED-only
                ts = float(c.get("report_time") or 0)
                if ts > latest_ts:
                    latest_uuid, latest_ts = c["uuid"], ts
            metric = t.get("searcher_metric")
            if metric is not None:
                better = best_metric is None or (
                    float(metric) < best_metric
                    if smaller else float(metric) > best_metric
                )
                ck = _live(t.get("latest_checkpoint"))
                if better and ck:
                    best_uuid, best_metric = ck, float(metric)
        return best_uuid if which == "best" and best_uuid else latest_uuid

    def exp_continue(r: ApiRequest):
        """Continue training a finished experiment: fork from its latest
        checkpoint with a longer searcher target (ref: `det experiment
        continue`)."""
        src = m.db.get_experiment(int(r.groups[0]))
        if src is None:
            raise ApiError(404, "no such experiment")
        body = dict(r.body or {})
        overrides = body.get("config") or {}
        length = body.get("max_length")
        if length is not None:
            overrides = dict(overrides)
            searcher = dict(overrides.get("searcher")
                            or src["config"].get("searcher") or {})
            searcher["max_length"] = int(length)
            overrides["searcher"] = searcher
        r.body = {"config": overrides,
                  "checkpoint_uuid": body.get("checkpoint_uuid", "latest")}
        return exp_fork(r)

    def list_resource_pools(r: ApiRequest):
        """Cluster overview (ref: GetResourcePools, api_resourcepools)."""
        pools = []
        for name, pool in m.rm.pools.items():
            agents = pool.agents_snapshot()
            snap = pool.queue_snapshot()
            pools.append({
                "name": name,
                "type": type(pool).__name__,
                "agents": len(agents),
                "agents_disabled": sum(
                    1 for a in agents.values() if not a["enabled"]
                ),
                "slots_total": sum(a["slots"] for a in agents.values()),
                "slots_used": sum(a["used"] for a in agents.values()),
                "slots_disabled": sum(
                    # A disabled agent's whole capacity is out of service.
                    a["slots"] if not a["enabled"]
                    else a.get("disabled_slots", 0)
                    for a in agents.values()
                ),
                "pending_allocs": len(snap["pending"]),
                "pending_slots": snap["pending_slots"],
                "running_allocs": len(snap["running"]),
            })
        return {"resource_pools": pools}

    def get_experiment(r: ApiRequest):
        row = m.db.get_experiment(int(r.groups[0]))
        if row is None:
            raise ApiError(404, "no such experiment")
        live = m.get_experiment(int(r.groups[0]))
        if live is not None:
            row["state"] = live.state
        return row

    def exp_resources(r: ApiRequest):
        """Live priority/weight/max_slots update (ref: UpdateJobQueue,
        api.proto:1110; det experiment set priority). Takes effect on the
        next tick — the priority scheduler may preempt on a flip."""
        body = r.body
        kwargs: Dict[str, Any] = {}
        for field in ("priority", "weight"):
            if field in body:
                if body[field] is None:
                    # None means "not provided" downstream; accepting an
                    # explicit null would 200 as a silent no-op while
                    # reporting live requests updated.
                    raise ApiError(400, f"{field} must not be null")
                kwargs[field] = body[field]
        if "max_slots" in body:
            kwargs["max_slots"] = body["max_slots"]  # null clears the cap
        if not kwargs:
            raise ApiError(
                400, "body must carry priority, weight, or max_slots"
            )
        try:
            return m.update_experiment_resources(int(r.groups[0]), **kwargs)
        except KeyError as e:
            raise ApiError(404, str(e))
        except (TypeError, ValueError) as e:
            raise ApiError(400, str(e))

    def exp_delete(r: ApiRequest):
        """DeleteExperiment (ref api_experiment.go:365): terminal
        experiments only; checkpoint files then rows, async on the
        master's background worker (state DELETING → gone, or
        DELETE_FAILED with rows intact)."""
        try:
            m.delete_experiment(int(r.groups[0]))
        except KeyError as e:
            raise ApiError(404, str(e))
        except ValueError as e:
            raise ApiError(400, str(e))
        return {"state": "DELETING"}

    def ckpt_delete(r: ApiRequest):
        """DeleteCheckpoints (ref api_checkpoint.go:375): files removed,
        row marked DELETED; registry-referenced checkpoints refuse."""
        try:
            m.delete_checkpoint(r.groups[0])
        except KeyError as e:
            raise ApiError(404, str(e))
        except ValueError as e:
            raise ApiError(400, str(e))
        return {}

    def exp_action(r: ApiRequest):
        exp = m.get_experiment(int(r.groups[0]))
        if exp is None:
            raise ApiError(404, "no such experiment")
        action = r.groups[1]
        {"pause": exp.pause, "activate": exp.activate,
         "cancel": exp.cancel, "kill": exp.kill}[action]()
        return {"state": exp.state}

    def list_trials(r: ApiRequest):
        exp_id = int(r.groups[0])
        limit = r.q("limit", "")
        kw: Dict[str, Any] = {}
        try:
            if limit:
                kw["limit"] = max(1, min(int(limit), 500))
                kw["offset"] = max(0, int(r.q("offset", "0") or 0))
        except ValueError:
            raise ApiError(400, "limit/offset must be integers")
        return {
            "trials": m.db.list_trials(exp_id, **kw),
            "total": m.db.count_trials(exp_id),
        }

    def searcher_events(r: ApiRequest):
        exp = m.get_experiment(int(r.groups[0]))
        if exp is None:
            raise ApiError(404, "no such experiment")
        try:
            events = exp.get_searcher_events(
                after_id=int(r.q("after", "0") or 0),
                timeout=r.qfloat("timeout_seconds", 60.0),
            )
        except ValueError as e:
            raise ApiError(400, str(e))
        return {"events": events, "experiment_state": exp.state}

    def post_searcher_ops(r: ApiRequest):
        exp = m.get_experiment(int(r.groups[0]))
        if exp is None:
            raise ApiError(404, "no such experiment")
        try:
            exp.post_searcher_operations(r.body.get("operations", []))
        except ValueError as e:
            raise ApiError(400, str(e))
        return {}

    def get_trial(r: ApiRequest):
        row = m.db.get_trial(int(r.groups[0]))
        if row is None:
            raise ApiError(404, "no such trial")
        return row

    def trial_checkpoints(r: ApiRequest):
        return {"checkpoints": m.db.list_checkpoints(int(r.groups[0]))}

    # -- NTSC commands ----------------------------------------------------------
    def create_command(r: ApiRequest):
        return {"task_id": m.create_command(r.body["config"])}

    def list_commands(r: ApiRequest):
        return {"commands": m.list_commands()}

    def kill_command(r: ApiRequest):
        m.kill_command(r.groups[0])
        return {}

    # -- serving-fleet router ----------------------------------------------------
    def fleet_generate(r: ApiRequest):
        """POST /api/v1/generate — cache-aware fan-out over the RUNNING
        SERVING replicas (master/router.py): consistent-hash on the
        prompt's leading page hash, load spill, shed-aware failover
        (once, within the request deadline). The replica's response —
        SSE token stream or buffered JSON — passes through verbatim."""
        from determined_tpu.master.router import NoReplicas

        body = r.body
        # The route key needs the token stream the REPLICA will see:
        # same extraction rules as serving/service.py tokenize().
        if "prompt" in body:
            prompt = body["prompt"]
            if not isinstance(prompt, list) or not all(
                isinstance(t, int) and not isinstance(t, bool)
                for t in prompt
            ):
                raise ApiError(400, "prompt must be a list of token ids")
        elif "text" in body:
            if not isinstance(body["text"], str):
                raise ApiError(400, "text must be a string")
            prompt = list(body["text"].encode("utf-8"))
        else:
            raise ApiError(
                400, "body must carry prompt (token ids) or text"
            )
        deadline_ms = body.get("deadline_ms")
        if deadline_ms is not None and (
            isinstance(deadline_ms, bool)
            or not isinstance(deadline_ms, (int, float))
        ):
            raise ApiError(400, "deadline_ms must be a number")
        pool = body.get("resource_pool")
        if pool is not None and not isinstance(pool, str):
            raise ApiError(400, "resource_pool must be a string")
        fwd_headers = {"Content-Type": "application/json"}
        tp = r.headers.get("traceparent")
        if tp:
            fwd_headers["traceparent"] = tp
        try:
            status, headers, chunks, _replica = m.router.dispatch(
                prompt, r.raw, fwd_headers, pool=pool,
                deadline_s=(
                    float(deadline_ms) / 1e3
                    if deadline_ms is not None else None
                ),
            )
        except NoReplicas as e:
            raise ApiError(503, str(e))
        raise _RawStream(status, headers, chunks)

    def cluster_stats(r: ApiRequest):
        """GET /api/v1/stats — fleet snapshot: the router's recent
        routing decisions/in-flight accounting plus the routable
        replica set."""
        return {
            "router": m.router.stats(),
            "replicas": m.router.replicas(r.q("pool")),
        }

    # -- model registry ---------------------------------------------------------
    def create_model(r: ApiRequest):
        m.db.add_model(
            r.body["name"], r.body.get("description", ""), r.body.get("metadata")
        )
        return m.db.get_model(r.body["name"])

    def list_models(r: ApiRequest):
        return {"models": m.db.list_models()}

    def get_model(r: ApiRequest):
        model = m.db.get_model(r.groups[0])
        if model is None:
            raise ApiError(404, "no such model")
        return model

    def delete_model(r: ApiRequest):
        """DeleteModel (ref api_model.go:525): removes the model and its
        versions — the checkpoints they pinned become GC/delete-eligible."""
        try:
            m.db.delete_model(r.groups[0])
        except KeyError as e:
            raise ApiError(404, str(e))
        return {}

    def delete_model_version(r: ApiRequest):
        try:
            m.db.delete_model_version(r.groups[0], int(r.groups[1]))
        except KeyError as e:
            raise ApiError(404, str(e))
        return {}

    def create_model_version(r: ApiRequest):
        name = r.groups[0]
        if m.db.get_model(name) is None:
            raise ApiError(404, "no such model")
        if m.db.get_checkpoint(r.body["checkpoint_uuid"]) is None:
            raise ApiError(404, "no such checkpoint")
        version = m.db.add_model_version(
            name, r.body["checkpoint_uuid"], r.body.get("metadata")
        )
        return {"version": version}

    def list_model_versions(r: ApiRequest):
        return {"versions": m.db.list_model_versions(r.groups[0])}

    # -- workspaces / projects ----------------------------------------------------
    def create_workspace(r: ApiRequest):
        return {"id": m.db.add_workspace(r.body["name"])}

    def list_workspaces(r: ApiRequest):
        return {"workspaces": m.db.list_workspaces()}

    def create_project(r: ApiRequest):
        return {
            "id": m.db.add_project(
                r.body["name"], int(r.body.get("workspace_id", 1))
            )
        }

    def list_projects(r: ApiRequest):
        wid = r.q("workspace_id")
        return {"projects": m.db.list_projects(int(wid) if wid else None)}

    # -- webhooks -----------------------------------------------------------------
    def create_webhook(r: ApiRequest):
        return {
            "id": m.db.add_webhook(
                r.body["url"],
                r.body.get("trigger_states", ["COMPLETED", "ERRORED"]),
            )
        }

    def list_webhooks(r: ApiRequest):
        return {"webhooks": m.db.list_webhooks()}

    def delete_webhook(r: ApiRequest):
        m.db.delete_webhook(int(r.groups[0]))
        return {}

    # -- context files (model-def upload, ref: common/context.py bundling) -----
    MAX_CONTEXT_BYTES = 96 * 1024 * 1024

    def upload_file(r: ApiRequest):
        if not r.raw:
            raise ApiError(400, "empty upload")
        if len(r.raw) > MAX_CONTEXT_BYTES:
            raise ApiError(413, "context too large (96MB cap)")
        return {"id": m.db.put_file(r.raw)}

    def download_file(r: ApiRequest):
        data = m.db.get_file(r.groups[0])
        if data is None:
            raise ApiError(404, "no such file")
        raise _PlainText(data, content_type="application/octet-stream")

    def master_info(r: ApiRequest):
        from determined_tpu.master import native_sched

        return {
            "cluster_id": m.cluster_id,
            "version": __import__("determined_tpu").__version__,
            "agents": m.agent_hub.list(),
            # Which gang-fitting scan this process runs: a failed build of
            # native/scheduler.cpp falls back silently otherwise.
            "scheduler_fit": (
                "native" if native_sched.load_library(build=False) is not None
                else "python"
            ),
        }

    def master_logs(r: ApiRequest):
        """GetMasterLogs (ref: api_master.go): tail of the master's own
        log ring; ?since_id= for follow-without-duplicates."""
        try:
            limit = min(int(r.q("limit", "200") or 200), 1000)
            since_id = int(r.q("since_id", "0") or 0)
        except ValueError:
            raise ApiError(400, "limit/since_id must be integers")
        return {"logs": m._log_buffer.tail(limit=limit, since_id=since_id)}

    # -- RBAC admin (ref internal/rbac + internal/usergroup) ----------------
    def _persist_rbac():
        m.db.set_kv("rbac", m.auth.rbac_state())

    def list_users(r: ApiRequest):
        state = m.auth.rbac_state()
        known = m.auth.known_users()
        return {"users": [
            {"username": u, "role": role,
             "effective_role": m.auth.effective_role(u),
             "active": known.get(u, {}).get("active", True)}
            for u, role in sorted(state["roles"].items())
        ]}

    def create_user(r: ApiRequest):
        """PostUser (ref: api_user.go PostUser): runtime user creation,
        admin-only via the /users route class."""
        try:
            m.auth.create_user(
                str(r.body.get("username", "")),
                str(r.body.get("password", "")),
                str(r.body.get("role", "editor")),
            )
        except ValueError as e:
            raise ApiError(400, str(e))
        return {"username": r.body.get("username", "")}

    def set_user_password(r: ApiRequest):
        """Admin password reset (ref: SetUserPassword). Self-service lives
        at /api/v1/auth/password (this whole route class is admin)."""
        try:
            m.auth.set_password(r.groups[0], str(r.body.get("password", "")))
        except KeyError as e:
            raise ApiError(404, str(e))
        except ValueError as e:
            raise ApiError(400, str(e))
        return {}

    def change_own_password(r: ApiRequest):
        """Self-service password change: any authenticated user, own
        account only (so it rides outside the admin /users class)."""
        who = m.auth.validate(r.token) or ""
        if not who or who == "anonymous" or ":" in who:
            raise ApiError(403, "a logged-in user session is required")
        # Re-verify the current password: a bearer token alone is a
        # TTL-bounded credential and must not mint a permanent one
        # (r4 advisor; admin resets via /users/<name>/password don't
        # re-verify — they're the recovery path).
        if not m.auth.verify_password(
            who, str(r.body.get("current_password", ""))
        ):
            raise ApiError(403, "current password incorrect")
        try:
            m.auth.set_password(who, str(r.body.get("password", "")))
        except (KeyError, ValueError) as e:
            raise ApiError(400, str(e))
        return {}

    def patch_user(r: ApiRequest):
        """PatchUser activate/deactivate (ref: api_user.go PatchUser)."""
        if "active" not in r.body:
            raise ApiError(400, "body must carry {'active': bool}")
        try:
            m.auth.set_active(r.groups[0], bool(r.body["active"]))
        except KeyError as e:
            raise ApiError(404, str(e))
        except ValueError as e:  # last-admin lockout guard
            raise ApiError(400, str(e))
        return {"active": bool(r.body["active"])}

    def set_user_role(r: ApiRequest):
        try:
            m.auth.set_user_role(r.groups[0], str(r.body.get("role", "")))
        except KeyError as e:
            raise ApiError(404, str(e))
        except ValueError as e:
            raise ApiError(400, str(e))
        _persist_rbac()
        return {}

    def list_groups(r: ApiRequest):
        return {"groups": m.auth.rbac_state()["groups"]}

    def upsert_group(r: ApiRequest):
        name = str(r.body.get("name", ""))
        if not name:
            raise ApiError(400, "group name required")
        try:
            m.auth.upsert_group(name, str(r.body.get("role", "viewer")))
        except ValueError as e:
            raise ApiError(400, str(e))
        _persist_rbac()
        return {}

    def modify_group(r: ApiRequest):
        try:
            m.auth.modify_group_members(
                r.groups[0],
                add=[str(u) for u in r.body.get("add", [])],
                remove=[str(u) for u in r.body.get("remove", [])],
            )
        except KeyError as e:
            raise ApiError(404, str(e))
        except ValueError as e:  # last-admin lockout guard
            raise ApiError(400, str(e))
        _persist_rbac()
        return {}

    def delete_group(r: ApiRequest):
        try:
            m.auth.delete_group(r.groups[0])
        except ValueError as e:  # last-admin lockout guard
            raise ApiError(400, str(e))
        _persist_rbac()
        return {}

    def auth_login(r: ApiRequest):
        token = m.auth.login(r.body.get("username", ""), r.body.get("password", ""))
        if token is None:
            raise ApiError(401, "invalid credentials")
        return {"token": token}

    def auth_logout(r: ApiRequest):
        m.auth.logout(r.token or r.body.get("token", ""))
        return {}

    def webui_page(r: ApiRequest):
        from determined_tpu.master.webui import PAGE

        raise _PlainText(PAGE, content_type="text/html; charset=utf-8")

    def prometheus_metrics(r: ApiRequest):
        # The process-global registry (common/metrics.py) in strict
        # Prometheus text format — counters/histograms accrue continuously
        # from the instrumented paths; the cluster-state gauges below
        # (ref: internal/prom/det_state_metrics.go:91) are refreshed from
        # pool snapshots at scrape time. This replaces the hand-rolled
        # exposition whose output (`dtpu_x{} 1`, no HELP/TYPE, unescaped
        # labels) a strict parser rejected.
        for pool_name, pool in m.rm.pools.items():
            agents = pool.agents_snapshot()
            POOL_AGENTS.labels(pool_name).set(len(agents))
            POOL_SLOTS_TOTAL.labels(pool_name).set(
                sum(a["slots"] for a in agents.values())
            )
            POOL_SLOTS_USED.labels(pool_name).set(
                sum(a["used"] for a in agents.values())
            )
            q = pool.queue_snapshot()
            POOL_ALLOCS_PENDING.labels(pool_name).set(len(q["pending"]))
            POOL_ALLOCS_RUNNING.labels(pool_name).set(len(q["running"]))
        by_state: Dict[str, int] = {}
        for e in m.db.list_experiments():
            by_state[e["state"]] = by_state.get(e["state"], 0) + 1
        # Atomic swap: a state that emptied out must drop from the
        # exposition, and a CONCURRENT render (second scrape, co-resident
        # agent metrics server) must never observe the family mid-rebuild.
        EXPERIMENTS_BY_STATE.replace(
            {(state,): float(n) for state, n in by_state.items()}
        )
        # exemplars ride as `# EXEMPLAR` comment lines: strict/lenient
        # parsers skip them; the scrape sweep harvests them so quantile
        # answers can name the concrete trace behind a bucket.
        raise _PlainText(METRICS.render(exemplars=True))

    # -- time-series plane (common/tsdb.py + master/timeseries.py): the
    # -- master's own metric HISTORY, not just the instant /metrics ----------
    def metrics_query(r: ApiRequest):
        """GET /api/v1/metrics/query — instant + range queries over the
        in-master TSDB. `name` selects the family; `match=label=value`
        (repeatable) filters series; `func` is raw|instant|rate|increase|
        quantile (`window` seconds for the windowed funcs, `q` for
        quantile); `start`/`end`/`step` (unix seconds) make it a range."""
        name = r.q("name")
        if not name:
            raise ApiError(400, "query needs ?name=<metric family>")
        matchers: Dict[str, str] = {}
        for item in r.query.get("match", []):
            label, sep, value = item.partition("=")
            if not sep or not label:
                raise ApiError(
                    400, f"bad match {item!r} (want label=value)"
                )
            matchers[label] = value
        start = r.q("start")
        try:
            # Numeric param junk answers 400 here too — a dashboard's
            # malformed time range must not read as a server error.
            result = m.tsdb.query(
                name,
                func=r.q("func", "instant"),
                matchers=matchers,
                window_s=r.qfloat("window", 300.0),
                q=r.qfloat("q", 0.99),
                start=float(start) if start is not None else None,
                end=(
                    float(r.q("end")) if r.q("end") is not None else None
                ),
                step=(
                    float(r.q("step")) if r.q("step") is not None else None
                ),
            )
        except (TypeError, ValueError) as e:
            raise ApiError(400, str(e))
        payload = {
            "name": name,
            "func": r.q("func", "instant"),
            "range": start is not None,
            "result": result,
        }
        # Quantile answers carry the exemplars of the bucket series they
        # were computed from (trace plane: `histogram_quantile` → the
        # concrete slow trace). ?exemplars=1 attaches them to any func.
        if r.q("func", "instant") == "quantile" or r.q("exemplars") in (
            "1", "true",
        ):
            payload["exemplars"] = m.tsdb.exemplars(name, matchers)
        return payload

    def metrics_series(r: ApiRequest):
        """GET /api/v1/metrics/series — series discovery + TSDB bounds
        accounting (series/points vs their by-construction caps)."""
        return {
            "series": m.tsdb.series(r.q("name")),
            "stats": m.tsdb.stats(),
        }

    def list_alerts(r: ApiRequest):
        """GET /api/v1/alerts — pending/firing instances, recent resolved
        history, and the loaded rule set's names."""
        try:
            limit = int(r.q("limit", "50"))
        except ValueError:
            raise ApiError(400, "limit must be an integer")
        return {
            "alerts": m.alert_engine.active(),
            "history": m.alert_engine.history(limit),
            "rules": m.alert_engine.rule_names(),
        }

    # -- trace plane (master/tracestore.py): the master's own span store,
    # -- fed by the common/trace.py SpanShipper in every process ------------
    def traces_ingest(r: ApiRequest):
        """POST /api/v1/traces/ingest — batch span ingest from shippers.
        Never 4xxes a well-formed envelope: per-span problems are dropped
        and counted inside the store (a shipper must not retry-loop over
        one bad span)."""
        from determined_tpu.common import faults

        if not m._traces_cfg["enabled"]:
            # Launched tasks are told not to ship (DTPU_TRACE_INGEST=off)
            # but daemons configured before the toggle — or agents, which
            # ship unconditionally — must not fill a disabled plane's
            # store. 404 is a non-retryable status for the shipper: the
            # batch is counted dropped once, no retry churn.
            raise ApiError(404, "trace plane disabled (traces.enabled)")
        faults.inject("master.trace_ingest")
        spans = r.body.get("spans")
        if spans is None:
            spans = []
        if not isinstance(spans, list):
            raise ApiError(400, "spans must be a list of OTLP span objects")
        return {"stored": m.tracestore.ingest(spans)}

    def traces_get(r: ApiRequest):
        """GET /api/v1/traces/<trace_id> — ONE assembled trace: span tree
        plus the derived lifecycle critical-path breakdown."""
        doc = m.tracestore.get(r.groups[0])
        if doc is None:
            raise ApiError(404, f"no trace {r.groups[0]}")
        # Log correlation: per-span structured-log counts ride the trace
        # answer (lines outside any span count under ""), so a waterfall
        # can offer "show this span's logs" without a round-trip per span.
        doc["log_counts"] = m.logstore.span_counts(r.groups[0])
        return doc

    def traces_search(r: ApiRequest):
        """GET /api/v1/traces?experiment=…&status=error&min_duration_ms=…
        &root=…&limit=… — trace summaries, newest first, plus the store's
        bounds accounting."""
        exp = r.q("experiment")
        limit = r.q("limit", "50")
        min_dur = r.q("min_duration_ms")
        try:
            # Numeric junk answers 400, same contract as metrics_query.
            traces = m.tracestore.search(
                experiment=int(exp) if exp is not None else None,
                status=r.q("status"),
                root=r.q("root"),
                min_duration_ms=(
                    float(min_dur) if min_dur is not None else None
                ),
                limit=int(limit),
            )
        except (TypeError, ValueError) as e:
            raise ApiError(400, str(e))
        return {"traces": traces, "stats": m.tracestore.stats()}

    # -- profiling plane (master/profilestore.py): the master's own
    # -- flamegraph store, fed by the common/profiling.py sampler in every
    # -- process ------------------------------------------------------------
    def profiles_ingest(r: ApiRequest):
        """POST /api/v1/profiles/ingest — batch window ingest from
        samplers. Never 4xxes a well-formed envelope: per-window problems
        are dropped and counted inside the store (a shipper must not
        retry-loop over one bad window)."""
        from determined_tpu.common import faults

        if not m._profiling_cfg["enabled"]:
            # Same contract as the disabled trace plane: 404 is a
            # non-retryable status for the shipper — the batch is counted
            # dropped once, no retry churn filling a disabled store.
            raise ApiError(404, "profiling plane disabled (profiling.enabled)")
        faults.inject("master.profile_ingest")
        windows = r.body.get("windows")
        if windows is None:
            windows = []
        if not isinstance(windows, list):
            raise ApiError(400, "windows must be a list of profile windows")
        return {"stored": m.profilestore.ingest(windows)}

    def _profile_filters(r: ApiRequest) -> Dict[str, Any]:
        try:
            since = r.q("since")
            until = r.q("until")
            return {
                "target": r.q("target"),
                "span": r.q("span"),
                "phase": r.q("phase"),
                "since": float(since) if since is not None else None,
                "until": float(until) if until is not None else None,
            }
        except (TypeError, ValueError) as e:
            raise ApiError(400, str(e))

    def profiles_flame(r: ApiRequest):
        """GET /api/v1/profiles/flame?target=…&span=…&phase=…&since=…
        &until=… — merged folded stacks over the slice (flamegraph wire
        format), plus the store's bounds accounting."""
        flt = _profile_filters(r)
        doc = m.profilestore.flame(
            limit=int(r.q("limit", "5000")), **flt
        )
        doc["stats"] = m.profilestore.stats()
        return doc

    def profiles_top(r: ApiRequest):
        """GET /api/v1/profiles/top?n=… — top-N frames by self time."""
        flt = _profile_filters(r)
        doc = m.profilestore.top(n=int(r.q("n", "20")), **flt)
        doc["stats"] = m.profilestore.stats()
        return doc

    def profiles_diff(r: ApiRequest):
        """GET /api/v1/profiles/diff?a_since=…&a_until=…&b_since=…
        &b_until=… — window-vs-window folded-stack delta."""
        try:
            ranges = {
                k: (float(v) if (v := r.q(k)) is not None else None)
                for k in ("a_since", "a_until", "b_since", "b_until")
            }
        except (TypeError, ValueError) as e:
            raise ApiError(400, str(e))
        return m.profilestore.diff(
            target=r.q("target"), span=r.q("span"), phase=r.q("phase"),
            limit=int(r.q("limit", "200")), **ranges,
        )

    def profiles_capture(r: ApiRequest):
        """POST /api/v1/profiles/capture — operator-requested bounded XLA
        trace on a running trial ({"trial_id": N}) or serving/command
        task ({"task_id": "…"}); delivered as a directive on the target's
        next progress-beat / preemption poll."""
        trial_id = r.body.get("trial_id")
        task_id = r.body.get("task_id")
        steps = r.body.get("steps", 3)
        if (trial_id is None) == (task_id is None):
            raise ApiError(400, "exactly one of trial_id / task_id required")
        try:
            steps = int(steps)
        except (TypeError, ValueError):
            raise ApiError(400, "steps must be an integer")
        if trial_id is not None:
            exp_of_trial(int(trial_id))  # 404s unknown trials
            cap = m.profilestore.request_capture("trial", int(trial_id),
                                                 steps=steps)
        else:
            if str(task_id) not in m._commands:
                raise ApiError(404, f"no such task {task_id}")
            cap = m.profilestore.request_capture("task", str(task_id),
                                                 steps=steps)
        return cap

    def profiles_captures(r: ApiRequest):
        return {"captures": m.profilestore.list_captures()}

    def profiles_capture_complete(r: ApiRequest):
        """POST /api/v1/profiles/captures/<id>/complete — the captured
        process registers the uploaded artifact link (or the failure)."""
        doc = m.profilestore.complete_capture(
            r.groups[0],
            artifact=str(r.body.get("artifact", "") or ""),
            error=str(r.body.get("error", "") or ""),
        )
        if doc is None:
            raise ApiError(404, f"no capture {r.groups[0]}")
        return doc

    # -- log plane (master/logstore.py): the master's own structured-log
    # -- store, fed by the common/logship.py handler in every process --------
    def logs_ingest(r: ApiRequest):
        """POST /api/v1/logs/ingest — batch line ingest from shippers.
        Never 4xxes a well-formed envelope: per-line problems are dropped
        and counted inside the store (a shipper must not retry-loop over
        one bad line)."""
        from determined_tpu.common import faults

        if not m._logs_cfg["enabled"]:
            # Same contract as the disabled trace/profiling planes: 404
            # is a non-retryable status for the shipper.
            raise ApiError(404, "log plane disabled (logs.enabled)")
        faults.inject("master.log_ingest")
        lines = r.body.get("lines")
        if lines is None:
            lines = []
        if not isinstance(lines, list):
            raise ApiError(400, "lines must be a list of structured lines")
        return {"stored": m.logstore.ingest(lines)}

    def _log_selectors(r: ApiRequest) -> Dict[str, Any]:
        """Shared selector surface of query and tail: label matchers
        (?match=k=v, repeatable; ?target= is shorthand for the identity
        label), trace/span ids, a level FLOOR, substring, time range."""
        labels: Dict[str, str] = {}
        for raw in r.query.get("match", []):
            key, sep, value = raw.partition("=")
            if not sep or not key:
                raise ApiError(400, f"match must be key=value, got {raw!r}")
            labels[key] = value
        target = r.q("target")
        if target:
            labels["target"] = target
        return {
            "labels": labels or None,
            "trace": r.q("trace"),
            "span": r.q("span"),
            "level": r.q("level"),
            "substring": r.q("search") or None,
            "since": _q_num(r.q("since"), float, "since"),
            "until": _q_num(r.q("until"), float, "until"),
        }

    def logs_query(r: ApiRequest):
        """GET /api/v1/logs/query?trace=…&match=k=v&level=…&search=…
        &since=…&until=…&limit=… — cluster-wide selector search, no
        task_id required; newest `limit` matches in id order, plus the
        store's bounds accounting."""
        sel = _log_selectors(r)
        limit = _q_num(r.q("limit"), int, "limit")
        # ?after=N flips to cursor semantics (FIRST limit past the id,
        # for poll-style follows like `dtpu logs tail`); without it the
        # LAST limit (a debugger wants recency).
        after = _q_num(r.q("after"), int, "after")
        logs = m.logstore.query(
            limit=500 if limit is None else limit, after_id=after, **sel
        )
        return {"logs": logs, "stats": m.logstore.stats()}

    def logs_tail(r: ApiRequest):
        """GET /api/v1/logs/tail?…same selectors…&after=N — SSE live
        follow over the same selector surface as /logs/query (the WebUI
        log pane; `dtpu logs tail`)."""
        sel = _log_selectors(r)
        start = _sse_start(r)

        def fetch(cursor):
            cursor = start if cursor is None else cursor
            rows = m.logstore.query(after_id=cursor, limit=500, **sel)
            if rows:
                cursor = rows[-1]["id"]
            return rows, cursor

        raise _EventStream(_sse_follow(fetch))

    R = lambda method, pat, h: (method, re.compile(f"^{pat}$"), h)  # noqa: E731
    return [
        R("POST", r"/api/v1/trials/(\d+)/metrics", post_metrics),
        R("GET", r"/api/v1/trials/(\d+)/metrics", get_metrics),
        R("GET", r"/api/v1/trials/(\d+)/metrics/stream",
          stream_trial_metrics),
        R("POST", r"/api/v1/trials/(\d+)/progress", post_progress),
        R("POST", r"/api/v1/trials/(\d+)/status", post_status),
        R("GET", r"/api/v1/trials/(\d+)/best_validation", best_validation),
        R("GET", r"/api/v1/trials/(\d+)/searcher/operation", searcher_operation),
        R("POST", r"/api/v1/trials/(\d+)/searcher/completed", searcher_completed),
        R("POST", r"/api/v1/trials/(\d+)/searcher/progress", searcher_progress),
        R("GET", r"/api/v1/trials/(\d+)/checkpoints", trial_checkpoints),
        R("GET", r"/api/v1/trials/(\d+)", get_trial),
        R("POST", r"/api/v1/checkpoints", post_checkpoint),
        R("GET", r"/api/v1/checkpoints/([0-9a-f-]+)", get_checkpoint),
        R("DELETE", r"/api/v1/checkpoints/([0-9a-f-]+)", ckpt_delete),
        R("GET", r"/api/v1/allocations/([\w.\-]+)/signals/preemption", preemption_signal),
        R("POST", r"/api/v1/allocations/([\w.\-]+)/signals/ack_preemption", ack_preemption),
        R("POST", r"/api/v1/allocations/([\w.\-]+)/signals/preemption_from_task", preempt_from_task),
        R("POST", r"/api/v1/allocations/([\w.\-]+)/proxy", register_proxy),
        R("GET", r"/api/v1/proxies", list_proxies),
        R("POST", r"/api/v1/allocations/([\w.\-]+)/progress", alloc_progress),
        R("POST", r"/api/v1/allocations/([\w.\-]+)/rendezvous", rendezvous_arrive),
        R("GET", r"/api/v1/allocations/([\w.\-]+)/rendezvous", rendezvous_info),
        R("POST", r"/api/v1/allocations/([\w.\-]+)/allgather", allgather),
        R("POST", r"/api/v1/task_logs", post_task_logs),
        R("GET", r"/api/v1/task_logs", get_task_logs),
        R("GET", r"/api/v1/task_logs/stream", stream_task_logs),
        R("GET", r"/api/v1/task_logs/search", search_task_logs),
        R("POST", r"/api/v1/templates", set_template),
        R("GET", r"/api/v1/templates", list_templates),
        R("GET", r"/api/v1/templates/([\w.\-]+)", get_template),
        R("DELETE", r"/api/v1/templates/([\w.\-]+)", delete_template),
        R("GET", r"/api/v1/audit", list_audit),
        R("POST", r"/api/v1/agents", register_agent),
        R("GET", r"/api/v1/agents/([\w.\-]+)/actions", agent_actions),
        R("POST", r"/api/v1/agents/([\w.\-]+)/events", agent_events),
        R("POST", r"/api/v1/agents/([\w.\-]+)/enable", agent_enable),
        R("POST", r"/api/v1/agents/([\w.\-]+)/disable", agent_disable),
        R("POST", r"/api/v1/agents/([\w.\-]+)/slots/(\d+)/(enable|disable)",
          slot_state),
        R("GET", r"/api/v1/agents", list_agents),
        R("GET", r"/api/v1/queues", queue_list),
        R("POST", r"/api/v1/queues/move", queue_move),
        R("POST", r"/api/v1/files", upload_file),
        R("GET", r"/api/v1/files/([0-9a-f]+)", download_file),
        R("POST", r"/api/v1/commands", create_command),
        R("GET", r"/api/v1/commands", list_commands),
        R("POST", r"/api/v1/commands/([\w.\-]+)/kill", kill_command),
        R("POST", r"/api/v1/generate", fleet_generate),
        R("GET", r"/api/v1/stats", cluster_stats),
        R("POST", r"/api/v1/models", create_model),
        R("GET", r"/api/v1/models", list_models),
        R("GET", r"/api/v1/models/([\w.\-]+)/versions", list_model_versions),
        R("POST", r"/api/v1/models/([\w.\-]+)/versions", create_model_version),
        R("GET", r"/api/v1/models/([\w.\-]+)", get_model),
        R("DELETE", r"/api/v1/models/([\w.\-]+)/versions/(\d+)",
          delete_model_version),
        R("DELETE", r"/api/v1/models/([\w.\-]+)", delete_model),
        R("POST", r"/api/v1/workspaces", create_workspace),
        R("GET", r"/api/v1/workspaces", list_workspaces),
        R("POST", r"/api/v1/projects", create_project),
        R("GET", r"/api/v1/projects", list_projects),
        R("POST", r"/api/v1/webhooks", create_webhook),
        R("GET", r"/api/v1/webhooks", list_webhooks),
        R("DELETE", r"/api/v1/webhooks/(\d+)", delete_webhook),
        R("POST", r"/api/v1/experiments", create_experiment),
        R("GET", r"/api/v1/experiments", list_experiments),
        R("GET", r"/api/v1/experiments/(\d+)", get_experiment),
        R("PATCH", r"/api/v1/experiments/(\d+)", exp_patch),
        R("PATCH", r"/api/v1/experiments/(\d+)/resources", exp_resources),
        R("DELETE", r"/api/v1/experiments/(\d+)", exp_delete),
        R("POST", r"/api/v1/experiments/(\d+)/(pause|activate|cancel|kill)", exp_action),
        R("POST", r"/api/v1/experiments/(\d+)/(archive|unarchive)", exp_archive),
        R("POST", r"/api/v1/experiments/(\d+)/fork", exp_fork),
        R("POST", r"/api/v1/experiments/(\d+)/continue", exp_continue),
        R("POST", r"/api/v1/experiments/(\d+)/move", exp_move),
        R("POST", r"/api/v1/trials/(\d+)/kill", trial_kill),
        R("GET", r"/api/v1/resource-pools", list_resource_pools),
        R("GET", r"/api/v1/experiments/(\d+)/trials", list_trials),
        R("GET", r"/api/v1/experiments/(\d+)/searcher/events", searcher_events),
        R("POST", r"/api/v1/experiments/(\d+)/searcher/operations", post_searcher_ops),
        R("GET", r"/api/v1/master", master_info),
        R("GET", r"/api/v1/master/logs", master_logs),
        R("GET", r"/api/v1/users", list_users),
        R("POST", r"/api/v1/users", create_user),
        R("POST", r"/api/v1/users/([\w.@+\-]+)/password", set_user_password),
        R("PATCH", r"/api/v1/users/([\w.@+\-]+)", patch_user),
        R("POST", r"/api/v1/auth/password", change_own_password),
        R("POST", r"/api/v1/users/([\w.@+\-]+)/role", set_user_role),
        R("GET", r"/api/v1/groups", list_groups),
        R("POST", r"/api/v1/groups", upsert_group),
        R("POST", r"/api/v1/groups/([\w.\-]+)/members", modify_group),
        R("DELETE", r"/api/v1/groups/([\w.\-]+)", delete_group),
        R("POST", r"/api/v1/auth/login", auth_login),
        R("POST", r"/api/v1/auth/logout", auth_logout),
        R("GET", r"/api/v1/metrics/query", metrics_query),
        R("GET", r"/api/v1/metrics/series", metrics_series),
        R("GET", r"/api/v1/alerts", list_alerts),
        R("POST", r"/api/v1/traces/ingest", traces_ingest),
        R("GET", r"/api/v1/traces/([0-9a-f]+)", traces_get),
        R("GET", r"/api/v1/traces", traces_search),
        R("POST", r"/api/v1/logs/ingest", logs_ingest),
        R("GET", r"/api/v1/logs/query", logs_query),
        R("GET", r"/api/v1/logs/tail", logs_tail),
        R("POST", r"/api/v1/profiles/ingest", profiles_ingest),
        R("GET", r"/api/v1/profiles/flame", profiles_flame),
        R("GET", r"/api/v1/profiles/top", profiles_top),
        R("GET", r"/api/v1/profiles/diff", profiles_diff),
        R("POST", r"/api/v1/profiles/capture", profiles_capture),
        R("GET", r"/api/v1/profiles/captures", profiles_captures),
        R("POST", r"/api/v1/profiles/captures/([\w\-]+)/complete",
          profiles_capture_complete),
        R("GET", r"/prom/metrics", prometheus_metrics),
        R("GET", r"/metrics", prometheus_metrics),
        R("GET", r"/(?:ui)?", webui_page),
    ]


class ApiServer:
    """HTTP(S) front end; `serve_forever` in a daemon thread via start().

    `tls=(cert_path, key_path)` serves HTTPS (ref: master TLS via
    `internal/proxy/tls.go` config); the upgrade tunnels (shells, Jupyter
    WS) ride the same listener, so TLS terminates at the master and
    master→task hops stay on the private agent network.
    """

    def __init__(
        self,
        master: Master,
        host: str = "127.0.0.1",
        port: int = 0,
        tls: Optional[tuple] = None,
    ) -> None:
        routes = build_routes(master)
        denied_limiter = _DeniedAuditLimiter()
        idempotency = _IdempotencyCache()

        class _Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"
            # TCP_NODELAY: without it, small request/response pairs on a
            # keep-alive connection stall on the Nagle × delayed-ACK
            # interaction — measured 44 ms → 1.5 ms per API call (the
            # trace-plane bench rung surfaced it; every control-plane
            # round-trip was paying the same tax).
            disable_nagle_algorithm = True

            def log_message(self, fmt: str, *args: Any) -> None:
                logger.debug("http: " + fmt, *args)

            AUTH_EXEMPT = ("/api/v1/auth/login", "/", "/ui", "/metrics",
                           "/prom/metrics")

            def _auth_token(self, parsed, proxy: bool = False) -> Optional[str]:
                """Bearer header, else cookie, else query param (browser UIs
                and raw upgrade sockets can't always set headers).

                Proxy routes accept only `dtpu_token=` from the query and
                ignore `token=`: `token` belongs to the proxied service
                (Jupyter authenticates with exactly that name), so consuming
                it as master auth would both misread Jupyter tokens and
                invite session tokens into URLs we forward to task code."""
                header = self.headers.get("Authorization", "")
                if header.startswith("Bearer "):
                    return header[7:]
                cookie = self.headers.get("Cookie", "")
                for part in cookie.split(";"):
                    name, _, value = part.strip().partition("=")
                    if name == "dtpu_token" and value:
                        return value
                q = parse_qs(parsed.query)
                got = q.get("dtpu_token") or (None if proxy else q.get("token"))
                return got[0] if got else None

            def _dispatch(self, method: str) -> None:
                if getattr(self.server, "stopping", False):
                    # A stopped server's lingering keep-alive handler
                    # threads must not serve — and above all not MUTATE —
                    # from stale state across an in-process master restart
                    # (a real crash resets connections at the OS level; an
                    # op_completed absorbed by the zombie would be lost to
                    # the successor). 503 is retryable: the client's next
                    # attempt lands on the new master.
                    try:
                        self._send(503, {"error": "master stopping"},
                                   close=True)
                    except (BrokenPipeError, ConnectionResetError, OSError):
                        pass
                    return
                parsed = urlparse(self.path)
                is_proxy = parsed.path.startswith("/proxy/")
                token = self._auth_token(parsed, proxy=is_proxy)
                if is_proxy:
                    # Raw pass-through to a task service. Same auth gate as
                    # the API (the reference authenticates proxy traffic via
                    # session cookies; we accept cookie/query tokens too).
                    # User principals only: a leaked task/agent token must
                    # not reach proxied interactive services (notebooks are
                    # a code-execution surface).
                    if master.auth.enabled:
                        # close=True throughout: these reject before the
                        # request body is consumed (the proxy streams it
                        # later), so keeping the connection would desync it.
                        principal = master.auth.validate(token)
                        if principal is None:
                            self._send(
                                401, {"error": "authentication required"},
                                close=True,
                            )
                            return
                        if principal.startswith(("task:", "agent:")):
                            self._send(403, {
                                "error": "task/agent tokens may not access "
                                         "proxied services"
                            }, close=True)
                            return
                        # Proxied services ARE code execution (notebook
                        # kernels, PTY shells): the viewer role's read-only
                        # contract must hold here too, not just on /api/v1.
                        role = master.auth.effective_role(principal)
                        if role not in ("editor", "admin"):
                            self._send(403, {
                                "error": f"role {role} may not access "
                                         "proxied services"
                            }, close=True)
                            return
                    connection = self.headers.get("Connection", "")
                    if "upgrade" in connection.lower():
                        self._proxy_upgrade(method, parsed)
                        return
                    self._proxy(method, parsed)
                    return
                def audit_denied(who: str, status: int) -> None:
                    # Denied mutations are what an audit trail exists for
                    # (probing, stolen tokens, privilege testing) — record
                    # them like the in-handler audit does, same machine-
                    # surface exclusions — BUT rate-limited: an
                    # unauthenticated attacker hammering 401s must not be
                    # able to grow the audit table (and fill the master's
                    # disk) at the batched writer's full ingest speed.
                    if (
                        method in ("POST", "PATCH", "DELETE")
                        and not TASK_TOKEN_ROUTES.match(parsed.path)
                        and not AGENT_TOKEN_ROUTES.match(parsed.path)
                        and denied_limiter.allowed()
                    ):
                        try:
                            master.db.add_audit(
                                who, method, parsed.path, status,
                                self.client_address[0],
                            )
                        except Exception:  # noqa: BLE001
                            logger.exception("audit write failed")

                principal: Optional[str] = None
                if master.auth.enabled and parsed.path not in self.AUTH_EXEMPT:
                    # Auth rejections happen BEFORE the body read below —
                    # responding while the declared body sits unread would
                    # desync this keep-alive connection (the next request
                    # would parse body bytes as its request line), so these
                    # _sends close like the 413 path does.
                    principal = master.auth.validate(token)
                    if principal is None:
                        audit_denied(
                            "invalid-token" if token else "anonymous", 401
                        )
                        self._send(401, {"error": "authentication required"},
                                   close=True)
                        return
                    if not principal_allowed(principal, parsed.path):
                        audit_denied(principal, 403)
                        self._send(403, {
                            "error": f"{principal} may not access {parsed.path}"
                        }, close=True)
                        return
                    if not principal.startswith(("task:", "agent:")):
                        role = master.auth.effective_role(principal)
                        if not user_allowed(role, method, parsed.path):
                            audit_denied(principal, 403)
                            self._send(403, {
                                "error": f"role {role} may not {method} "
                                         f"{parsed.path}"
                            }, close=True)
                            return
                body: Dict[str, Any] = {}
                raw: bytes = b""
                length = int(self.headers.get("Content-Length") or 0)
                if length > MAX_BODY_BYTES:
                    # Reject BEFORE reading: buffering an attacker-chosen
                    # Content-Length would OOM the master. The unread body
                    # would desync this keep-alive connection — close it.
                    self._send(413, {"error": "request body too large"},
                               close=True)
                    return
                if length:
                    raw = self.rfile.read(length)
                    ctype = self.headers.get("Content-Type", "application/json")
                    if "json" in ctype:
                        try:
                            body = json.loads(raw or b"{}")
                        except json.JSONDecodeError:
                            self._send(400, {"error": "bad json"})
                            return
                if principal is not None and principal.startswith("task:"):
                    err = task_identity_violation(
                        master, principal, method, parsed.path, body
                    )
                    if err:
                        self._send(403, {"error": err})
                        return
                # Idempotency replay (after auth: a replayed response must
                # never leak a mutation result past the token checks that
                # guarded the original). The cache key binds the client id
                # to (method, path, principal): a reused tracing id on a
                # DIFFERENT mutation — or another principal replaying a
                # leaked id — must execute, not replay someone else's
                # cached response.
                rid = (
                    self.headers.get("X-Request-Id")
                    if method in ("POST", "PATCH", "DELETE")
                    else None
                )
                if rid:
                    import hashlib

                    body_tag = hashlib.sha256(raw).hexdigest()[:16]
                    idem_key = (
                        f"{rid}|{method}|{parsed.path}|{principal or ''}"
                        f"|{body_tag}"
                    )
                else:
                    idem_key = None
                if idem_key:
                    cached = idempotency.get(idem_key)
                    if cached is not None:
                        self._send(200, cached)
                        return
                for m_, pat, handler in routes:
                    if m_ != method:
                        continue
                    match = pat.match(parsed.path)
                    if match:
                        # One span per API request (the gin-middleware
                        # analog of the reference's otel wiring); the route
                        # PATTERN names the span, not the raw path —
                        # bounded-cardinality names are the OTel norm. An
                        # incoming W3C `traceparent` (harness Session, CLI,
                        # agent) becomes the span's remote parent, so the
                        # caller's trace continues through the master.
                        span = master.tracer.start_span(
                            f"http {method} {pat.pattern}",
                            {"http.method": method, "http.target": parsed.path},
                            parent=trace_mod.parse_traceparent(
                                self.headers.get("traceparent")
                            ),
                        )
                        t_start = time.monotonic()
                        finished = False

                        def finish(status: int) -> None:
                            # ONE latency/status observation + span end per
                            # request, wherever it completes (success, error
                            # branch, or SSE stream start). Lives on the
                            # shared dispatch path, so every route is
                            # observed by construction
                            # (tests/test_metrics_discipline.py).
                            nonlocal finished
                            if finished:
                                return
                            finished = True
                            span.set_attribute("http.status_code", status)
                            master.tracer.end_span(span)
                            # The latency observation carries the request
                            # span's trace id as its exemplar: the p99
                            # answer links to the stored slow trace. Only
                            # spans the StoreExporter will actually keep
                            # (propagated parent, errored, or slow) get
                            # one — an exemplar must never 404.
                            dur = time.monotonic() - t_start
                            linkable = bool(span.trace_id) and (
                                bool(span.parent_span_id)
                                or span.status == "ERROR"
                                or dur * 1e3 >= trace_mod._env_float(
                                    trace_mod.TRACE_SLOW_MS_ENV,
                                    trace_mod.DEFAULT_SLOW_MS,
                                )
                            )
                            API_LATENCY.labels(method, pat.pattern).observe(
                                dur,
                                trace_id=span.trace_id if linkable else None,
                            )
                            API_REQUESTS.labels(
                                method, pat.pattern, str(status)
                            ).inc()

                        status_code = 200
                        admitted_plane = None
                        try:
                            # activate(): master-internal spans started by
                            # the handler parent under the request span.
                            with master.tracer.activate(span):
                                # Two-lane overload control: bulk telemetry
                                # ingest passes per-plane admission; a
                                # saturated plane answers 429 + Retry-After
                                # HERE, before the handler runs, so control
                                # routes (not in the map) never wait behind
                                # a telemetry flood. Raising inside the
                                # span keeps finish() observing the 429
                                # into dtpu_api_requests_total.
                                plane = BULK_INGEST_PLANES.get(
                                    (method, pat.pattern)
                                )
                                if plane is not None:
                                    if not master.admission.try_acquire(
                                        plane
                                    ):
                                        ra = master.admission.retry_after_s
                                        raise ApiError(
                                            429,
                                            f"{plane} ingest saturated",
                                            payload={
                                                "plane": plane,
                                                "retry_after_s": ra,
                                            },
                                            headers={
                                                "Retry-After": "%g" % ra
                                            },
                                        )
                                    admitted_plane = plane
                                result = handler(
                                    ApiRequest(
                                        match.groups(), body,
                                        parse_qs(parsed.query), token=token,
                                        client_ip=self.client_address[0],
                                        raw=raw,
                                        headers=dict(self.headers.items()),
                                    )
                                )
                            if idem_key:
                                idempotency.put(
                                    idem_key,
                                    result if result is not None else {},
                                )
                            self._send(200, result if result is not None else {})
                        except _PlainText as pt:
                            data = (
                                pt.text.encode()
                                if isinstance(pt.text, str)
                                else pt.text
                            )
                            self.send_response(200)
                            self.send_header("Content-Type", pt.content_type)
                            self.send_header("Content-Length", str(len(data)))
                            self.end_headers()
                            self.wfile.write(data)
                        except _EventStream as es:
                            # SSE: one response, chunk per event, connection
                            # closed at generator exhaustion (no keep-alive
                            # reuse — the stream owns the socket). Observed
                            # at stream START: a follow stream's lifetime is
                            # client-chosen and unbounded — recording it as
                            # "latency" would poison the histogram.
                            span.set_attribute("http.stream", True)
                            finish(200)
                            self.send_response(200)
                            self.send_header(
                                "Content-Type", "text/event-stream"
                            )
                            self.send_header("Cache-Control", "no-cache")
                            self.send_header("Connection", "close")
                            self.close_connection = True
                            self.end_headers()
                            try:
                                for item in es.gen:
                                    if getattr(self.server, "stopping", False):
                                        break
                                    if item is None:
                                        self.wfile.write(b": keepalive\n\n")
                                    else:
                                        ev_id, payload = item
                                        # id: → Last-Event-ID on reconnect,
                                        # so a dropped stream resumes at
                                        # its cursor instead of replaying.
                                        self.wfile.write(
                                            f"id: {ev_id}\ndata: "
                                            f"{payload}\n\n".encode()
                                        )
                                    self.wfile.flush()
                            except (BrokenPipeError, ConnectionResetError,
                                    OSError):
                                pass  # viewer closed the tab
                            finally:
                                es.gen.close()
                        except _RawStream as rs:
                            # Verbatim backend pass-through (router
                            # generate): same unbuffered relay contract
                            # as _proxy — chunks reach the client as the
                            # replica produces them, observed at stream
                            # start like every open-ended response.
                            span.set_attribute("http.stream", True)
                            finish(rs.status)
                            expected = next(
                                (int(v) for k, v in rs.headers.items()
                                 if k.lower() == "content-length"
                                 and v.isdigit()),
                                None,
                            )
                            sent = 0
                            try:
                                self.send_response(rs.status)
                                for k, v in rs.headers.items():
                                    self.send_header(k, v)
                                if expected is None:
                                    self.send_header("Connection", "close")
                                    self.close_connection = True
                                self.end_headers()
                                for chunk in rs.chunks:
                                    self.wfile.write(chunk)
                                    self.wfile.flush()
                                    sent += len(chunk)
                            except (BrokenPipeError, ConnectionResetError,
                                    OSError):
                                pass  # client went away mid-stream
                            finally:
                                if expected is not None and sent != expected:
                                    # Advertised length undelivered:
                                    # reuse would desync — tear down.
                                    self.close_connection = True
                                close = getattr(rs.chunks, "close", None)
                                if close is not None:
                                    close()
                        except (BrokenPipeError, ConnectionResetError):
                            # Long-poll client went away (e.g. task exited
                            # mid-response); nothing to answer.
                            status_code = 0
                        except ApiError as e:
                            status_code = e.status
                            if e.status >= 500:
                                span.status = "ERROR"
                            self._send(
                                e.status, {"error": str(e), **e.payload},
                                headers=e.headers or None,
                            )
                        except KeyError as e:
                            status_code = 404
                            self._send(404, {"error": f"not found: {e}"})
                        except Exception as e:  # noqa: BLE001
                            status_code = 500
                            span.status = "ERROR"
                            logger.exception("handler error %s %s", method, parsed.path)
                            self._send(500, {"error": str(e)})
                        finally:
                            if admitted_plane is not None:
                                master.admission.release(admitted_plane)
                            finish(status_code)
                            # Append-only audit of every mutating API call
                            # (ref internal/audit.go): who, what, outcome.
                            # Machine traffic is churn, not user action —
                            # excluded by principal class AND by surface
                            # (on auth-disabled clusters every harness POST
                            # would otherwise flood the trail as
                            # "anonymous").
                            if (
                                method in ("POST", "PATCH", "DELETE")
                                and not (principal or "").startswith(
                                    ("task:", "agent:")
                                )
                                and not TASK_TOKEN_ROUTES.match(parsed.path)
                                and not AGENT_TOKEN_ROUTES.match(parsed.path)
                            ):
                                try:
                                    master.db.add_audit(
                                        principal or "anonymous", method,
                                        parsed.path, status_code,
                                        self.client_address[0],
                                    )
                                except Exception:  # noqa: BLE001
                                    logger.exception("audit write failed")
                        return
                self._send(404, {"error": f"no route {method} {parsed.path}"})

            def _proxy(self, method: str, parsed) -> None:
                parts = parsed.path.split("/", 3)  # '', 'proxy', task_id, rest
                task_id = parts[2] if len(parts) > 2 else ""
                rest = "/" + (parts[3] if len(parts) > 3 else "")
                length = int(self.headers.get("Content-Length") or 0)
                if length > MAX_BODY_BYTES:
                    # Same pre-read cap as _dispatch: an attacker-supplied
                    # Content-Length must not buffer into master memory.
                    self._send(413, {"error": "request body too large"},
                               close=True)
                    return
                body = self.rfile.read(length) if length else b""
                status, headers, chunks = master.proxy.forward_stream(
                    task_id, method, rest, parsed.query,
                    dict(self.headers), body,
                )
                # Pass-through is UNBUFFERED: chunks reach the client as
                # the task service produces them (an SSE token stream's
                # TTFT must survive the proxy). With a backend
                # Content-Length the connection stays reusable; without
                # one the response is close-delimited.
                expected = next(
                    (int(v) for k, v in headers.items()
                     if k.lower() == "content-length" and v.isdigit()),
                    None,
                )
                sent = 0
                try:
                    self.send_response(status)
                    for k, v in headers.items():
                        self.send_header(k, v)
                    if expected is None:
                        self.send_header("Connection", "close")
                        self.close_connection = True
                    self.end_headers()
                    for chunk in chunks:
                        self.wfile.write(chunk)
                        self.wfile.flush()
                        sent += len(chunk)
                except (BrokenPipeError, ConnectionResetError):
                    pass
                finally:
                    if expected is not None and sent != expected:
                        # The backend died mid-body: we advertised
                        # Content-Length but delivered less. Reusing the
                        # keep-alive connection would hand the next
                        # request misaligned bytes — tear it down (the
                        # client sees a truncated response, as it should).
                        self.close_connection = True
                    close = getattr(chunks, "close", None)
                    if close is not None:
                        close()

            def _proxy_upgrade(self, method: str, parsed) -> None:
                """WebSocket (or any Upgrade) pass-through: hand the raw
                connection to the proxy's byte tunnel (ref: proxy/ws.go
                hijacks the conn and io.Copies both ways)."""
                parts = parsed.path.split("/", 3)
                task_id = parts[2] if len(parts) > 2 else ""
                rest = "/" + (parts[3] if len(parts) > 3 else "")
                err = master.proxy.tunnel_upgrade(
                    task_id, method, rest, parsed.query,
                    dict(self.headers), self.connection, self.rfile,
                )
                if err is not None:
                    self._send(502, {"error": err}, close=True)
                    return
                # The connection carried opaque tunnel bytes; it cannot be
                # reused for HTTP.
                self.close_connection = True

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
                    # Rejected without reading the declared body: the next
                    # keep-alive request would parse body bytes as a
                    # request line. Tear the connection down.
                    self.send_header("Connection", "close")
                    self.close_connection = True
                if getattr(self.server, "stopping", False):
                    # Keep-alive connections would otherwise let lingering
                    # handler threads keep serving clients from a stopped
                    # server's state (in-process restarts; a real crash
                    # resets connections at the OS level).
                    self.send_header("Connection", "close")
                    self.close_connection = True
                self.end_headers()
                self.wfile.write(data)

            def do_GET(self) -> None:  # noqa: N802
                self._dispatch("GET")

            def do_POST(self) -> None:  # noqa: N802
                self._dispatch("POST")

            def do_PATCH(self) -> None:  # noqa: N802
                self._dispatch("PATCH")

            def do_DELETE(self) -> None:  # noqa: N802
                self._dispatch("DELETE")

        ssl_ctx = None
        if tls is not None:
            from determined_tpu.common.tls import server_context

            ssl_ctx = server_context(tls[0], tls[1])

        class _Server(ThreadingHTTPServer):
            def get_request(self):  # noqa: ANN201
                sock, addr = super().get_request()
                if ssl_ctx is not None:
                    # do_handshake_on_connect=False: the handshake then
                    # happens at the handler thread's first read, so a
                    # stalled client can't block the accept loop.
                    sock = ssl_ctx.wrap_socket(
                        sock, server_side=True, do_handshake_on_connect=False
                    )
                return sock, addr

            def handle_error(self, request, client_address):  # noqa: ANN001
                import sys

                # sys.exception() is 3.11+; exc_info works everywhere.
                exc = sys.exc_info()[1]
                if isinstance(exc, (BrokenPipeError, ConnectionResetError)):
                    return  # client hung up mid-request (task exit); routine
                import ssl as ssl_mod

                if isinstance(exc, ssl_mod.SSLError) and ssl_ctx is not None:
                    # Plaintext/bad-TLS probes on an HTTPS port are routine
                    # noise; real handler OSErrors (ENOSPC, EMFILE) must
                    # still surface.
                    return
                super().handle_error(request, client_address)

        self._httpd = _Server((host, port), _Handler)
        self._httpd.daemon_threads = True
        self.port = self._httpd.server_address[1]
        scheme = "https" if ssl_ctx is not None else "http"
        self.url = f"{scheme}://{host}:{self.port}"
        self._thread: Optional[threading.Thread] = None

    def start(self) -> None:
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="api-server", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self._httpd.stopping = True
        self._httpd.shutdown()
        self._httpd.server_close()
