"""Agent daemon: runs on each TPU host, executes tasks for the master.

Rebuild of `agent/internal/agent.go:41,86` + `containers/manager.go:35` with
the container runtime swapped for process supervision: on a TPU VM the unit
of execution is a process group owning the host's chips (there is no
nvidia-docker equivalent in the TPU runtime; the harness process grabs the
chips via libtpu). START actions spawn `determined_tpu.exec.prep_and_run`
with the DTPU_* env; exits are reported back as events; stdout/stderr is
shipped to the master's task-log store (replacing the ws ContainerLog path,
aproto/master_message.go:41).

Reattach (ref: containers/manager.go:76 + aproto/master_message.go:46-55):
a running task survives both master and agent restarts. Tasks log to FILES
in a persistent state dir (not pipes — a pipe dies with its reader), each
task has a state file (pid + start-time + shipped-log offset) and a
supervisor shim (_shim.py) that persists the exit code. On (re)registration
the agent reports its live allocations; the master answers with which were
adopted vs orphaned, and only the orphans are killed. A restarted agent
process re-adopts live pids from the state dir and resumes log shipping at
the recorded offset.
"""
from __future__ import annotations

import json
import logging
import os
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from determined_tpu.common import faults
from determined_tpu.common import logship as logship_mod
from determined_tpu.common import profiling as profiling_mod
from determined_tpu.common import trace as trace_mod
from determined_tpu.common.api_session import Session
from determined_tpu.common.metrics import REGISTRY as METRICS
from determined_tpu.common.resilience import AGENT_RETRY

logger = logging.getLogger("determined_tpu.agent")

# Agent-side observability (common/metrics.py): the same process-global
# registry the master uses — on a real TPU VM this process is alone and
# the health port serves agent series; in-process devclusters co-resident
# with a master simply share one exposition.
# Labeled by agent id: set() on an unlabeled gauge would have co-resident
# AgentDaemons (devcluster) clobbering one another's value; per-agent
# series compose under sum() instead.
AGENT_TASKS_RUNNING = METRICS.gauge(
    "dtpu_agent_tasks_running", "Task processes currently supervised.",
    labels=("agent",),
)
AGENT_TASKS_STARTED = METRICS.counter(
    "dtpu_agent_tasks_started_total", "Task processes spawned.",
)
AGENT_TASK_EXITS = METRICS.counter(
    "dtpu_agent_task_exits_total",
    "Task exits reported to the master, by outcome.",
    labels=("outcome",),
)
AGENT_LOG_LINES_SHIPPED = METRICS.counter(
    "dtpu_agent_log_lines_shipped_total",
    "Task log lines delivered to the master.",
)


class AgentMetricsServer:
    """`/metrics` (+ `/healthz`) on the agent's health port: the scrape
    surface for per-host series — Prometheus discovers TPU hosts the same
    way it discovers the master (docs/operations.md Observability)."""

    def __init__(self, host: str = "0.0.0.0", port: int = 0) -> None:
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        class _Handler(BaseHTTPRequestHandler):
            # same Nagle × delayed-ACK fix as the master's ApiServer:
            # scrape round-trips must not pay a 40 ms idle tax.
            disable_nagle_algorithm = True

            def log_message(self, fmt: str, *args: Any) -> None:
                logger.debug("metrics http: " + fmt, *args)

            def do_GET(self) -> None:  # noqa: N802
                if self.path.split("?")[0] == "/metrics":
                    # exemplars ride as comment lines (parsers skip them;
                    # the master's scrape sweep harvests them).
                    body = METRICS.render(exemplars=True).encode()
                    ctype = "text/plain; version=0.0.4"
                elif self.path.split("?")[0] == "/healthz":
                    body, ctype = b"ok\n", "text/plain"
                else:
                    self.send_response(404)
                    self.send_header("Content-Length", "0")
                    self.end_headers()
                    return
                self.send_response(200)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        self._httpd = ThreadingHTTPServer((host, port), _Handler)
        self._httpd.daemon_threads = True
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="agent-metrics",
            daemon=True,
        )
        self._thread.start()

    def stop(self) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()


class SlotDetectionError(RuntimeError):
    """The host was asked to detect its chips and could not: no accelerator
    stack to import, or one that is present but broken. The host must
    refuse to register rather than fall back to a 1-slot CPU agent — a TPU
    host whose runtime is wedged would otherwise silently join the pool
    with the wrong shape and poison gang fitting (ref:
    agent/internal/detect/detect.go:19, which likewise errors out rather
    than guessing)."""


#: Run by a short-lived child; its last stdout line is the device list.
_DETECT_SCRIPT = (
    "import json, jax; print(json.dumps(["
    "{'id': i, 'kind': d.device_kind, 'platform': d.platform, "
    "'coords': list(getattr(d, 'coords', ()) or ())} "
    "for i, d in enumerate(jax.local_devices())]))"
)


def detect_devices(spec: Any = "auto") -> List[Dict[str, Any]]:
    """Per-slot device descriptions (ref: agent/internal/detect/detect.go +
    pkg/device — there nvidia-smi/rocm rows with uuid/brand; here the TPU
    runtime's own view). An int (artificial slots, dev mode) reports that
    many synthetic "slot" devices and never touches the runtime.

    "auto" asks jax — from a child process that exits before any task
    starts. A chip belongs to one process at a time: an agent that
    initialised a backend itself would hold the host's chips, and every
    task it spawned would fail or hang waiting for them.
    """
    if spec != "auto":
        return [
            {"id": i, "kind": "slot", "platform": "cpu"}
            for i in range(int(spec))
        ]
    try:
        proc = subprocess.run(
            [sys.executable, "-c", _DETECT_SCRIPT],
            capture_output=True, text=True, timeout=300,
        )
    except (OSError, subprocess.TimeoutExpired) as e:
        raise SlotDetectionError(f"device detection did not run: {e}") from e
    if proc.returncode != 0:
        raise SlotDetectionError(
            "device detection failed (is the accelerator runtime installed "
            f"and free?): {proc.stderr.strip()[-2000:]}"
        )
    return json.loads(proc.stdout.strip().splitlines()[-1])


def detect_slots(spec: Any = "auto") -> int:
    """Slot (chip) count for this host (ref: agent/internal/detect/detect.go:19):
    an int as given, or one slot per device `detect_devices` finds."""
    return len(detect_devices(spec)) if spec == "auto" else int(spec)


def _shim_path() -> str:
    """File path of the supervisor shim. It is pure stdlib and run by
    path, not `-m`: importing this package for it would pull in the
    agent's own dependencies on every task spawn."""
    from determined_tpu.agent import _shim

    return _shim.__file__


def _proc_stat(pid: int) -> Optional[Tuple[int, str]]:
    """(starttime, state-letter) from /proc/<pid>/stat, or None if gone.

    starttime (field 22) disambiguates pid reuse across agent restarts;
    state 'Z' marks a zombie — dead for our purposes even though /proc
    still lists it."""
    try:
        with open(f"/proc/{pid}/stat", "rb") as f:
            data = f.read().decode("ascii", "replace")
        rest = data.rsplit(")", 1)[1].split()
        return int(rest[19]), rest[0]
    except (OSError, IndexError, ValueError):
        return None


class _Task:
    def __init__(
        self,
        alloc_id: str,
        task_id: str,
        *,
        pid: int,
        slots: int,
        log_path: str,
        exit_file: str,
        state_path: str,
        proc: Optional[subprocess.Popen] = None,
        offset: int = 0,
        start_time: Optional[int] = None,
        rank: Optional[int] = None,
    ) -> None:
        self.alloc_id = alloc_id
        self.task_id = task_id
        self.pid = pid
        self.slots = slots
        self.log_path = log_path
        self.exit_file = exit_file
        self.state_path = state_path
        self.proc = proc  # None when re-adopted (not our child)
        self.offset = offset  # log bytes already shipped
        self.start_time = start_time
        #: the task's DTPU_ALLOC_RANK at launch — addresses the
        #: `agent.reclaim.rank<r>` deterministic spot-reclaim drill.
        self.rank = rank
        self.done = threading.Event()  # process observed dead
        self.follower: Optional[threading.Thread] = None


class AgentDaemon:
    def __init__(
        self,
        master_url: str,
        agent_id: Optional[str] = None,
        slots: Any = "auto",
        pool: str = "default",
        python_exe: Optional[str] = None,
        token: str = "",
        state_dir: Optional[str] = None,
        metrics_port: Optional[int] = None,
    ) -> None:
        self.master_url = master_url
        self.agent_id = agent_id or socket.gethostname()
        self.devices = detect_devices(slots)
        self.slots = len(self.devices)
        self.pool = pool
        self.session = Session(master_url, token=token)
        self._token = token
        # Trace plane: this daemon's spans (agent.task_launch) ship to the
        # master's trace store — the agent has no launch env to
        # self-configure from, so it points the shipper explicitly.
        trace_mod.configure_shipper(master_url, token)
        self.python_exe = python_exe or sys.executable
        # State dir is the reattach anchor: task state files, log files and
        # exit files live here. An ephemeral default still gives master-
        # restart survival (same agent process); agent-restart survival
        # needs a stable --state-dir, as on a real TPU VM.
        self._ephemeral_state = state_dir is None
        self.state_dir = state_dir or tempfile.mkdtemp(
            prefix=f"dtpu-agent-{self.agent_id}-"
        )
        os.makedirs(self.state_dir, exist_ok=True)
        self._tasks: Dict[str, _Task] = {}
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._dead = False       # die(): suppress exit reports (abrupt loss)
        self._detached = False   # detach(): agent "crashed", tasks live on
        #: exits observed while the master was unreachable (or while this
        #: agent was down): reported after the next successful registration.
        self._pending_exits: List[Tuple[_Task, Optional[int]]] = []
        #: health-port scrape surface (None = disabled; 0 = ephemeral port,
        #: the bound port lands in .metrics.port).
        self.metrics: Optional[AgentMetricsServer] = None
        if metrics_port is not None:
            self.metrics = AgentMetricsServer(port=metrics_port)
        #: continuous-profiling sampler for this daemon (started when the
        #: register ack opts us in; per-agent object, NOT the module
        #: singleton — devcluster runs several agents in one process).
        self._profiler: Optional[profiling_mod.SamplingProfiler] = None
        #: structured-log shipping for this daemon's own records — a
        #: per-agent handler object on the agent logger tree (NOT the
        #: module singleton — devcluster runs several agents in one
        #: process; each tags lines with its own identity).
        self._log_handler: Optional[logship_mod.StructuredLogHandler] = None
        try:
            self._log_handler = logship_mod.StructuredLogHandler(
                f"agent:{self.agent_id}",
                shipper=logship_mod.LogShipper(master_url, token),
            )
            logging.getLogger("determined_tpu.agent").addHandler(
                self._log_handler
            )
        except Exception:  # noqa: BLE001 — observability never kills work
            logger.debug("agent log shipper start failed", exc_info=True)
        self._recover_tasks()
        # Deterministic spot-reclaim drill (`agent.reclaim.rank<r>` fault
        # sites): a dedicated watcher so the reclaim lands mid-training,
        # not at the ~30s action-poll cadence. One faults.active() None
        # check per tick when no plan is installed.
        threading.Thread(
            target=self._reclaim_loop, daemon=True,
            name=f"reclaim-{self.agent_id}",
        ).start()

    # -- lifecycle -----------------------------------------------------------
    def register(self) -> bool:
        """(Re)register, reporting live allocations for reattach. Returns
        True when the master asked us to hold some allocs and retry (its
        experiment restore hasn't caught up yet)."""
        with self._lock:
            running = [
                {"alloc_id": t.alloc_id, "task_id": t.task_id, "slots": t.slots}
                for t in self._tasks.values()
            ]
            # Allocs whose exit report is still pending delivery: the master
            # must not mistake them for silently-lost work and fail them
            # over — the real exit code is seconds away.
            exiting = [t.alloc_id for t, _ in self._pending_exits]
        faults.inject("agent.register")
        resp = self.session.post(
            "/api/v1/agents",
            json_body={
                "agent_id": self.agent_id, "slots": self.slots,
                "pool": self.pool, "running_allocs": running,
                "exiting_allocs": exiting, "devices": self.devices,
                # Scrape-target registration: the master's time-series
                # plane scrapes this health port (the host side is the
                # master's view of this connection's source address).
                "metrics_port": (
                    self.metrics.port if self.metrics is not None else None
                ),
            },
        ) or {}
        orphaned = set(resp.get("orphaned") or [])
        retry = set(resp.get("retry") or [])
        for alloc_id in orphaned:
            with self._lock:
                task = self._tasks.get(alloc_id)
            if task is not None:
                logger.info("master disowned %s; killing it", alloc_id)
                self._kill(task)
        adopted = set(resp.get("adopted") or [])
        logger.info(
            "agent %s registered: %d slots in pool %s%s",
            self.agent_id, self.slots, self.pool,
            f" (reattach: {len(adopted)} adopted, {len(orphaned)} orphaned)"
            if running else "",
        )
        self._flush_pending_exits()
        prof_cfg = resp.get("profiling")
        if prof_cfg and self._profiler is None:
            # Master opted this daemon into the profiling plane: sample our
            # own stacks (poll loops, launch path, log pumps) and ship
            # folded windows back as target agent:<id>.
            try:
                self._profiler = profiling_mod.SamplingProfiler(
                    f"agent:{self.agent_id}",
                    hz=float(prof_cfg.get("sample_hz") or 0) or None,
                    window_s=float(prof_cfg.get("window_s") or 0) or None,
                    shipper=profiling_mod.ProfileShipper(
                        self.master_url, self._token
                    ),
                ).start()
            except Exception:  # noqa: BLE001 — observability never kills work
                logger.debug("agent profiler start failed", exc_info=True)
        return bool(retry)

    def run_forever(self) -> None:
        needs_register = True
        # Supervision loops never give up; they back off (resilience
        # Backoff, deterministic jitter) while the master is away and
        # reset the moment it answers — replacing the old fixed
        # time.sleep(2) retry loops.
        reg_backoff = AGENT_RETRY.backoff(f"agent.register:{self.agent_id}")
        poll_backoff = AGENT_RETRY.backoff(f"agent.poll:{self.agent_id}")
        while not self._stop.is_set():
            if needs_register:
                # Retry registration until the master accepts it — a single
                # swallowed failure here must not leave the agent invisible
                # (the master answers polls for unknown agents too).
                try:
                    needs_register = self.register()
                except Exception as e:  # noqa: BLE001
                    logger.warning("register failed (%s); retrying", e)
                    self._stop.wait(reg_backoff.next_delay())
                    continue
                reg_backoff.reset()
                if needs_register:
                    self._stop.wait(1)  # master restore in progress; re-offer
                    continue
            if self._pending_exits:
                # Exits the master deferred (503 during its restore) or
                # that failed mid-flight: keep offering them — they carry
                # completed work.
                self._flush_pending_exits()
            try:
                faults.inject("agent.poll")
                resp = self.session.get(
                    f"/api/v1/agents/{self.agent_id}/actions",
                    params={"timeout_seconds": 30}, timeout=40,
                )
                poll_backoff.reset()
            except Exception as e:  # noqa: BLE001
                logger.warning("poll failed (%s); retrying", e)
                self._stop.wait(poll_backoff.next_delay())
                needs_register = True  # master may have restarted
                continue
            if self._stop.is_set() or self._detached:
                # detach()/stop() landed while the long-poll was in flight:
                # these actions belong to our successor — executing them
                # here would create ghost tasks nobody ships logs for.
                break
            for action in resp.get("actions", []):
                if action.get("type") == "REREGISTER":
                    # Master doesn't know us (restart or liveness reap).
                    # Do NOT kill local tasks — re-register offering them
                    # for reattach; the master's answer names the true
                    # orphans (ref: the reattach redesign of aproto
                    # ErrAgentMustReconnect, master_message.go:46-55).
                    needs_register = True
                    continue
                try:
                    self.handle(action)
                except Exception:  # noqa: BLE001
                    logger.exception("action failed: %s", action.get("type"))

    def _kill_all_tasks(self) -> None:
        with self._lock:
            tasks = list(self._tasks.values())
        for t in tasks:
            self._kill(t)

    def stop(self) -> None:
        self._stop.set()
        self._kill_all_tasks()
        # Ship the tail span batch before the process (or test) moves on:
        # the launch spans of just-killed tasks are exactly what a
        # post-mortem wants.
        trace_mod.flush_shipper()
        if self._profiler is not None:
            # Final window ships with the stop (the master keeps it under
            # retention; an agent vanishing mid-window loses ≤ one window).
            self._profiler.stop(flush=True)
            self._profiler = None
        if self._log_handler is not None:
            # Detach first so the close/flush path's own records don't
            # re-enter the handler being torn down; close() flushes the
            # tail batch through the shipper.
            logging.getLogger("determined_tpu.agent").removeHandler(
                self._log_handler
            )
            self._log_handler.close()
            self._log_handler = None
        if self.metrics is not None:
            self.metrics.stop()
            self.metrics = None
        if self._ephemeral_state:
            import shutil

            # Auto-created state dirs must not accumulate under /tmp; a
            # real deployment passes --state-dir and keeps it (reattach).
            shutil.rmtree(self.state_dir, ignore_errors=True)

    def detach(self) -> None:
        """Simulate an agent-process crash WITHOUT killing its tasks: stop
        polling, reporting and shipping, leave the subprocesses running
        (they log to files, not pipes, so they don't notice). A successor
        AgentDaemon on the same state_dir re-adopts them — the e2e shape of
        a real agent binary restart on a TPU VM."""
        self._detached = True
        self._stop.set()

    def die(self) -> None:
        """Abrupt death (spot-reclaim simulation): kill everything and
        report NOTHING — the master must discover the loss itself
        (provisioner reconcile / lose_agent), exactly as with a yanked VM.
        A graceful stop() would race EXITED reports into the master and
        misattribute the loss as a workload crash (budget charge)."""
        self._dead = True
        self.stop()

    def _reclaim_loop(self) -> None:
        """Deterministic spot-reclaim drill: when a DTPU_FAULT_PLAN arms
        `agent.reclaim.rank<r>`, the supervised task launched as rank r is
        SIGKILLed — the wire shape of a reclaimed host's process dying
        mid-step. The ordinary exit pipeline then reports the nonzero exit
        to the master, whose elastic layer sheds the rank and reshards the
        survivors (or, elastic off, requeues the gang as an infra
        failure). Per-rank site names because the env-inherited plan is
        identical in every agent process."""
        while not self._stop.is_set():
            if faults.active() is not None:
                with self._lock:
                    tasks = [
                        t for t in self._tasks.values() if t.rank is not None
                    ]
                for task in tasks:
                    try:
                        faults.inject(f"agent.reclaim.rank{task.rank}")
                    except faults.InjectedFault:
                        logger.warning(
                            "fault drill: reclaiming task %s (rank %s) — "
                            "SIGKILL, no grace", task.alloc_id, task.rank,
                        )
                        try:
                            os.killpg(os.getpgid(task.pid), signal.SIGKILL)
                        except (ProcessLookupError, PermissionError, OSError):
                            pass
            self._stop.wait(0.5)

    # -- task state files ------------------------------------------------------
    def _write_state(self, task: _Task) -> None:
        tmp = task.state_path + ".tmp"
        try:
            with open(tmp, "w") as f:
                json.dump(
                    {
                        "alloc_id": task.alloc_id, "task_id": task.task_id,
                        "pid": task.pid, "start_time": task.start_time,
                        "slots": task.slots, "offset": task.offset,
                        "rank": task.rank,
                    },
                    f,
                )
            os.replace(tmp, task.state_path)
        except OSError as e:
            logger.warning("state write failed for %s: %s", task.alloc_id, e)

    def _cleanup_state(self, task: _Task) -> None:
        for path in (task.state_path, task.exit_file, task.log_path):
            try:
                os.remove(path)
            except OSError:
                pass

    def _recover_tasks(self) -> None:
        """Re-adopt tasks recorded in the state dir (agent restart). Live
        pids become tracked tasks again; dead ones are queued for exit
        reporting after registration (their exit code comes from the shim's
        exit file — ref containers/manager.go:76 reattach)."""
        try:
            names = sorted(os.listdir(self.state_dir))
        except OSError:
            return
        for name in names:
            if not name.endswith(".json"):
                continue
            path = os.path.join(self.state_dir, name)
            try:
                with open(path) as f:
                    st = json.load(f)
            except (OSError, ValueError):
                continue
            alloc_id = str(st.get("alloc_id", ""))
            if not alloc_id:
                continue
            task = _Task(
                alloc_id,
                str(st.get("task_id", "")),
                pid=int(st.get("pid", 0)),
                slots=int(st.get("slots", 0)),
                log_path=os.path.join(self.state_dir, f"{alloc_id}.log"),
                exit_file=os.path.join(self.state_dir, f"{alloc_id}.exit"),
                state_path=path,
                proc=None,
                offset=int(st.get("offset", 0)),
                start_time=st.get("start_time"),
                rank=st.get("rank"),
            )
            stat = _proc_stat(task.pid) if task.pid else None
            alive = (
                stat is not None
                and stat[1] != "Z"
                and (task.start_time is None or stat[0] == task.start_time)
            )
            if alive:
                logger.info(
                    "re-adopting running task %s (pid %d)", alloc_id, task.pid
                )
                with self._lock:
                    self._tasks[alloc_id] = task
                    # Re-adoption is a supervision-load change too: without
                    # this, a restarted agent scrapes tasks_running=0 while
                    # its re-adopted tasks keep training.
                    AGENT_TASKS_RUNNING.labels(self.agent_id).set(len(self._tasks))
                self._spawn_task_threads(task)
            else:
                logger.info(
                    "task %s died while agent was down; will report", alloc_id
                )
                task.done.set()
                self._pending_exits.append((task, self._read_exit_file(task)))

    def _flush_pending_exits(self) -> None:
        with self._lock:
            pending, self._pending_exits = self._pending_exits, []
        for task, code in pending:
            try:
                self._ship_log_tail(task)
                self._report_exit(task, code)
            except Exception as e:  # noqa: BLE001 - master flaked again: requeue
                logger.warning("pending exit report failed for %s: %s",
                               task.alloc_id, e)
                with self._lock:
                    self._pending_exits.append((task, code))

    # -- actions ---------------------------------------------------------------
    def handle(self, action: Dict[str, Any]) -> None:
        kind = action.get("type")
        if kind == "START":
            self._start(action)
        elif kind == "KILL":
            with self._lock:
                task = self._tasks.get(action["alloc_id"])
            if task is not None:
                self._kill(task)
        else:
            logger.warning("unknown action %r", kind)

    def _start(self, action: Dict[str, Any]) -> None:
        with self._lock:
            old = self._tasks.get(action["alloc_id"])
        if old is not None:
            # A START while the previous process of the SAME allocation is
            # still draining (elastic grow re-placed onto this host before
            # the dropped rank finished exiting): spawning now would
            # clobber the old task's state/exit files and cross-wire its
            # exit report to the newcomer. Kill it and wait it out first.
            logger.warning(
                "START for %s while its previous process (pid %d) is "
                "draining; killing it first", action["alloc_id"], old.pid,
            )
            self._kill(old)
            old.done.wait(timeout=15.0)
        env = dict(os.environ)
        env.update(action["env"])
        env["DTPU_ENTRYPOINT"] = action.get("entrypoint", "")
        # Trace propagation (common/trace.py): the master stamped the
        # allocation's trace context into the action env; the launch span
        # parents under it and the TASK inherits the launch span's context
        # — submit → schedule → launch → trial chain, one trace id.
        launch_parent = trace_mod.parse_traceparent(
            env.get(trace_mod.TRACEPARENT_ENV)
        )
        with trace_mod.span(
            "agent.task_launch",
            {
                "agent.id": self.agent_id,
                "alloc.id": action["alloc_id"],
                "task.id": action.get("task_id", ""),
            },
            parent=launch_parent,
        ) as launch_ctx:
            if launch_parent is not None:
                env[trace_mod.TRACEPARENT_ENV] = (
                    trace_mod.format_traceparent(*launch_ctx)
                )
            self._spawn(action, env)

    def _spawn(self, action: Dict[str, Any], env: Dict[str, str]) -> None:
        # Line-buffered task stdout: log lines reach the file (and thus the
        # master) as they happen, not when a 8k block fills.
        env.setdefault("PYTHONUNBUFFERED", "1")
        alloc_id = action["alloc_id"]
        log_path = os.path.join(self.state_dir, f"{alloc_id}.log")
        exit_file = os.path.join(self.state_dir, f"{alloc_id}.exit")
        for stale in (log_path, exit_file):
            try:
                os.remove(stale)
            except OSError:
                pass
        logf = open(log_path, "ab")
        try:
            proc = subprocess.Popen(
                [
                    self.python_exe, _shim_path(), exit_file,
                    self.python_exe, "-m", "determined_tpu.exec.prep_and_run",
                ],
                env=env,
                stdout=logf,
                stderr=subprocess.STDOUT,
                start_new_session=True,  # own process group: clean KILL semantics
            )
        finally:
            logf.close()  # the child holds its own descriptor
        task = _Task(
            alloc_id,
            action.get("task_id", ""),
            pid=proc.pid,
            slots=int(env.get("DTPU_SLOTS", "0") or 0),
            log_path=log_path,
            exit_file=exit_file,
            state_path=os.path.join(self.state_dir, f"{alloc_id}.json"),
            proc=proc,
            rank=int(env.get("DTPU_ALLOC_RANK", "0") or 0),
        )
        stat = _proc_stat(proc.pid)
        task.start_time = stat[0] if stat else None
        with self._lock:
            self._tasks[task.alloc_id] = task
            AGENT_TASKS_RUNNING.labels(self.agent_id).set(len(self._tasks))
        AGENT_TASKS_STARTED.inc()
        self._write_state(task)
        self._spawn_task_threads(task)
        logger.info("started %s (pid %d)", task.alloc_id, proc.pid)

    def _spawn_task_threads(self, task: _Task) -> None:
        task.follower = threading.Thread(
            target=self._follow_logs, args=(task,), daemon=True,
            name=f"logs-{task.alloc_id}",
        )
        task.follower.start()
        threading.Thread(
            target=self._wait_exit, args=(task,), daemon=True,
            name=f"wait-{task.alloc_id}",
        ).start()

    # -- log shipping ----------------------------------------------------------
    _READ_CAP = 1 << 20

    def _follow_logs(self, task: _Task) -> None:
        """Tail the task's log FILE and ship in batches. The shipped offset
        persists in the state file, so nothing is lost or duplicated across
        agent restarts, and a failed ship retries instead of dropping the
        batch (unlike a pipe, the data is still on disk)."""
        #: Once the task is DONE, keep retrying the tail for at most this
        #: long — the master is gone for good past that, and lingering
        #: ship threads would stall agent shutdown.
        done_retry_window_s = 60.0
        give_up_at: Optional[float] = None
        ship_backoff = AGENT_RETRY.backoff(f"agent.ship:{task.alloc_id}")
        while not self._detached:
            chunk = b""
            try:
                with open(task.log_path, "rb") as f:
                    f.seek(task.offset)
                    chunk = f.read(self._READ_CAP)
            except OSError:
                pass
            done = task.done.is_set()
            if chunk:
                nl = chunk.rfind(b"\n")
                if nl >= 0:
                    end = nl + 1
                elif done or len(chunk) >= self._READ_CAP:
                    # Final partial line, or a single line longer than the
                    # read cap: ship what we have.
                    end = len(chunk)
                else:
                    task.done.wait(0.2)  # wakes early on task exit
                    continue
                try:
                    # _ship_lines advances task.offset per shipped sub-batch,
                    # so a mid-chunk failure resumes after the delivered
                    # lines instead of duplicating them.
                    self._ship_lines(task, chunk[:end])
                    ship_backoff.reset()
                    continue  # immediately look for more
                except Exception as e:  # noqa: BLE001
                    logger.warning("log ship failed for %s: %s", task.alloc_id, e)
                    delay = ship_backoff.next_delay()
                    if done:
                        if give_up_at is None:
                            give_up_at = time.time() + done_retry_window_s
                        if time.time() + delay > give_up_at:
                            return  # master gone for good; stop retrying
                        time.sleep(delay)  # done already set: wait() no-ops
                    else:
                        task.done.wait(delay)  # wakes early on task exit
                    continue
            if done:
                return
            task.done.wait(0.2)  # wakes early on task exit

    def _ship_lines(self, task: _Task, data: bytes) -> None:
        """Ship `data` (bytes from task.offset) in sub-batches, advancing
        task.offset AFTER each delivered sub-batch — a failure mid-way
        resumes exactly after the delivered lines (no loss, no dupes).
        Splits on raw bytes so byte accounting survives undecodable input."""
        lines = data.split(b"\n")
        if lines and lines[-1] == b"":
            lines.pop()
        base = task.offset
        total = len(data)
        consumed = 0
        now = time.time()
        for i in range(0, len(lines), 256):
            sub = lines[i:i + 256]
            self.session.post(
                "/api/v1/task_logs",
                json_body={
                    "task_id": task.task_id,
                    "logs": [
                        {"ts": now, "log": ln.decode("utf-8", "replace")}
                        for ln in sub
                    ],
                },
            )
            AGENT_LOG_LINES_SHIPPED.inc(len(sub))
            # +1 per newline; the final line may lack one (partial-line
            # ship at process death) — clamp to the data we actually had.
            consumed = min(total, consumed + sum(len(ln) + 1 for ln in sub))
            task.offset = base + consumed
            self._write_state(task)

    def _ship_log_tail(self, task: _Task) -> None:
        """Synchronous drain for tasks that died while the agent was away."""
        try:
            with open(task.log_path, "rb") as f:
                f.seek(task.offset)
                data = f.read()
        except OSError:
            return
        if data:
            self._ship_lines(task, data)

    # -- exit handling ---------------------------------------------------------
    def _read_exit_file(self, task: _Task) -> Optional[int]:
        try:
            with open(task.exit_file) as f:
                return int(f.read().strip())
        except (OSError, ValueError):
            return None

    def _wait_exit(self, task: _Task) -> None:
        code: Optional[int] = None
        if task.proc is not None:
            code = task.proc.wait()
        else:
            code = self._poll_dead(task)
        if self._detached:
            return  # the successor agent owns this task now
        task.done.set()
        if code is None:
            code = self._read_exit_file(task)
        with self._lock:
            # Identity-matched pop: a grow may have already registered a
            # SUCCESSOR task under the same alloc_id — the old waiter must
            # not evict it.
            if self._tasks.get(task.alloc_id) is task:
                self._tasks.pop(task.alloc_id, None)
            AGENT_TASKS_RUNNING.labels(self.agent_id).set(len(self._tasks))
        if self._dead:
            return  # abrupt death: no goodbye (see die())
        # Let the follower drain the log tail before the master tears down
        # the task's log routing.
        if task.follower is not None:
            task.follower.join(timeout=15.0)
        try:
            self._report_exit(task, code)
        except Exception as e:  # noqa: BLE001
            logger.error("failed to report exit of %s: %s", task.alloc_id, e)
            with self._lock:
                self._pending_exits.append((task, code))

    def _poll_dead(self, task: _Task) -> Optional[int]:
        """Wait for a re-adopted (non-child) pid to ACTUALLY die. Tries
        waitpid anyway — in the same-process devcluster simulation the task
        IS our child and yields a real exit code; otherwise /proc polling.
        Keeps polling through stop() (the concurrent _kill escalates to
        SIGKILL, so death is bounded) — returning early on _stop would
        report a still-running process as exited and delete its reattach
        state. Only detach() abandons the wait (successor owns the task)."""
        while not self._detached:
            try:
                pid, status = os.waitpid(task.pid, os.WNOHANG)
                if pid == task.pid:
                    return os.waitstatus_to_exitcode(status)
            except (ChildProcessError, OSError):
                pass  # not our child: true cross-process re-adoption
            stat = _proc_stat(task.pid)
            if (
                stat is None
                or stat[1] == "Z"
                or (task.start_time is not None and stat[0] != task.start_time)
            ):
                return None  # gone; shim's exit file may hold the code
            time.sleep(0.3)  # resilience-ok: /proc poll; non-child pids have no waitable handle
        return None

    def _report_exit(self, task: _Task, code: Optional[int]) -> None:
        if code is None:
            code, reason, outcome = 1, "process lost (exit code unknown)", "lost"
        else:
            reason = "" if code == 0 else f"exit code {code}"
            outcome = "clean" if code == 0 else "error"
        self.session.post(
            f"/api/v1/agents/{self.agent_id}/events",
            json_body={
                "type": "EXITED", "alloc_id": task.alloc_id,
                "exit_code": code, "reason": reason,
            },
        )
        # Counted AFTER the POST lands: a failed report requeues through
        # _pending_exits and retries through here — counting first would
        # inflate the series by one per retry during a master outage.
        AGENT_TASK_EXITS.labels(outcome).inc()
        self._cleanup_state(task)
        logger.info("%s exited with %d", task.alloc_id, code)

    def _kill(self, task: _Task, grace_s: float = 10.0) -> None:
        """SIGTERM the group, escalate to SIGKILL (ref: container stop flow).
        Works for both owned (child) and re-adopted (non-child) tasks."""
        stat = _proc_stat(task.pid)
        if stat is None or (
            task.start_time is not None and stat[0] != task.start_time
        ):
            # Already gone — or the pid was RECYCLED by an unrelated
            # process. killpg on a recycled pid would murder a stranger's
            # whole process group (with raw re-adopted pids this is a real
            # hazard, unlike the old child-only Popen handles).
            return
        try:
            pgid = os.getpgid(task.pid)
        except (ProcessLookupError, PermissionError):
            return
        try:
            os.killpg(pgid, signal.SIGTERM)
        except (ProcessLookupError, PermissionError):
            return
        deadline = time.time() + grace_s
        while time.time() < deadline:
            # done.wait doubles as the poll interval AND wakes early the
            # moment the waiter thread reaps the exit (condition-driven,
            # not a bare sleep poll); _proc_stat still covers re-adopted
            # non-child pids the waiter can't reap.
            if task.done.wait(0.2):
                return
            stat = _proc_stat(task.pid)
            if stat is None or stat[1] == "Z":
                return
        try:
            os.killpg(pgid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass


def main() -> None:
    import argparse

    parser = argparse.ArgumentParser(description="determined_tpu agent")
    parser.add_argument("--master-url", required=True)
    parser.add_argument("--agent-id", default=None)
    parser.add_argument("--slots", default="auto",
                        help='"auto", or an int (artificial slots)')
    parser.add_argument("--pool", default="default")
    parser.add_argument("--state-dir", default=None,
                        help="persistent task-state dir (enables reattach "
                             "across agent restarts)")
    parser.add_argument("--token", default=os.environ.get("DTPU_TOKEN", ""),
                        help="auth token (when the master has users configured)")
    parser.add_argument("--metrics-port", type=int, default=None,
                        help="serve /metrics (+ /healthz) on this port "
                             "(0 = ephemeral; omit to disable)")
    args = parser.parse_args()
    logging.basicConfig(level=logging.INFO)
    slots: Any = args.slots if args.slots == "auto" else int(args.slots)
    AgentDaemon(
        args.master_url, args.agent_id, slots, args.pool, token=args.token,
        state_dir=args.state_dir, metrics_port=args.metrics_port,
    ).run_forever()


if __name__ == "__main__":
    main()
