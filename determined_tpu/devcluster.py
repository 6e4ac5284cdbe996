"""DevCluster: a whole cluster (master + agents) in one process tree.

Rebuild of the reference's devcluster tooling (`tools/devcluster.yaml`, e2e
`ManagedCluster` at `e2e_tests/tests/cluster/managed_cluster.py:28`): start
an in-process Master + ApiServer and N agent daemons on this box; agents
spawn REAL trial subprocesses through the full exec chain, so everything
from `POST /experiments` to rendezvous to checkpoint upload runs exactly as
on a TPU pod — the workhorse for cluster e2e tests and local development.
"""
from __future__ import annotations

import os
import sys
import threading
from typing import Any, Dict, List, Optional

from determined_tpu.agent.agent import AgentDaemon
from determined_tpu.common.api_session import Session
from determined_tpu.master.api_server import ApiServer
from determined_tpu.master.core import Master


class DevCluster:
    def __init__(
        self,
        n_agents: int = 1,
        slots_per_agent: int = 1,
        db_path: str = ":memory:",
        scheduler: Optional[Dict[str, Any]] = None,
        preempt_timeout_s: float = 120.0,
        tls: bool = False,
        trace_file: Optional[str] = None,
        agent_metrics: bool = False,
        metrics_config: Optional[Dict[str, Any]] = None,
        alerts_config: Optional[Dict[str, Any]] = None,
        traces_config: Optional[Dict[str, Any]] = None,
        profiling_config: Optional[Dict[str, Any]] = None,
        logs_config: Optional[Dict[str, Any]] = None,
    ) -> None:
        #: agent_metrics=True gives every agent an ephemeral health port
        #: (+ registers it as a master scrape target) — opt-in so the
        #: extra HTTP servers don't ride along under every e2e test.
        self._agent_metrics = agent_metrics
        # Trial subprocesses must import determined_tpu without installation.
        repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        pypath = os.environ.get("PYTHONPATH", "")
        if repo_root not in pypath.split(os.pathsep):
            os.environ["PYTHONPATH"] = (
                f"{repo_root}{os.pathsep}{pypath}" if pypath else repo_root
            )

        self.master = Master(
            db_path=db_path,
            pools_config={"default": {"scheduler": scheduler or {"type": "priority"}}},
            preempt_timeout_s=preempt_timeout_s,
            trace_file=trace_file,
            metrics_config=metrics_config,
            alerts_config=alerts_config,
            traces_config=traces_config,
            profiling_config=profiling_config,
            logs_config=logs_config,
        )
        self._cert_env_prev: Optional[str] = None
        self._tls_dir: Optional[str] = None
        self._tls = tls
        try:
            if tls:
                # Self-signed bootstrap (det deploy local analog):
                # in-process agents and their REAL trial subprocesses all
                # verify against the cert via the inherited
                # DTPU_MASTER_CERT env.
                import tempfile

                from determined_tpu.common import tls as tls_mod

                self._tls_dir = tempfile.mkdtemp(prefix="dtpu-tls-")
                cert, key = tls_mod.generate_self_signed(self._tls_dir)
                self._cert_env_prev = os.environ.get(tls_mod.CERT_ENV)
                os.environ[tls_mod.CERT_ENV] = cert
                self.api = ApiServer(self.master, tls=(cert, key))
            else:
                self.api = ApiServer(self.master)
            self.api.start()
        except BaseException:
            self._restore_tls_state()
            raise
        self.master.external_url = self.api.url
        self.agents: List[AgentDaemon] = []
        self._agent_threads: List[threading.Thread] = []
        for i in range(n_agents):
            self.start_agent(f"agent-{i}", slots_per_agent)

    # -- agents (start/kill for chaos tests, ref test_agent_restart.py) -------
    def start_agent(
        self, agent_id: str, slots: Any, state_dir: Optional[str] = None
    ) -> AgentDaemon:
        """`slots`: an int (artificial slots — what the tests use), or
        "auto" to detect the host's chips. Detection runs in a short-lived
        child (agent.detect_devices), so this process — which also hosts
        the master — never holds a chip its trials need."""
        agent = AgentDaemon(
            self.api.url, agent_id=agent_id, slots=slots,
            python_exe=sys.executable, state_dir=state_dir,
            metrics_port=0 if self._agent_metrics else None,
        )
        thread = threading.Thread(
            target=agent.run_forever, daemon=True, name=f"agent-{agent_id}"
        )
        thread.start()
        self.agents.append(agent)
        self._agent_threads.append(thread)
        return agent

    def restart_agent(self, agent: AgentDaemon) -> AgentDaemon:
        """Simulate an agent-binary restart: the old daemon 'crashes'
        (detach — its task subprocesses keep running against their log
        files) and a successor on the same state dir re-adopts them
        (ref: containers/manager.go:76 reattach)."""
        agent.detach()
        if agent in self.agents:
            self.agents.remove(agent)
        successor = self.start_agent(
            agent.agent_id, agent.slots, state_dir=agent.state_dir
        )
        # Inherit ephemeralness: an auto-created /tmp state dir must still
        # be cleaned by whoever stops LAST, or chaos tests strand one dir
        # per restart.
        successor._ephemeral_state = agent._ephemeral_state
        return successor

    def kill_agent(self, agent: AgentDaemon) -> None:
        # Order matters for failure attribution: the master learns of the
        # loss FIRST (as with a real abrupt VM death — allocations complete
        # as infra failures, no restart-budget charge), then the local
        # process tree is torn down. The reverse order races the dying
        # agent's EXITED report into the master and misattributes the loss
        # as a workload crash. The task token is revoked at completion, so
        # the briefly-surviving old process can no longer write.
        self.master.lose_agent(agent.agent_id)
        agent.die()

    # -- client-side --------------------------------------------------------
    def session(self) -> Session:
        return Session(self.api.url)

    def create_experiment(self, config: Dict[str, Any]) -> int:
        return int(self.session().post(
            "/api/v1/experiments", json_body={"config": config}
        )["id"])

    def wait_experiment(self, exp_id: int, timeout: float = 300.0) -> str:
        exp = self.master.get_experiment(exp_id)
        assert exp is not None
        return exp.wait_done(timeout=timeout)

    def _restore_tls_state(self) -> None:
        if not self._tls:
            return
        from determined_tpu.common.tls import CERT_ENV

        if self._cert_env_prev is None:
            os.environ.pop(CERT_ENV, None)
        else:
            os.environ[CERT_ENV] = self._cert_env_prev
        if self._tls_dir is not None:
            import shutil

            # The dir holds the master's private key; don't leave copies
            # strewn across /tmp after every TLS devcluster.
            shutil.rmtree(self._tls_dir, ignore_errors=True)
            self._tls_dir = None

    def stop(self) -> None:
        for agent in self.agents:
            agent.stop()
        self.master.shutdown()
        self.api.stop()
        # The agents pointed the process-global span shipper at this
        # master; drop it so later in-process spans (next test's cluster)
        # don't ship to a dead port.
        from determined_tpu.common import trace as trace_mod

        trace_mod.reset_shipper()
        # Same hygiene for the module-singleton profiler a task started
        # in-process (notebook/serving helpers under tests).
        from determined_tpu.common import profiling as profiling_mod

        profiling_mod.reset_profiler()
        # And for the module-singleton structured-log handler (a task's
        # in-process logship.start_shipping under tests).
        from determined_tpu.common import logship as logship_mod

        logship_mod.reset_shipping()
        self._restore_tls_state()

    def __enter__(self) -> "DevCluster":
        return self

    def __exit__(self, *exc: Any) -> None:
        self.stop()
