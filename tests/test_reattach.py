"""Reattach: running trials survive master AND agent restarts with ZERO
restarts and no checkpoint rollback.

The reference's flagship fault-tolerance feature (SURVEY.md §7 hard part c):
agents reconnect and re-adopt running containers
(`agent/internal/containers/manager.go:76`,
`aproto/master_message.go:46-55`, `restore.go:59`). Here the agent reports
its live allocations at (re)registration; the master adopts them instead of
requeueing — a master bounce or an agent-binary restart costs the trial
nothing.
"""
import threading
import time

import pytest

from determined_tpu.agent.agent import AgentDaemon, SlotDetectionError, detect_slots
from determined_tpu.devcluster import DevCluster
from determined_tpu.master.api_server import ApiServer
from determined_tpu.master.core import Master
from determined_tpu.sdk import Determined


def _trial_cfg(tmp_path, sleep_s=0.3, max_length=40):
    return {
        "entrypoint": "determined_tpu.exec.builtin_trials:SyntheticTrial",
        "searcher": {"name": "single", "max_length": max_length, "metric": "loss"},
        "hyperparameters": {
            "model": "mnist-mlp", "batch_size": 16, "lr": 1e-3,
            "sleep_s": sleep_s,  # slow enough to bounce components mid-trial
        },
        "resources": {"slots_per_trial": 1},
        "scheduling_unit": 1,
        "min_checkpoint_period": {"batches": 5},
        "checkpoint_storage": {"type": "shared_fs",
                               "host_path": str(tmp_path / "ckpt")},
        "environment": {"jax_platform": "cpu"},
        "max_restarts": 3,
    }


def _wait_mid_flight(db, exp_id, min_reports=5, timeout=120.0):
    """Block until the (single) trial is genuinely MID-TRAINING.

    Gate on live training-metric reports, NOT steps_completed: that column
    only moves at searcher-op completion, so for a "single" searcher it
    jumps 0 → max_length at the END — a steps-based gate would fire
    post-training and the bounce would exercise the exit-race path instead
    of live adoption."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        trials = db.list_trials(exp_id)
        if trials:
            trial_id = trials[0]["id"]
            n = len(db.get_metrics(trial_id, "training"))
            if n >= min_reports and trials[0]["steps_completed"] == 0:
                return trial_id
            if trials[0]["steps_completed"]:
                raise AssertionError(
                    "trial finished before the bounce; gate raced"
                )
        time.sleep(0.2)
    raise AssertionError("trial never reached mid-flight")


class TestMasterRestartReattach:
    def test_trial_survives_master_restart_with_zero_restarts(self, tmp_path):
        db_path = str(tmp_path / "master.db")
        cfg = _trial_cfg(tmp_path)

        m1 = Master(db_path=db_path)
        api1 = ApiServer(m1, port=0)
        port = api1.port
        api1.start()
        m1.external_url = api1.url
        agent = AgentDaemon(
            api1.url, agent_id="reattach-agent", slots=1,
            state_dir=str(tmp_path / "agent-state"),
        )
        threading.Thread(target=agent.run_forever, daemon=True).start()
        deadline = time.time() + 30
        while time.time() < deadline and not m1.agent_hub.list():
            time.sleep(0.2)

        exp_id = Determined(api1.url).create_experiment(cfg).id
        trial_id = _wait_mid_flight(m1.db, exp_id)

        # "Crash" the master mid-trial (ungraceful: no preemption).
        api1.stop()
        m1.shutdown()

        # Boot 2 on the same DB + SAME PORT; restore BEFORE serving (the
        # main.py boot order) so the first agent re-registration adopts.
        m2 = Master(db_path=db_path, agent_timeout_s=600,
                    reconcile_grace_s=120.0)
        restored = m2.restore_experiments()
        assert restored == 1
        api2 = ApiServer(m2, port=port)
        api2.start()
        m2.external_url = api2.url
        try:
            exp2 = m2.get_experiment(exp_id)
            assert exp2 is not None
            state = exp2.wait_done(timeout=300)
            assert state == "COMPLETED"
            row = m2.db.get_trial(trial_id)
            # THE reattach guarantees: all work done, zero restarts, the
            # ORIGINAL run finished (no relaunch, no checkpoint rollback).
            assert row["steps_completed"] == 40
            assert row["restarts"] == 0
            assert row["infra_requeues"] == 0
            assert row["run_id"] == 0
            runs = {m["trial_run_id"]
                    for m in m2.db.get_metrics(trial_id, "training")}
            assert runs == {0}, f"expected one continuous run, got {runs}"
            # The adopted allocation went through the full exit path — and
            # the in-memory record in master 2 proves LIVE adoption (the
            # exit-race fallback never creates one).
            alloc_id = f"{exp_id}.{trial_id}.0"
            alloc = m2.db.get_allocation(alloc_id)
            assert alloc is not None and alloc["state"] == "TERMINATED"
            live = m2.alloc_service.get(alloc_id)
            assert live is not None and live.state == "TERMINATED"
        finally:
            agent.stop()
            api2.stop()
            m2.shutdown()


class TestAgentRestartReattach:
    def test_trial_survives_agent_restart_with_zero_restarts(self, tmp_path):
        with DevCluster(n_agents=0) as cluster:
            agent = cluster.start_agent(
                "bouncy", 1, state_dir=str(tmp_path / "astate")
            )
            exp_id = cluster.create_experiment(_trial_cfg(tmp_path))
            trial_id = _wait_mid_flight(cluster.master.db, exp_id)

            successor = cluster.restart_agent(agent)
            assert successor is not agent

            assert cluster.wait_experiment(exp_id, timeout=300) == "COMPLETED"
            row = cluster.master.db.get_trial(trial_id)
            assert row["steps_completed"] == 40
            assert row["restarts"] == 0
            assert row["run_id"] == 0
            runs = {m["trial_run_id"]
                    for m in cluster.master.db.get_metrics(trial_id, "training")}
            assert runs == {0}


class TestReattachUnits:
    def test_detect_slots_refuses_broken_runtime(self, monkeypatch):
        from determined_tpu.agent import agent as agent_mod

        monkeypatch.setattr(
            agent_mod, "_DETECT_SCRIPT",
            "raise RuntimeError('TPU runtime wedged')",
        )
        with pytest.raises(SlotDetectionError, match="TPU runtime wedged"):
            detect_slots("auto")
        # No accelerator stack to import is the same refusal, not "1 slot".
        monkeypatch.setattr(
            agent_mod, "_DETECT_SCRIPT", "import no_such_accelerator_stack"
        )
        with pytest.raises(SlotDetectionError, match="no_such_accelerator"):
            detect_slots("auto")
        # Explicit counts never touch the runtime.
        assert detect_slots(4) == 4

    def test_auto_detection_leaves_the_agent_off_the_chip(self):
        """A chip belongs to one process at a time, and the tasks the
        agent spawns are the ones that need it: `auto` detection must
        learn the devices without this process initialising a backend.
        Run in a fresh interpreter — the test process itself has long
        since touched jax."""
        import json
        import os
        import subprocess
        import sys

        code = (
            "import json, sys\n"
            "from determined_tpu.agent.agent import detect_devices\n"
            "devs = detect_devices('auto')\n"
            "backends = None\n"
            "if 'jax' in sys.modules:\n"
            "    from jax._src import xla_bridge\n"
            "    backends = sorted(xla_bridge._backends)\n"
            "print(json.dumps({'devices': devs, 'jax_imported': 'jax' in "
            "sys.modules, 'backends': backends}))\n"
        )
        out = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=300, check=True,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ).stdout
        got = json.loads(out.strip().splitlines()[-1])
        assert got["devices"] and got["devices"][0]["platform"] == "cpu"
        assert [d["id"] for d in got["devices"]] == list(
            range(len(got["devices"]))
        )
        # Stronger than "no backend": the agent never even imports jax.
        assert got["jax_imported"] is False and got["backends"] is None

    def test_detect_devices_and_registration_model(self):
        """Per-slot device model rides registration to the master's agent
        registry (ref: agent detect.go + master/pkg/device)."""
        from determined_tpu.agent.agent import detect_devices

        devs = detect_devices("auto")  # CPU test host: jax cpu devices
        assert devs and all("kind" in d and "platform" in d for d in devs)
        synthetic = detect_devices(3)
        assert [d["id"] for d in synthetic] == [0, 1, 2]
        m = Master()
        try:
            m.agent_registered("a1", 2, "default", devices=synthetic[:2])
            agents = m.agent_hub.list()
            assert [d["id"] for d in agents["a1"]["devices"]] == [0, 1]
        finally:
            m.shutdown()

    def test_unknown_alloc_is_orphaned(self):
        m = Master()
        try:
            res = m.agent_registered(
                "a1", 1, "default",
                [{"alloc_id": "999.1.0", "task_id": "trial-1", "slots": 1}],
            )
            assert res["orphaned"] == ["999.1.0"]
            assert res["adopted"] == [] and res["retry"] == []
        finally:
            m.shutdown()

    def test_unreported_alloc_fails_over(self, tmp_path):
        """The reverse diff: an agent re-registering WITHOUT an allocation
        the master booked on it (host rebooted, state dir lost) must free
        the slots and requeue the trial as an infra failure — but a START
        still sitting undelivered in its action queue is exempt."""
        m = Master(db_path=str(tmp_path / "m.db"))
        try:
            m.agent_registered("a1", 1, "default", [])
            exp_id = m.create_experiment({
                "entrypoint": "x:Y",
                "searcher": {"name": "single", "max_length": 10,
                             "metric": "loss"},
                "hyperparameters": {},
                "resources": {"slots_per_trial": 1},
            })
            exp = m.get_experiment(exp_id)
            rec = next(iter(exp.trials.values()))
            alloc_id = f"{exp_id}.{rec.trial_id}.0"
            assert m.alloc_service.get(alloc_id) is not None

            # START not yet delivered: re-registering empty must NOT kill it.
            m.agent_registered("a1", 1, "default", [])
            assert m.alloc_service.get(alloc_id).state != "TERMINATED"

            # Deliver the START (drain the queue), then re-register empty:
            # the agent received-and-lost the work -> infra failover.
            actions = m.agent_hub.poll("a1", timeout=0.1)
            assert any(a.get("type") == "START" for a in actions)
            m.agent_registered("a1", 1, "default", [])
            assert m.alloc_service.get(alloc_id).state == "TERMINATED"
            assert rec.infra_requeues == 1
            assert rec.run_id == 1  # requeued, budget untouched
            assert rec.restarts == 0
        finally:
            m.shutdown()

    def test_stale_run_is_orphaned(self, tmp_path):
        """An alloc from a superseded run (the master already relaunched a
        newer one) must be killed, not adopted — two processes would fight
        for the chips."""
        m = Master(db_path=str(tmp_path / "m.db"))
        try:
            # slots_per_trial larger than the agent: the trial stays PENDING,
            # so registration can't legitimately place it mid-test.
            exp_id = m.create_experiment({
                "entrypoint": "x:Y",
                "searcher": {"name": "single", "max_length": 10,
                             "metric": "loss"},
                "hyperparameters": {},
                "resources": {"slots_per_trial": 4},
            })
            exp = m.get_experiment(exp_id)
            rec = next(iter(exp.trials.values()))
            # Fake a persisted allocation from run 0, then bump the run.
            old_alloc = f"{exp_id}.{rec.trial_id}.0"
            m.db.upsert_allocation(
                old_alloc, task_id=f"trial-{rec.trial_id}",
                trial_id=rec.trial_id, state="RUNNING", slots=1,
                num_processes=1,
            )
            rec.run_id = 3
            res = m.agent_registered(
                "a1", 1, "default",
                [{"alloc_id": old_alloc, "slots": 1}],
            )
            assert res["orphaned"] == [old_alloc]
        finally:
            m.shutdown()
