"""Training health sentinel tests: in-graph non-finite guard, consecutive-
skip rollback with data fast-forward, loss-spike detection, replica-
divergence audit — every failure mode driven deterministically through the
PR-1 fault plan's new `train.*` sites, all on CPU.
"""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from determined_tpu import core
from determined_tpu.common.faults import FaultPlan, FaultSpec, plan_active
from determined_tpu.models import MnistMLP
from determined_tpu.models.vision import MLPConfig
from determined_tpu.parallel.mesh import MeshConfig, make_mesh
from determined_tpu.trainer import Batch, JAXTrial, Trainer
from determined_tpu.trainer import _sentinel


class _IndexedStream:
    """Deterministic batch-indexed stream with the O(1) skip() contract:
    batch i depends only on i. Records every consumed index."""

    def __init__(self, record):
        self.i = 0
        self.record = record

    def skip(self, n):
        self.i += n

    def __iter__(self):
        return self

    def __next__(self):
        i = self.i
        self.i += 1
        self.record.append(i)
        rng = np.random.default_rng(1000 + i)
        x = rng.normal(size=(16, 8)).astype(np.float32)
        y = (np.arange(16) % 4).astype(np.int32)
        return {"image": x, "label": y}


class _SentinelTrial(JAXTrial):
    record: list  # class-level so resumed instances share the recorder

    def build_model(self, mesh):
        return MnistMLP(MLPConfig(in_dim=8, hidden=16, n_classes=4), mesh=mesh)

    def build_optimizer(self):
        return optax.adam(1e-2)

    def build_training_data(self):
        return _IndexedStream(self.record)

    def build_validation_data(self):
        return []


def _trial(record):
    t = _SentinelTrial()
    t.record = record
    return t


def _ctx(tmp_path):
    return core._context._dummy_init(checkpoint_storage=str(tmp_path))


def _leaves_differ(a, b):
    """Names of the leaves of two state trees that are not bit-equal."""
    return [
        jax.tree_util.keystr(path)
        for (path, x), y in zip(
            jax.tree_util.tree_leaves_with_path(jax.device_get(a)),
            jax.tree_util.tree_leaves(jax.device_get(b)),
        )
        if not np.array_equal(x, y, equal_nan=True)
    ]


class TestGuard:
    @pytest.mark.parametrize("optimizer", [
        lambda: optax.adam(1e-2),
        # the benchmark cells' optimizer (`SyntheticTrial`)
        lambda: optax.chain(
            optax.clip_by_global_norm(1.0),
            optax.adamw(1e-2, weight_decay=0.1),
        ),
    ], ids=["adam", "clip_adamw"])
    def test_nonfinite_step_skips_update_in_graph(
        self, tmp_path, monkeypatch, optimizer
    ):
        """A NaN loss leaves every leaf of params and optimizer state
        (Adam's count too) bit-equal, only the step advances, and the
        on-device skip counter is bumped; a healthy step resets it and
        gives bit for bit the state of the same update with no guard."""
        trial = _trial([])
        trial.build_optimizer = optimizer
        trainer = Trainer(trial, _ctx(tmp_path), seed=0)
        trainer._step_fn = trainer._build_step_fn()
        stream = iter(_IndexedStream([]))
        s0 = jax.device_get(trainer.state)

        batch = trainer._put_batch(next(stream))
        state, metrics, skips = trainer._step_fn(
            trainer.state, batch, np.float32(np.nan), jnp.zeros((), jnp.int32)
        )
        assert int(state["step"]) == int(s0["step"]) + 1
        assert int(metrics["sentinel_skipped"]) == 1
        assert int(skips) == 1
        assert _leaves_differ(s0, state) == ["['step']"]
        s1 = jax.device_get(state)

        batch = trainer._put_batch(next(stream))
        state2, metrics2, skips2 = trainer._step_fn(
            state, batch, np.float32(1.0), skips
        )
        assert int(metrics2["sentinel_skipped"]) == 0
        assert int(skips2) == 0
        changed = _leaves_differ(s1, state2)
        assert any("params" in name for name in changed), (
            "healthy step must update params"
        )
        assert any("count" in name for name in changed), changed

        # the same step from the same state, the guard's `ok` forced true
        monkeypatch.setattr(
            _sentinel, "guarded_update",
            lambda old, new, loss, gnorm, skips: (
                new, jnp.bool_(True), jnp.zeros((), jnp.int32)),
        )
        unguarded, _, _ = trainer._build_step_fn()(
            jax.device_put(s1), batch, np.float32(1.0), skips
        )
        assert _leaves_differ(unguarded, state2) == []

    def test_consecutive_counter_accumulates(self, tmp_path):
        trainer = Trainer(_trial([]), _ctx(tmp_path), seed=0)
        trainer._step_fn = trainer._build_step_fn()
        stream = iter(_IndexedStream([]))
        state, skips = trainer.state, jnp.zeros((), jnp.int32)
        for expect in (1, 2, 3):
            batch = trainer._put_batch(next(stream))
            state, metrics, skips = trainer._step_fn(
                state, batch, np.float32(np.nan), skips
            )
            assert int(skips) == expect
            assert int(metrics["sentinel_skips"]) == expect


class TestRollback:
    def test_consecutive_skips_trigger_rollback_and_fast_forward(
        self, tmp_path
    ):
        """The acceptance drill: injected NaN batches → in-graph skips;
        max_consecutive_skips reached → verified-checkpoint rollback +
        data fast-forward past the poisoned window."""
        record = []
        trainer = Trainer(
            _trial(record), _ctx(tmp_path), seed=0,
            health={"max_consecutive_skips": 3},
        )
        trainer.fit(max_length=Batch(4), report_period=Batch(1))
        sid = trainer._save_checkpoint(sync=True)
        assert sid is not None and record == [0, 1, 2, 3]

        plan = FaultPlan({"train.nonfinite": FaultSpec(failures=3)})
        with plan_active(plan):
            trainer.fit(max_length=Batch(12), report_period=Batch(1))

        assert trainer.steps_completed == 12
        assert trainer.rollbacks == 1
        assert trainer.steps_skipped == 3
        # Steps 5-7 consumed (and poisoned) indices 4-6; the rollback
        # restored step 4 and did NOT rewind the stream — steps 5-12
        # retrain on indices 7-14. The poisoned window is gone forever.
        assert record[4:7] == [4, 5, 6]
        assert record[7:] == list(range(7, 15))
        assert trainer._data_offset == 3

    def test_offset_persists_for_identical_resume(self, tmp_path):
        """Satellite: data-stream skip() determinism across a rollback —
        the batch a resumed process consumes at step i is the batch the
        in-process run would have consumed."""
        record = []
        trainer = Trainer(
            _trial(record), _ctx(tmp_path), seed=0,
            health={"max_consecutive_skips": 2},
        )
        trainer.fit(max_length=Batch(3), report_period=Batch(1))
        trainer._save_checkpoint(sync=True)
        with plan_active(FaultPlan({"train.nonfinite": FaultSpec(failures=2)})):
            trainer.fit(max_length=Batch(8), report_period=Batch(1))
        assert trainer.rollbacks == 1 and trainer._data_offset == 2
        sid = trainer._save_checkpoint(sync=True)

        # The uninterrupted continuation consumes the next index...
        record_cont = list(record)
        trainer.fit(max_length=Batch(9), report_period=Batch(1))
        next_index_inproc = record[len(record_cont)]

        # ...and a fresh process restoring the checkpoint consumes the
        # SAME index for the same step (skip = steps + data_offset).
        record2 = []
        t2 = Trainer(_trial(record2), _ctx(tmp_path), seed=0)
        t2.fit(
            max_length=Batch(9), report_period=Batch(1),
            latest_checkpoint=sid,
        )
        assert t2._data_offset == 2
        assert record2[0] == next_index_inproc

    def test_no_checkpoint_degrades_to_guard_only(self, tmp_path):
        """Rollback with nothing to roll back to: params stayed clean
        in-graph; training continues instead of dying."""
        record = []
        trainer = Trainer(
            _trial(record), _ctx(tmp_path), seed=0,
            health={"max_consecutive_skips": 2},
        )
        with plan_active(FaultPlan({"train.nonfinite": FaultSpec(failures=3)})):
            trainer.fit(max_length=Batch(6), report_period=Batch(1))
        assert trainer.steps_completed == 6
        assert trainer.rollbacks == 0
        assert trainer.steps_skipped == 3


class TestSpike:
    def test_detector_flags_spike_not_baseline(self):
        cfg = _sentinel.SentinelConfig(
            spike_zscore=4.0, spike_min_history=4
        )
        det = _sentinel.SpikeDetector(cfg)
        for x in (1.0, 1.1, 0.9, 1.0, 1.05):
            assert det.observe(x) is False
        assert det.observe(100.0) is True
        # the spike did not poison the baseline
        assert det.observe(1.0) is False
        # non-finite is the guard's jurisdiction
        assert det.observe(float("nan")) is False

    def test_cold_detector_never_fires(self):
        det = _sentinel.SpikeDetector(
            _sentinel.SentinelConfig(spike_zscore=1.0, spike_min_history=8)
        )
        assert det.observe(1.0) is False
        assert det.observe(1e9) is False  # only 1 observation of history

    def test_spike_triggers_rollback(self, tmp_path):
        """A finite-but-wild loss (the guard can't see it) trips the
        robust z-score and rides the same rollback path."""
        record = []
        trainer = Trainer(
            _trial(record), _ctx(tmp_path), seed=0,
            health={
                "max_consecutive_skips": 0,
                "spike_zscore": 5.0,
                "spike_min_history": 4,
            },
        )
        trainer.fit(max_length=Batch(6), report_period=Batch(1))
        trainer._save_checkpoint(sync=True)
        with plan_active(FaultPlan({"train.spike": FaultSpec(failures=1)})):
            trainer.fit(max_length=Batch(10), report_period=Batch(1))
        assert trainer.rollbacks == 1
        assert trainer.steps_skipped == 0  # finite: never skipped in-graph
        assert trainer.steps_completed == 10
        assert trainer._data_offset == 1  # one poisoned batch skipped


class TestDivergence:
    def test_compare_checksums_names_minority(self):
        gathered = [
            (0, {"k|0:4": [("dev0", (1.0, 2.0))]}),
            (1, {"k|0:4": [("dev1", (1.0, 2.0))]}),
            (2, {"k|0:4": [("dev2", (1.5, 2.0))]}),
        ]
        msg = _sentinel.compare_checksums(
            gathered, addrs={2: "10.0.0.3:4242"}
        )
        assert msg is not None
        assert "rank 2" in msg and "10.0.0.3:4242" in msg and "dev2" in msg
        assert "rank 0" not in msg

    def test_compare_checksums_clean_and_disjoint(self):
        clean = [
            (0, {"a|0:2": [("d0", (1.0, 1.0))]}),
            (1, {"a|0:2": [("d1", (1.0, 1.0))]}),
        ]
        assert _sentinel.compare_checksums(clean) is None
        # different regions (fsdp shards) are never compared
        disjoint = [
            (0, {"a|0:2": [("d0", (1.0, 1.0))]}),
            (1, {"a|2:4": [("d1", (9.0, 9.0))]}),
        ]
        assert _sentinel.compare_checksums(disjoint) is None

    def test_audit_clean_on_replicated_mesh(self, devices8, tmp_path):
        mesh = make_mesh(MeshConfig(data=8), devices=devices8)
        trainer = Trainer(
            _trial([]), _ctx(tmp_path), seed=0, mesh=mesh,
            health={"divergence_check_period": 2},
        )
        trainer.fit(max_length=Batch(2), report_period=Batch(1))
        assert trainer.steps_completed == 2

    def test_injected_bitflip_errors_trial_naming_rank(
        self, devices8, tmp_path
    ):
        """Acceptance drill: injected replica bit-flip → the audit errors
        the trial with the offending holder named."""
        mesh = make_mesh(MeshConfig(data=8), devices=devices8)
        trainer = Trainer(
            _trial([]), _ctx(tmp_path), seed=0, mesh=mesh,
            health={"divergence_check_period": 2},
        )
        plan = FaultPlan({"train.divergence.rank0": FaultSpec(failures=1)})
        with plan_active(plan):
            with pytest.raises(
                _sentinel.ReplicaDivergenceError, match="rank 0"
            ):
                trainer.fit(max_length=Batch(2), report_period=Batch(1))


class TestFaultSites:
    def test_poison_factor_sites(self):
        assert _sentinel.poison_factor() == 1.0
        with plan_active(FaultPlan({"train.nonfinite": FaultSpec(failures=1)})):
            assert np.isnan(_sentinel.poison_factor())
            assert _sentinel.poison_factor() == 1.0
        with plan_active(FaultPlan({"train.spike": FaultSpec(failures=1)})):
            assert _sentinel.poison_factor() == _sentinel.SPIKE_FACTOR

    def test_divergence_site_is_rank_targeted(self):
        plan = FaultPlan({"train.divergence.rank1": FaultSpec(failures=1)})
        with plan_active(plan):
            assert _sentinel.divergence_fault(0) is False
            assert _sentinel.divergence_fault(1) is True
            assert _sentinel.divergence_fault(1) is False  # budget spent


class TestConfig:
    def test_from_config_defaults_and_parsing(self):
        cfg = _sentinel.SentinelConfig.from_config(None)
        assert cfg.max_consecutive_skips == 3
        assert cfg.spike_zscore == 0.0 and cfg.divergence_check_period == 0
        cfg = _sentinel.SentinelConfig.from_config(
            {"stall_timeout_s": 120, "spike_zscore": 6, "max_consecutive_skips": 5}
        )
        assert cfg.stall_timeout_s == 120.0
        assert cfg.spike_zscore == 6.0 and cfg.max_consecutive_skips == 5

    def test_expconf_rejects_typoed_health_keys(self):
        from determined_tpu.master import expconf

        errs = expconf.validate(
            {"entrypoint": "m:T", "health": {"stall_timeout": 10}}
        )
        assert any("unknown key 'stall_timeout'" in e for e in errs)
        errs = expconf.validate(
            {"entrypoint": "m:T", "health": {"spike_zscore": -1}}
        )
        assert any("spike_zscore" in e for e in errs)
        errs = expconf.validate(
            {"entrypoint": "m:T", "health": {"max_consecutive_skips": 1.5}}
        )
        assert any("max_consecutive_skips" in e for e in errs)
        assert expconf.validate({
            "entrypoint": "m:T",
            "health": {
                "stall_timeout_s": 300, "max_consecutive_skips": 3,
                "spike_zscore": 6.0, "divergence_check_period": 500,
            },
        }) == []
