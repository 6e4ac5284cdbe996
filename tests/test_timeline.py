"""Trainer step-phase timeline + goodput ledger (trainer/_timeline.py):
ledger arithmetic, metadata persistence, and the trainer-integrated
rollback-and-restart drill the acceptance criteria name."""
import time

import numpy as np
import optax
import pytest

from determined_tpu.trainer._timeline import Timeline


class TestLedger:
    def test_window_decomposition(self):
        tl = Timeline(enabled=True)
        tl.reset_window()
        tl.window["data_wait"] += 0.5
        tl.window["h2d_put"] += 0.25
        # wall is real perf_counter elapsed (tiny); the injected phase
        # times dominate, so the residual clamps at >= 0
        out = tl.close_window()
        assert out["window_s"] > 0
        assert 0.0 <= out["step_frac"] <= 1.0
        assert out["data_wait_frac"] > out["h2d_put_frac"]
        total = sum(
            out[f"{p}_frac"]
            for p in ("data_wait", "h2d_put", "report", "checkpoint", "step")
        )
        assert abs(total - 1.0) < 1e-6

    def test_commit_vs_rollback_accounting(self):
        tl = Timeline(enabled=True)
        tl.uncommitted_s = 10.0
        tl.commit()
        assert tl.productive_s == 10.0 and tl.uncommitted_s == 0.0
        tl.uncommitted_s = 5.0
        tl.on_rollback(restore_s=1.0)
        assert tl.lost_s == 6.0 and tl.rollbacks == 1
        assert tl.uncommitted_s == 0.0
        # goodput = 10 / 16
        assert abs(tl.goodput_pct - 100.0 * 10.0 / 16.0) < 1e-9

    def test_restart_gap_charged(self):
        tl = Timeline(enabled=True)
        tl.productive_s = 30.0
        md = tl.to_metadata()
        tl2 = Timeline(enabled=True)
        tl2.load(md, now=md["saved_at"] + 12.0)
        assert tl2.productive_s == 30.0
        assert tl2.restarts == 1
        assert abs(tl2.restart_lost_s - 12.0) < 1e-9
        assert tl2.goodput_pct < 100.0

    def test_metadata_roundtrip(self):
        tl = Timeline(enabled=True)
        tl.productive_s, tl.lost_s, tl.rollbacks = 7.0, 3.0, 2
        tl.phase_totals["data_wait"] = 1.5
        md = tl.to_metadata()
        tl2 = Timeline(enabled=True)
        tl2.load(md, now=md["saved_at"])  # zero gap
        assert tl2.rollbacks == 2
        assert tl2.phase_totals["data_wait"] == 1.5
        assert tl2.lost_s == 3.0  # zero-gap restart adds nothing

    def test_foreign_ledger_rejected_on_warm_start(self):
        """A warm-started FORK restores the source trial's checkpoint
        under a new trial id: it must start a fresh ledger, not inherit
        the source's losses plus the save→fork wall gap as restart loss."""
        tl = Timeline(enabled=True)
        tl.productive_s, tl.lost_s, tl.rollbacks = 50.0, 20.0, 3
        md = tl.to_metadata(trial_id=7)
        fork = Timeline(enabled=True)
        fork.load(md, now=md["saved_at"] + 3600.0, trial_id=8)  # foreign
        assert fork.rollbacks == 0 and fork.lost_s == 0.0
        assert fork.goodput_pct == 100.0
        resume = Timeline(enabled=True)
        resume.load(md, now=md["saved_at"] + 1.0, trial_id=7)   # same trial
        assert resume.rollbacks == 3 and resume.restarts == 1

    def test_corrupt_metadata_never_raises(self):
        tl = Timeline(enabled=True)
        tl.load({"productive_s": "garbage"})
        tl.load({})

    def test_kill_switch(self, monkeypatch):
        monkeypatch.setenv("DTPU_TIMELINE", "0")
        assert Timeline().enabled is False
        monkeypatch.delenv("DTPU_TIMELINE")
        assert Timeline().enabled is True


class _DrillTrial:
    pass


def _make_trial():
    from determined_tpu.models import MnistMLP
    from determined_tpu.models.vision import MLPConfig
    from determined_tpu.trainer import JAXTrial

    class _T(JAXTrial):
        def build_model(self, mesh):
            return MnistMLP(
                MLPConfig(in_dim=8, hidden=16, n_classes=4), mesh=mesh
            )

        def build_optimizer(self):
            return optax.adam(1e-2)

        def build_training_data(self):
            rng = np.random.default_rng(0)
            while True:
                yield {
                    "image": rng.normal(size=(16, 8)).astype(np.float32),
                    "label": (np.arange(16) % 4).astype(np.int32),
                }

    return _T()


class TestTrainerIntegration:
    def test_goodput_survives_rollback_and_restart(self, tmp_path):
        """Acceptance drill: the ledger records a sentinel rollback as
        lost time, persists through a checkpoint, and a restarted trainer
        resumes the SAME ledger with the restart gap charged."""
        from determined_tpu import core as core_mod
        from determined_tpu.common.faults import (
            FaultPlan,
            FaultSpec,
            plan_active,
        )
        from determined_tpu.trainer import Batch, Trainer

        ctx = core_mod._context._dummy_init(checkpoint_storage=str(tmp_path))
        tr = Trainer(_make_trial(), ctx, health={"max_consecutive_skips": 2})
        tr.fit(max_length=Batch(3), report_period=Batch(1))
        tr._save_checkpoint(sync=True)
        tr.timeline.commit()
        with plan_active(FaultPlan({
            "train.nonfinite": FaultSpec(failures=2)
        })):
            tr.fit(max_length=Batch(8), report_period=Batch(1))
        assert tr.rollbacks == 1
        assert tr.timeline.rollbacks == 1
        assert tr.timeline.rollback_lost_s > 0
        assert 0.0 < tr.timeline.goodput_pct < 100.0
        ckpt = tr._save_checkpoint(sync=True)

        # process "restart": a fresh Trainer restores the checkpoint and
        # continues the same ledger
        ctx2 = core_mod._context._dummy_init(checkpoint_storage=str(tmp_path))
        tr2 = Trainer(_make_trial(), ctx2,
                      health={"max_consecutive_skips": 2})
        tr2.fit(max_length=Batch(10), report_period=Batch(2),
                latest_checkpoint=ckpt)
        assert tr2.timeline.rollbacks == 1       # carried over
        assert tr2.timeline.restarts == 1        # the resume itself
        assert tr2.timeline.restart_lost_s > 0   # save->restore gap
        assert 0.0 < tr2.timeline.goodput_pct < 100.0

    def test_profiling_group_carries_breakdown(self, tmp_path):
        from determined_tpu import core as core_mod
        from determined_tpu.trainer import Batch, Trainer

        ctx = core_mod._context._dummy_init(checkpoint_storage=str(tmp_path))
        tr = Trainer(_make_trial(), ctx)
        tr.fit(max_length=Batch(4), report_period=Batch(2))
        prof = [m for (g, s, m) in ctx.train._reported if g == "profiling"]
        assert prof, "no profiling-group timeline report"
        last = prof[-1]
        for key in ("data_wait_frac", "h2d_put_frac", "step_frac",
                    "goodput_pct", "productive_s", "lost_s"):
            assert key in last, key
        assert 0.0 < last["goodput_pct"] <= 100.0
        # training metrics still flow alongside
        assert any(g == "training" for (g, s, m) in ctx.train._reported)

    def test_timeline_disabled_skips_reports(self, tmp_path, monkeypatch):
        monkeypatch.setenv("DTPU_TIMELINE", "0")
        from determined_tpu import core as core_mod
        from determined_tpu.trainer import Batch, Trainer

        ctx = core_mod._context._dummy_init(checkpoint_storage=str(tmp_path))
        tr = Trainer(_make_trial(), ctx)
        assert tr.timeline.enabled is False
        tr.fit(max_length=Batch(2), report_period=Batch(1))
        assert not any(
            g == "profiling" for (g, s, m) in ctx.train._reported
        )
