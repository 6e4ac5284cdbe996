"""Driver of `"kind": "train"` mixes: one `Trainer.fit`, as the trial
harness and `chip_smoke.py::child_sharded` call it.

The trial subclasses `SyntheticTrial` only to draw its batches from
`--seed` (and to have no validation set). The context is the program's
own off-cluster one with two parts replaced by subclasses that add
nothing to the program's work: a train context that stamps the clock
when a report arrives (the trainer reports right after the boundary
sync, `device_get` of the window's metrics), and a searcher that hands
out operations the way the master's does: one of a single batch (its
report is the first step's loss, which `correct` compares), then the
warm-up windows, then one report period at a time until `--seconds`
have passed since the window began. Ending through the searcher (a
`max_length` that grows) and not through `preempt.should_preempt`
keeps the exit checkpoint (18 GB for GPT-2 XL) out of the run.

Traffic file: `mesh` (MeshConfig axes), `global_batch`, `seq_len`,
`report_period`, `warmup_reports`, `lr`, `reference_rows_per_call`.
"""
from __future__ import annotations

import math
import time
from typing import Any, Dict, Iterator, List

import numpy as np


def run(h) -> Dict[str, Any]:
    import jax

    from benchmark import reference
    from benchmark.model import gpt_config_kwargs
    from determined_tpu import core
    from determined_tpu.core._searcher import (
        SearcherContext,
        SearcherOperation,
    )
    from determined_tpu.core._train import DummyTrainContext
    from determined_tpu.exec.builtin_trials import SyntheticTrial
    from determined_tpu.parallel.mesh import MeshConfig, make_mesh
    from determined_tpu.trainer import Batch, Trainer

    t = h.traffic
    rep = int(t["report_period"])
    warm_reports = int(t["warmup_reports"])
    batch_rows, seq = int(t["global_batch"]), int(t["seq_len"])
    first_batch: List[np.ndarray] = []

    class Trial(SyntheticTrial):
        def build_training_data(self) -> Iterator[Dict[str, Any]]:
            batches = self._batches(h.seed)
            while True:
                with h.span("data.next_batch"):
                    batch = next(batches)
                if not first_batch:
                    first_batch.append(batch["tokens"].copy())
                yield batch

        def build_validation_data(self):
            return []

    reports: List[Dict[str, Any]] = []   # one per training report
    timelines: List[Dict[str, Any]] = []

    class TrainContext(DummyTrainContext):
        def _report(self, group, steps_completed, metrics):
            now = time.perf_counter()
            with h.span("context.report"):
                if group == "training":
                    reports.append({"t": now, "step": int(steps_completed),
                                    "loss": metrics.get("loss")})
                    # The last warm-up report opens the window: set-up
                    # ends, the trace (if any) starts.
                    if len(reports) == 1 + warm_reports:
                        reports[-1]["t"] = h.window_begin()
                elif group == "profiling":
                    timelines.append({"step": int(steps_completed), **metrics})

    class Searcher(SearcherContext):
        def __init__(self, dist) -> None:  # noqa: super needs a session
            self._dist = dist

        def operations(self) -> Iterator[SearcherOperation]:
            length = 1
            yield SearcherOperation(None, 0, length, True)
            length = warm_reports * rep
            yield SearcherOperation(None, 0, length, True)
            while time.perf_counter() - h.t0 < h.seconds:
                length += rep
                with h.span("searcher.next_operation"):
                    op = SearcherOperation(None, 0, length, True)
                yield op

    model_kw = gpt_config_kwargs(h.config)
    trial = Trial({
        "model": "gpt2-small", "model_kw": model_kw,
        "seq_len": seq, "vocab_size": int(h.config["vocab_size"]),
        "batch_size": batch_rows, "lr": float(t.get("lr", 1e-3)),
    })
    ctx = core._context._dummy_init(
        checkpoint_storage=f"{h.scratch}/checkpoints")
    ctx.train = TrainContext()
    ctx.searcher = Searcher(ctx.distributed)
    mesh = make_mesh(MeshConfig(**t["mesh"]), devices=h.devices)
    with h.span("trainer.build"):
        trainer = Trainer(trial, ctx, mesh=mesh, seed=h.seed)
    with h.span("trainer.fit"):
        trainer.fit(report_period=Batch(rep))
    h.window_end()

    # -- what the window held -------------------------------------------
    begin = warm_reports        # index of the report that opened the window
    inside = reports[begin:]
    # the last operation may close after t0 + seconds; its window counts
    # (whole windows, by their own wall time).
    losses = [r["loss"] for r in reports]
    steps = inside[-1]["step"] - inside[0]["step"]
    wall = inside[-1]["t"] - inside[0]["t"]
    finite = all(x is not None and math.isfinite(x) for x in losses)

    # -- correct: the first step's loss against the float32 reference ----
    # The trainer's state is dropped first: the reference's copy of the
    # initial parameters (same key, same initializer) needs the room. It
    # is laid out over the cell's chips as the trainer lays out its own
    # (placement, not arithmetic: the reference stays plain float32
    # `jax.numpy`), because one chip that has just run XL's step cannot
    # hold 6.2 GB more.
    model, shardings = trainer.model, trainer._param_shardings()
    trainer._state = None
    del trainer
    with h.span("reference"):
        params = jax.jit(model.init, out_shardings=shardings)(
            jax.random.PRNGKey(h.seed))
        ref = reference.batch_loss(
            params, first_batch[0], int(t["reference_rows_per_call"]))
        del params
    check = reference.check_loss(float(losses[0]), ref)
    check["all_losses_finite"] = finite
    check["ok"] = bool(check["ok"] and finite)
    return {
        "kind": "train",
        "correct": check,
        "attempted": steps, "failed": 0 if finite else steps,
        "steps": steps, "wall_s": wall,
        "tokens_per_step": batch_rows * seq,
        "reports": inside, "timelines": [
            tl for tl in timelines if tl["step"] > inside[0]["step"]],
        "notes": {"reports_in_window": len(inside) - 1,
                  "window_losses": [losses[begin], losses[-1]],
                  "model_kw": {k: str(v) for k, v in model_kw.items()}},
    }
