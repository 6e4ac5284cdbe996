"""Driver of `"kind": "train_lm"` mixes: one `Trainer.fit` of a registry
language model built from the configuration's own keys.

`drivers/train.py` is GPT-2's: it names `gpt2-small`, translates
`n_embd`/`n_head` through `benchmark/model.py`, and compares with
`benchmark/reference.py`. This one is for every other architecture: the
configuration's `model_type` names the registry model and the reference
module (`MODELS` below), the model is built by
`get_model(<name>, **<the configuration's keys>)`, and the rest is
`drivers/train.py`'s run, stamp for stamp: the program's off-cluster
context with a train context that stamps the clock when a report
arrives and a searcher that hands out one batch (its report is the first
step's loss), then the warm-up windows, then one report period at a time
until `--seconds` have passed. `correct` compares that first loss, and
the gradient of the program's loss on the same batch at the initial
parameters (`jax.grad` of `model.loss`, what the train step
differentiates, at the timed sizes: the chunked rule, the blocked flash
kernels and the grouped matmuls as timed), with the float32 reference's,
leaf by leaf (`check` in the reference's module), outside the window. The records
have `"kind": "train"`, so the readers of the other training cells read
them unchanged. (A `benchmark` PR should fold the two drivers into one:
ROADMAP D13.)

Three things differ, all by the traffic file. `weights_seed` seeds the
trainer (its initial parameters); `--seed` draws the token batches and
nothing else, so seeds differ in order and not in work: with random
weights, WHICH experts a router favours is the weights', and a cell that
holds 32 of 512 would otherwise do a different amount of work a seed.
`lr_warmup_steps` ramps the rate from 0 to `lr` (0 or absent: none); it
is what keeps the seeds' work equal after the first step: at a constant
`lr` from step 1 the router retrains within ten steps and where it
settles follows the data order, so the rows the held experts get, and
the step's time with them, follow `--seed`. And the expert layer's
counters, which the model reports with its loss, are kept from every
report: `moe_held_rows_per_token` says how much work the held experts
did.

Traffic file: `mesh`, `global_batch`, `seq_len`, `report_period`,
`warmup_reports`, `lr`, `lr_warmup_steps`, `weights_seed`,
`trace_seconds`.
"""
from __future__ import annotations

import importlib
import math
import time
from typing import Any, Dict, Iterator, List

import numpy as np

#: model_type -> (registry name, the reference's module)
MODELS = {
    "qwen3_next": ("qwen3-next", "benchmark.reference_qwen3_next"),
}
#: Keys of a configuration file that describe it and configure nothing.
DOCUMENTATION = ("source", "changed", "assumed", "reduced", "deployment",
                 "published")
COUNTERS = ("moe_held_rows_per_token", "moe_load_max_over_mean")


def model_of(config: Dict[str, Any]):
    """(registry name, keyword arguments, reference module's name)."""
    name, reference = MODELS[config["model_type"]]
    return name, {k: v for k, v in config.items()
                  if k not in DOCUMENTATION}, reference


def trial_hparams(config: Dict[str, Any], traffic: Dict[str, Any],
                  global_batch: int) -> Dict[str, Any]:
    name, model_kw, _ = model_of(config)
    return {
        "model": name, "model_kw": model_kw,
        "seq_len": int(traffic["seq_len"]),
        "vocab_size": int(config["vocab_size"]),
        "batch_size": global_batch, "lr": float(traffic.get("lr", 1e-3)),
        "lr_warmup_steps": int(traffic.get("lr_warmup_steps", 0)),
    }


def run(h) -> Dict[str, Any]:
    import jax

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
    weights_seed = int(t["weights_seed"])
    reference = importlib.import_module(model_of(h.config)[2])
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
                    reports.append({
                        "t": now, "step": int(steps_completed),
                        "loss": metrics.get("loss"),
                        **{k: metrics[k] for k in COUNTERS if k in metrics}})
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

    trial = Trial(trial_hparams(h.config, t, batch_rows))
    ctx = core._context._dummy_init(
        checkpoint_storage=f"{h.scratch}/checkpoints")
    ctx.train = TrainContext()
    ctx.searcher = Searcher(ctx.distributed)
    mesh = make_mesh(MeshConfig(**t["mesh"]), devices=h.devices)
    with h.span("trainer.build"):
        trainer = Trainer(trial, ctx, mesh=mesh, seed=weights_seed)
    with h.span("trainer.fit"):
        trainer.fit(report_period=Batch(rep))
    h.window_end()

    # -- what the window held -------------------------------------------
    begin = warm_reports        # index of the report that opened the window
    inside = reports[begin:]
    losses = [r["loss"] for r in reports]
    steps = inside[-1]["step"] - inside[0]["step"]
    wall = inside[-1]["t"] - inside[0]["t"]
    finite = all(x is not None and math.isfinite(x) for x in losses)
    # a report's counters are the mean over its steps; the window's are
    # the mean over its whole report windows (inside[0] closed before it)
    counters = {
        k: float(np.mean([r[k] for r in inside[1:]]))
        for k in COUNTERS if len(inside) > 1 and k in inside[1]}

    # -- correct: loss and gradient against the float32 reference --------
    # The trainer's state is dropped first: the initial parameters again
    # (same key, same initializer), the program's gradient and then the
    # reference's need the device; both gradients wait on the host.
    model, shardings = trainer.model, trainer._param_shardings()
    memory_at_window_end = {
        k: v for k, v in (h.devices[0].memory_stats() or {}).items()
        if isinstance(v, int)}
    trainer._state = None
    del trainer
    with h.span("reference"):
        params = jax.jit(model.init, out_shardings=shardings)(
            jax.random.PRNGKey(weights_seed))
        tokens = first_batch[0]
        program_loss, program_grads = jax.device_get(jax.jit(
            jax.value_and_grad(
                lambda p, t: model.loss(p, {"tokens": t}, None)[0]))(
                    params, tokens))
        params = jax.device_get(params)   # the device is the reference's now
        ref_loss, ref_grads = reference.loss_and_gradient(
            params, tokens, h.config)
        gaps = reference.gradient_gaps(program_grads, ref_grads)
    check = reference.check(float(losses[0]), ref_loss, gaps)
    check["program_loss_outside_the_step"] = float(program_loss)
    check["all_losses_finite"] = finite
    check["ok"] = bool(check["ok"] and finite)
    return {
        "kind": "train",
        "correct": check,
        "attempted": steps, "failed": 0 if finite else steps,
        "steps": steps, "wall_s": wall,
        "tokens_per_step": batch_rows * seq,
        "counters": counters,
        "reports": inside, "timelines": [
            tl for tl in timelines if tl["step"] > inside[0]["step"]],
        "notes": {"reports_in_window": len(inside) - 1,
                  "window_losses": [losses[begin], losses[-1]],
                  "weights_seed": weights_seed, **counters,
                  # the allocator as the window closed (the harness's own
                  # `memory_stats` is taken after the reference)
                  "memory_at_window_end": memory_at_window_end,
                  # a report window's wall time, and what of it the
                  # trainer's host phases took (ms): where a slow run
                  # lost its time
                  "report_wall_ms": [
                      round(1e3 * (b["t"] - a["t"]), 1)
                      for a, b in zip(inside, inside[1:])],
                  "host_phase_ms": [
                      [round(1e3 * tl["window_s"] * tl.get(f"{p}_frac", 0.0), 1)
                       for p in ("data_wait", "h2d_put", "report")]
                      for tl in timelines if tl["step"] > inside[0]["step"]
                      and "window_s" in tl],
                  # the counter report by report: how far the routing
                  # drifts as the router trains (no balance loss)
                  "held_rows_by_report": [
                      round(r[COUNTERS[0]], 4) for r in reports
                      if COUNTERS[0] in r]},
    }
