"""Trial harness: entrypoint class → core.init() → Trainer.fit.

Rebuild of `harness/determined/exec/harness.py:24,134` (_run_pytorch_trial):
imports the trial class named by the experiment config's `entrypoint`
("pkg.module:TrialClass"), builds the Trainer from the config's searcher /
period / mesh sections, and runs to searcher completion. Exit code 0 on
clean finish or graceful preemption; nonzero on error (the master's restart
budget applies, trial.go:78).
"""
from __future__ import annotations

import importlib
import logging
import sys
from typing import Any, Dict, Optional

from determined_tpu import core
from determined_tpu.common import compile_cache
from determined_tpu.common import logship
from determined_tpu.common import profiling
from determined_tpu.common import trace
from determined_tpu.parallel.mesh import MeshConfig, make_mesh
from determined_tpu.trainer import Batch, Epoch, Trainer
from determined_tpu.trainer._units import TrainUnit

logger = logging.getLogger("determined_tpu.exec")


def import_entrypoint(entrypoint: str) -> Any:
    module_name, _, attr = entrypoint.partition(":")
    module = importlib.import_module(module_name)
    return getattr(module, attr)


def resolve_mesh(
    hparams: Dict[str, Any], cfg: Dict[str, Any], elastic: bool = False
):
    """Mesh from hparams beats config: lets a searcher sweep parallelism
    layouts (mesh autotuning — the platform's DeepSpeed-autotune analog).

    `elastic`: the gang was resized, so the configured layout may no
    longer fit the surviving device count — refit it (MeshConfig.refit:
    model-parallel degrees preserved, data/fsdp absorb the change) instead
    of erroring a gang that just survived a reclaim."""
    mesh_cfg = hparams.get("mesh") or cfg.get("mesh")
    if not mesh_cfg:
        return None
    mc = MeshConfig(**mesh_cfg)
    if elastic:
        import jax

        try:
            return make_mesh(mc)
        except ValueError:
            refitted = mc.refit(len(jax.devices()))
            logger.warning(
                "elastic resize: configured mesh %s does not fit %d "
                "device(s); refitted to %s", mesh_cfg, len(jax.devices()),
                refitted,
            )
            return make_mesh(refitted)
    return make_mesh(mc)


def parse_unit(spec: Any) -> Optional[TrainUnit]:
    """expconf-style length: {"batches": N} | {"epochs": N} | int (batches)."""
    if spec is None:
        return None
    if isinstance(spec, int):
        return Batch(spec)
    if "batches" in spec:
        return Batch(int(spec["batches"]))
    if "epochs" in spec:
        return Epoch(int(spec["epochs"]))
    raise ValueError(f"bad train-unit spec {spec!r}")


def run(entrypoint: str) -> int:
    import os

    plat = os.environ.get("DTPU_JAX_PLATFORM")
    if plat:
        # The experiment's `environment.jax_platform` pins this trial's
        # backend (dev clusters and the test suite run trials on the CPU);
        # jax.config wins over whatever JAX_PLATFORMS the agent inherited.
        import jax

        jax.config.update("jax_platforms", plat)
    info = core._context._info.get_cluster_info()
    # Persistent XLA compilation cache shared across an experiment's trials:
    # every ASHA rung re-jits the same program shapes, so later trials start
    # in seconds instead of recompiling (SURVEY.md §7.9 — net-new vs. the
    # reference, whose per-container torch processes had no analog).
    # `environment.compilation_cache_dir` is the per-experiment override,
    # below JAX_COMPILATION_CACHE_DIR (common/compile_cache.py).
    compile_cache.enable(
        (info.trial.config if info and info.trial else {}).get(
            "environment", {}
        ).get("compilation_cache_dir")
    )
    assert info is not None and info.trial is not None, "harness needs a trial env"

    # Continuous-profiling plane: when the master enabled it for this
    # allocation (DTPU_PROFILE=1 in the task env), every rank samples its
    # own stacks and ships folded windows back — identity trial:<t>.r<k>.
    rank = int(os.environ.get("DTPU_ALLOC_RANK", "0"))
    profiling.maybe_start_from_env(
        target=f"trial:{info.trial.trial_id}.r{rank}",
        master_url=info.master_url,
        token=info.session_token,
    )
    # Structured log plane (DTPU_LOG_SHIP=1): every record this rank logs
    # — harness, trainer, user trial code (root-logger attach) — ships as
    # a structured line tagged with the trial identity and the ambient
    # trace/span of the emitting thread.
    logship.maybe_start_from_env(
        target=f"trial:{info.trial.trial_id}.r{rank}",
        master_url=info.master_url,
        token=info.session_token,
        labels={
            "experiment": str(info.trial.experiment_id),
            "trial": str(info.trial.trial_id),
            "rank": str(rank),
            "task": str(info.task_id),
        },
    )

    # Elastic resize loop: a resize directive exits Trainer.fit with
    # ElasticResizeExit; this loop re-enters rendezvous under the new
    # generation (exec/prep_and_run.apply_resize), rebuilds the core
    # context + mesh + Trainer for the new world size, and resumes from
    # the survivors' last verified checkpoint — all inside the same
    # allocation and the same process. A rank DROPPED by the directive
    # exits 0 (the master ignores resized-away members' exits).
    resume_ckpt: Optional[str] = None
    resume_event = "restart"
    try:
        return _run_loop(entrypoint, resume_ckpt, resume_event)
    finally:
        # Ship the tail span batch NOW: trial.run (and any spans its
        # teardown produced) must reach the master's trace store before
        # this short-lived subprocess exits — atexit is the backstop, but
        # an exec'd or hard-exiting wrapper would skip it.
        trace.flush_shipper()
        profiling.flush_profiler()
        logship.flush_shipping()


def _run_loop(
    entrypoint: str,
    resume_ckpt: Optional[str],
    resume_event: str,
) -> int:
    import os

    from determined_tpu.trainer._trainer import ElasticResizeExit

    while True:
        info = core._context._info.get_cluster_info()
        assert info is not None and info.trial is not None
        cfg: Dict[str, Any] = info.trial.config
        trial_cls = import_entrypoint(entrypoint)
        trial = trial_cls(info.trial.hparams)

        # Any nonzero-generation identity is an elastic leg — including a
        # GROW NEWCOMER, a fresh process launched into a gang smaller (or
        # larger) than the configured mesh expects: it must refit too.
        elastic_leg = (
            resume_event == "resize"
            or int(os.environ.get("DTPU_ALLOC_GENERATION", "0")) > 0
        )

        scfg = cfg.get("searcher", {})
        try:
            # Trial lifecycle span: child of the DTPU_TRACEPARENT the launch
            # chain injected (master allocation span → agent launch span), and
            # the ambient parent of every Session call the trial makes — the
            # master's request spans for metric reports land in the SAME trace
            # as the `det experiment create` that submitted this work.
            with trace.span(
                "trial.run",
                {"trial.id": info.trial.trial_id, "task.id": info.task_id},
            ), core.init() as ctx:
                # Mesh AFTER core.init(): on TPU pods jax.distributed is
                # (re)initialized there, and the device set the mesh must
                # cover — especially after a resize changed the world —
                # only exists once that handshake is done. Building it
                # earlier would enumerate the previous topology's devices.
                mesh = resolve_mesh(
                    info.trial.hparams, cfg, elastic=elastic_leg
                )
                tb_dir = None
                if cfg.get("tensorboard", True):
                    import tempfile

                    tb_dir = os.path.join(
                        tempfile.gettempdir(), f"dtpu-tb-{info.task_id}"
                    )
                trainer = Trainer(
                    trial,
                    ctx,
                    mesh=mesh,
                    seed=info.trial.trial_seed,
                    searcher_metric=scfg.get("metric", "loss"),
                    smaller_is_better=bool(scfg.get("smaller_is_better", True)),
                    profiling=bool(cfg.get("profiling", {}).get("enabled", False)),
                    tensorboard_dir=tb_dir,
                    health=cfg.get("health"),
                    resume_event=resume_event,
                )
                # Emitted inside the trial.run span: the structured-log
                # plane tags this line with the lifecycle trace, so
                # `dtpu logs query --trace <id>` names the rank's entry.
                logger.info(
                    "trial %d rank %d entering fit (%s)",
                    info.trial.trial_id, int(os.environ.get(
                        "DTPU_ALLOC_RANK", "0")), resume_event,
                )
                trainer.fit(
                    validation_period=parse_unit(cfg.get("min_validation_period")),
                    checkpoint_period=parse_unit(cfg.get("min_checkpoint_period")),
                    report_period=parse_unit(cfg.get("scheduling_unit")) or Batch(10),
                    latest_checkpoint=resume_ckpt or info.trial.latest_checkpoint,
                )
            return 0
        except ElasticResizeExit as rz:
            # The `with` above already tore down the old gang's contexts
            # (ZMQ star, preemption watcher) on the way out.
            if rz.dropped:
                logger.info(
                    "elastic resize dropped this rank (%s); exiting cleanly",
                    rz.directive.get("reason", ""),
                )
                return 0
            _teardown_jax_distributed()
            from determined_tpu.exec import prep_and_run

            if not prep_and_run.apply_resize(info.master_url, rz.directive):
                return 0  # dropped (directive had no mapping for us)
            # Identity env changed (rank/world/generation/rendezvous):
            # the next core.init() must re-read it.
            core._context._info.reset_cluster_info_cache()
            resume_ckpt = rz.restore_from
            resume_event = "resize"
            continue
        except Exception as e:  # noqa: BLE001
            logger.exception("trial failed")
            _report_divergence(info, e)
            return 1


def _report_divergence(info, exc) -> None:
    """Name a replica-divergence audit failure to the master on the way
    down: the agent's exit report only carries 'exit code 1', so without
    this the cluster-level divergence counter (core.py
    SENTINEL_DIVERGENCE, watched by the shipped `replica_divergence`
    alert rule) could never move. Best-effort — a master that is already
    gone doesn't change the exit."""
    from determined_tpu.trainer._sentinel import ReplicaDivergenceError

    if not isinstance(exc, ReplicaDivergenceError) or info.trial is None:
        return
    try:
        from determined_tpu.common.api_session import Session

        Session(info.master_url, token=info.session_token).post(
            f"/api/v1/trials/{info.trial.trial_id}/status",
            json_body={"event": "divergence", "detail": str(exc)[:500]},
        )
    except Exception:  # noqa: BLE001 — reporting must not mask the exit
        logger.warning("could not report divergence to the master",
                       exc_info=True)


def _teardown_jax_distributed() -> None:
    """Best-effort shutdown of the jax coordination service before a
    resize re-init: on TPU pods the old service spans the old (broken)
    topology. On CPU gangs nothing was initialized (see
    _maybe_init_jax_distributed) and this is a no-op."""
    try:
        import jax

        jax.distributed.shutdown()
    except Exception:  # noqa: BLE001 — not initialized / backend quirk
        pass


def main() -> None:
    import os

    logging.basicConfig(level=logging.INFO)
    sys.exit(run(os.environ["DTPU_ENTRYPOINT"]))


if __name__ == "__main__":
    main()
