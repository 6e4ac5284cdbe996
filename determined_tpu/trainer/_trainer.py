"""Trainer: the compiled training loop that drives a JAXTrial.

TPU-native rebuild of the reference's `_PyTorchTrialController` +
`Trainer.fit` (`harness/determined/pytorch/_pytorch_trial.py:176,546` and
`_trainer.py:16,65`). Same control shape — iterate searcher ops, train to
each op's length with periodic validation/checkpoint/report/preemption
boundaries, resume from the latest checkpoint — but the data plane is pure
XLA:

- one jitted train step (`donate_argnums` on the state: params/optimizer
  buffers update in place in HBM);
- parallelism is GSPMD over the trainer's Mesh: params sharded by the
  model's logical axes (fsdp/tensor/...), batches sharded over data×fsdp,
  gradients all-reduced by XLA over ICI — replacing the reference's
  horovod/DDP/DeepSpeed launch+allreduce stack;
- gradient aggregation (the reference's `aggregation_frequency`) is
  `optax.MultiSteps`; gradient clipping is part of the trial's optax chain;
- metrics stay on device between report boundaries (no per-step host sync —
  the reference pays a GPU→host copy every batch; we pay one per report
  period).
"""
from __future__ import annotations

import functools
import json
import logging
import os
import tempfile
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from determined_tpu import core as core_mod
from determined_tpu.common import faults
from determined_tpu.common import logship as logship_mod
from determined_tpu.common import profiling as profiling_mod
from determined_tpu.common import trace as trace_mod
from determined_tpu.core._searcher import DummySearcherContext
from determined_tpu.models.base import Model
from determined_tpu.parallel.mesh import batch_axes, make_mesh
from determined_tpu.parallel.sharding import (
    DEFAULT_RULES,
    ShardingRules,
    spec_for_pytree,
)
from determined_tpu.trainer import _checkpoint as ckpt_io
from determined_tpu.trainer import _sentinel
from determined_tpu.trainer import _timeline
from determined_tpu.trainer._trial import JAXTrial
from determined_tpu.trainer._units import Batch, TrainUnit, to_batches

logger = logging.getLogger("determined_tpu.trainer")

TRAINER_METADATA = "trainer_state.json"
ORBAX_SUBDIR = "orbax"  # presence marks an orbax/ocdbt-format checkpoint


class ElasticResizeExit(Exception):
    """Control-flow out of Trainer.fit: the master resized the gang (spot
    reclaim survived, or a grow back toward the requested size). The
    harness (exec/harness.py) catches this at the top of its resize loop,
    re-enters rendezvous under the directive's new generation, rebuilds
    the mesh for the new world size, and resumes from `restore_from` with
    every region resharded onto the new NamedShardings — same allocation,
    same process, restart budget untouched.

    `dropped`: this rank is absent from the directive's rank_map — it was
    resized away and must exit cleanly instead of re-entering."""

    def __init__(
        self,
        directive: Dict[str, Any],
        *,
        dropped: bool,
        restore_from: Optional[str],
    ) -> None:
        super().__init__(
            f"elastic resize to generation {directive.get('generation')} "
            f"({directive.get('num_processes')} processes)"
        )
        self.directive = directive
        self.dropped = dropped
        self.restore_from = restore_from


class Trainer:
    def __init__(
        self,
        trial: JAXTrial,
        core_context: Optional[core_mod.Context] = None,
        *,
        mesh: Optional[Mesh] = None,
        rules: ShardingRules = DEFAULT_RULES,
        seed: int = 0,
        searcher_metric: str = "loss",
        smaller_is_better: bool = True,
        profiling: bool = False,
        tensorboard_dir: Optional[str] = None,
        checkpoint_format: str = "npy",
        health: Optional[Dict[str, Any]] = None,
        resume_event: str = "restart",
    ) -> None:
        self.trial = trial
        self.core = core_context or core_mod.init()
        self.mesh = mesh if mesh is not None else make_mesh()
        self.rules = rules
        self.seed = seed
        # "npy": keypath-named .npy files + lazy per-device restore
        # (trainer/_checkpoint.py — transparent, multi-host shard-upload).
        # "orbax": orbax/ocdbt layout for JAX-ecosystem interchange (other
        # tools can open the checkpoint); restore places directly onto the
        # mesh via abstract ShapeDtypeStructs. Orbax's multi-host writers
        # assume one shared directory, which the upload-per-host storage
        # flow doesn't provide — hence single-process only.
        if checkpoint_format not in ("npy", "orbax"):
            # ValueError, not assert: user input must not silently fall
            # through to the npy path under python -O.
            raise ValueError(
                f"checkpoint_format {checkpoint_format!r} "
                "(one of: npy, orbax)"
            )
        if checkpoint_format == "orbax" and (
            jax.process_count() > 1 or self.core.distributed.size > 1
        ):
            raise ValueError(
                "checkpoint_format='orbax' is single-process only (orbax "
                "multi-host writes need one shared dir); use 'npy' for "
                "sharded multi-host checkpoints"
            )
        self.checkpoint_format = checkpoint_format
        self.searcher_metric = searcher_metric
        self.smaller_is_better = smaller_is_better

        # Training health sentinel (trainer/_sentinel.py): the `health:`
        # section of the experiment config when on-cluster, the `health`
        # kwarg off-cluster (tests/notebooks).
        if (
            health is None
            and self.core.info is not None
            and self.core.info.trial is not None
        ):
            health = (self.core.info.trial.config or {}).get("health")
        self.sentinel = _sentinel.SentinelConfig.from_config(health)
        self._spike = _sentinel.SpikeDetector(self.sentinel)
        self._steps_skipped = 0     # lifetime non-finite skips (host view)
        self._rollbacks = 0         # sentinel rollback-and-skip count
        self._skips = None          # device consecutive-skip scalar (fit)
        #: last checkpoint this process saved or restored — the rollback
        #: target. Collectively agreed: saves broadcast the storage_id.
        self._last_ckpt_id: Optional[str] = None
        #: batches the data stream is ahead of the step counter — the
        #: poisoned windows rollbacks skipped. Persisted in the trainer
        #: metadata so a process restart fast-forwards identically.
        self._data_offset = 0
        self._data_consumed = 0     # absolute batch cursor (fit-local)
        # Step-phase timer + goodput ledger (trainer/_timeline.py): phase
        # accumulators settle at report boundaries (no per-step host
        # sync); the ledger rides the trainer metadata across restarts.
        self.timeline = _timeline.Timeline()
        #: a rollback restore must NOT reload the checkpoint's ledger —
        #: the in-memory one is newer (it's about to record this rollback).
        self._restoring_for_rollback = False
        #: how the ledger classifies the save→resume gap on the first
        #: restore: "restart" (new process) or "resize" (elastic in-place
        #: resize — the harness rebuilt this Trainer after re-rendezvous;
        #: the gap is the drain→resume resize cost, charged to its own
        #: ledger bucket with the restart budget untouched).
        if resume_event not in ("restart", "resize"):
            raise ValueError(
                f"resume_event {resume_event!r} (one of: restart, resize)"
            )
        self._resume_event = resume_event

        self.model: Model = trial.build_model(self.mesh)
        self._tx = trial.build_optimizer()
        self._rng = jax.random.PRNGKey(seed)
        self._state: Optional[Dict[str, Any]] = None
        self._step_fn = None
        self._eval_fn = None
        self._ckpt_writer = ckpt_io.AsyncCheckpointWriter()
        # key -> (source array identity, placed device array): see
        # _put_batch's replicated-key caching.
        self._replicated_cache: Dict[str, Any] = {}
        # _put_batch host-overhead caches, filled on first batch:
        # NamedSharding construction walks the mesh and P() every call,
        # and the steady-state step loop calls _put_batch per key per
        # step — pure python overhead on the hot path. The mesh and the
        # trial's replicated-key contract never change after __init__, so
        # both resolve once and every later batch is dict/set lookups.
        self._batch_shardings: Optional[Tuple[Any, Any]] = None
        self._replicated_keys: Optional[frozenset] = None

        # Profiling plane: operator-triggered bounded XLA capture (one at
        # a time, chief-only) + the compiled step's cost_analysis FLOPs
        # (reported once under the profiling group → dtpu_step_flops).
        self._capture_dir: Optional[str] = None
        self._capture_id: Optional[str] = None
        self._capture_until: Optional[int] = None
        self._capture_storage: Optional[Dict[str, Any]] = None
        self._step_flops: Optional[float] = None

        # Observability (chief-only): system/device metrics to the master
        # (ref ProfilerAgent) + tfevents scalars for TensorBoard.
        self._profiler = None
        self._tb_writer = None
        self._tb_manager = None
        if self.core.distributed.is_chief:
            if profiling:
                from determined_tpu.profiler import ProfilerAgent

                self._profiler = ProfilerAgent(self.core.train)
            if tensorboard_dir:
                from determined_tpu.tensorboard import (
                    EventFileWriter,
                    TensorboardManager,
                )

                self._tb_writer = EventFileWriter(tensorboard_dir)
                storage = getattr(self.core.checkpoint, "_storage", None)
                task_id = getattr(self.core.checkpoint, "_task_id", "") or "local"
                if storage is not None:
                    self._tb_manager = TensorboardManager(
                        storage, task_id, tensorboard_dir
                    )

    def _tb_scalars(self, step: int, metrics: Dict[str, Any], prefix: str = "") -> None:
        if self._tb_writer is not None:
            self._tb_writer.add_scalars(
                step, {f"{prefix}{k}": v for k, v in metrics.items()}
            )

    def _tb_sync(self) -> None:
        if self._tb_writer is not None:
            self._tb_writer.flush()
        if self._tb_manager is not None:
            try:
                self._tb_manager.sync()
            except Exception:  # noqa: BLE001
                logger.exception("tensorboard sync failed")

    # -- profiling plane: operator-triggered XLA capture + step FLOPs -------
    def _begin_capture(self, cap: Dict[str, Any], step: int) -> None:
        """Start a bounded jax.profiler trace for a capture directive the
        master delivered on the progress beat. Never raises — a failed
        capture reports its error and training continues."""
        if self._capture_dir is not None:
            return  # one capture at a time; the directive stays delivered
        try:
            self._capture_dir = tempfile.mkdtemp(prefix="dtpu-xla-capture-")
            jax.profiler.start_trace(self._capture_dir)
            self._capture_id = str(cap.get("id", ""))
            self._capture_storage = cap.get("storage")
            self._capture_until = step + max(1, int(cap.get("steps", 3)))
            logger.info(
                "profile capture %s: tracing steps %d..%d",
                self._capture_id, step + 1, self._capture_until,
            )
        except Exception:  # noqa: BLE001 — profiling never breaks training
            logger.exception("profile capture start failed")
            self._report_capture(str(cap.get("id", "")), error="start failed")
            self._capture_dir = None
            self._capture_until = None

    def _finish_capture(self, step: int) -> None:
        """Stop the bounded trace, upload the artifact through the trial's
        storage manager (PR 1), register the link on the capture record."""
        cid, logdir = self._capture_id, self._capture_dir
        storage_cfg = self._capture_storage
        self._capture_dir = self._capture_id = None
        self._capture_until = self._capture_storage = None
        try:
            jax.block_until_ready(self._state)  # trace covers the steps
            jax.profiler.stop_trace()
        except Exception:  # noqa: BLE001
            logger.exception("profile capture stop failed")
            self._report_capture(cid, error="stop failed")
            return
        try:
            from determined_tpu.storage.base import from_config

            storage = getattr(self.core.checkpoint, "_storage", None)
            if storage is None or storage_cfg:
                storage = from_config(
                    storage_cfg, base_dir="/tmp/dtpu_captures"
                )
            storage_id = f"profile-capture-{cid}"
            storage.upload(logdir, storage_id)
            logger.info(
                "profile capture %s uploaded as %s (step %d)",
                cid, storage_id, step,
            )
            self._report_capture(cid, artifact=storage_id)
        except Exception as e:  # noqa: BLE001
            logger.exception("profile capture upload failed")
            self._report_capture(cid, error=f"upload failed: {e}")
        finally:
            import shutil

            shutil.rmtree(logdir, ignore_errors=True)

    def _report_capture(self, cid: Optional[str], artifact: str = "",
                        error: str = "") -> None:
        if not cid:
            return
        session = getattr(self.core.train, "_session", None)
        if session is None:
            return
        try:
            session.post(
                f"/api/v1/profiles/captures/{cid}/complete",
                json_body={"artifact": artifact, "error": error},
            )
        except Exception:  # noqa: BLE001 — registration loss is survivable
            logger.warning("capture %s completion report failed", cid)

    def _compute_step_flops(self, batch: Dict[str, Any],
                            poison: Any) -> float:
        """Per-step model FLOPs from XLA's cost_analysis of the already-
        compiled step (lower+compile hits the jit cache — no recompile).
        0.0 when the backend doesn't expose it; reported once."""
        try:
            lowered = self._step_fn.lower(
                self.state, batch, poison, self._skips
            )
            ca = lowered.compile().cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            if not isinstance(ca, dict):
                return 0.0
            return max(float(ca.get("flops", 0.0)), 0.0)
        except Exception:  # noqa: BLE001 — attribution, never a failure
            logger.debug("step cost_analysis failed", exc_info=True)
            return 0.0

    def _trial_id(self) -> int:
        """This run's trial identity (0 off-cluster) — the goodput
        ledger's ownership key across restarts."""
        if self.core.info is not None and self.core.info.trial is not None:
            return int(self.core.info.trial.trial_id)
        return 0

    # -- state construction -------------------------------------------------
    def _param_shardings(self) -> Any:
        specs = spec_for_pytree(self.model.logical_axes(), self.rules)
        return jax.tree.map(
            lambda spec: NamedSharding(self.mesh, spec),
            specs,
            is_leaf=lambda x: isinstance(x, P),
        )

    def _init_state(self) -> Dict[str, Any]:
        param_shardings = self._param_shardings()

        def init_fn(rng: jax.Array) -> Dict[str, Any]:
            params = self.model.init(rng)
            params = jax.lax.with_sharding_constraint(params, param_shardings)
            # The optimizer's params-shaped buffers (adam's mu/nu) take the
            # params' shardings by name: they are built by zeros_like, which
            # carries no data dependence for GSPMD to propagate a sharding
            # along, so left alone they come out REPLICATED — every device
            # holding the whole optimizer state until the first step
            # reshards it, and the step compiling twice (once for each
            # input layout).
            opt_state = optax.tree_utils.tree_map_params(
                self._tx, jax.lax.with_sharding_constraint,
                self._tx.init(params), param_shardings,
            )
            return {
                "step": jnp.zeros((), jnp.int32),
                "params": params,
                "opt_state": opt_state,
            }

        with self.mesh:
            return jax.jit(init_fn)(self._rng)

    def _zero_skips(self) -> jax.Array:
        """The consecutive-skip counter at zero, placed as the step returns
        it (replicated over the mesh): a bare `jnp.zeros` has another type
        to jit than the step's own output, and the step would trace and
        compile a second time on its second call."""
        return jax.device_put(
            jnp.zeros((), jnp.int32), NamedSharding(self.mesh, P())
        )

    @property
    def state(self) -> Dict[str, Any]:
        if self._state is None:
            self._state = self._init_state()
        return self._state

    @property
    def steps_completed(self) -> int:
        return int(jax.device_get(self.state["step"]))

    @property
    def steps_skipped(self) -> int:
        """Optimizer updates the non-finite guard skipped (host view;
        updated at report boundaries)."""
        return self._steps_skipped

    @property
    def rollbacks(self) -> int:
        """Sentinel rollback-and-skip events (consecutive-skip cap or
        loss spike)."""
        return self._rollbacks

    # -- compiled step -----------------------------------------------------
    def _build_step_fn(self):
        param_shardings = self._param_shardings()
        base_rng = self._rng

        def train_step(state, batch, poison, skips):
            rng = jax.random.fold_in(base_rng, state["step"])

            def loss_fn(params):
                loss, metrics = self.model.loss(params, batch, rng)
                # poison is 1.0 outside fault drills; a NaN or spike
                # factor rides the loss so the grads inherit it — the
                # wire shape of a poisoned batch (_sentinel fault sites).
                loss = loss * poison
                return loss, dict(metrics, loss=loss)

            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(state["params"])
            with jax.named_scope("optimizer"):
                updates, new_opt = self._tx.update(
                    grads, state["opt_state"], state["params"]
                )
                new_params = jax.tree.map(
                    lambda p, u: (p + u.astype(p.dtype)),
                    state["params"], updates,
                )
                new_params = jax.lax.with_sharding_constraint(
                    new_params, param_shardings
                )
                gnorm = optax_global_norm(grads)
                new_state = {
                    "step": state["step"] + 1,
                    "params": new_params,
                    "opt_state": new_opt,
                }
                # Non-finite guard, in-graph: a NaN/inf loss or grad norm
                # keeps the old params/optimizer (only the step advances)
                # and bumps the consecutive-skip counter. The counters ride
                # the device-resident metrics buffer — no host sync here.
                new_state, ok, skips_out = _sentinel.guarded_update(
                    state, new_state, loss, gnorm, skips
                )
            metrics = dict(
                metrics,
                grad_norm=gnorm,
                sentinel_skipped=(~ok).astype(jnp.int32),
                sentinel_skips=skips_out,
            )
            return new_state, metrics, skips_out

        return jax.jit(train_step, donate_argnums=(0,))

    def _build_eval_fn(self):
        def eval_step(params, batch):
            return self.model.eval_metrics(params, batch)

        return jax.jit(eval_step)

    # -- data placement ----------------------------------------------------
    def _put_batch(self, batch: Dict[str, Any]) -> Dict[str, Any]:
        # Shardings are resolved ONCE and reused across steps: building a
        # NamedSharding per key per step was measurable python overhead on
        # the steady-state loop, and both inputs (the mesh, the trial's
        # replicated-key contract) are fixed after __init__.
        if self._batch_shardings is None:
            self._batch_shardings = (
                NamedSharding(self.mesh, P(batch_axes())),
                NamedSharding(self.mesh, P()),
            )
        sharding, replicated = self._batch_shardings
        # Replication is a property of the TRIAL's batch contract, not the
        # trainer: trials declare which keys have no batch dim (default:
        # "positions", the zigzag layout's [S] position map — sharding it
        # over data axes would mis-inflate its global shape multi-host).
        # Read ONCE, like the shardings: the contract is fixed for the
        # trial's lifetime.
        if self._replicated_keys is None:
            self._replicated_keys = frozenset(getattr(
                self.trial, "replicated_batch_keys", frozenset({"positions"})
            ))
        replicated_keys = self._replicated_keys

        def put_with_key(key, x):
            if key in replicated_keys:
                # Cache per key+identity: these are CONSTANT across steps
                # (the dataset yields the same position array object every
                # batch), and on multi-host a fresh device_put of a
                # replicated array runs a cross-process equality check — a
                # host-sync collective that must not ride the steady-state
                # step loop. CONTRACT: replicated batch arrays must not be
                # mutated in place (yield a new array to change values —
                # an identity miss just re-places, it never breaks). The
                # DTPU_DEBUG mode verifies the contract each step.
                cached = self._replicated_cache.get(key)
                if cached is not None and cached[0] is x:
                    if os.environ.get("DTPU_DEBUG") and not np.array_equal(
                        np.asarray(x), np.asarray(cached[1])
                    ):
                        raise RuntimeError(
                            f"replicated batch key {key!r} was mutated in "
                            "place; yield a fresh array instead"
                        )
                    return cached[1]
                placed = jax.device_put(np.asarray(x), replicated)
                self._replicated_cache[key] = (x, placed)
                return placed
            x = np.asarray(x)
            if jax.process_count() == 1:
                return jax.device_put(x, sharding)
            # Multi-host: every process holds its local slice of the global
            # batch (the launch layer splits the stream by process index).
            return jax.make_array_from_process_local_data(sharding, x)

        return {k: jax.tree.map(lambda x: put_with_key(k, x), v)
                for k, v in batch.items()}

    # -- checkpoint --------------------------------------------------------
    def _save_checkpoint(self, *, sync: bool = False) -> Optional[str]:
        """Checkpoint the train state.

        Async by default: the step loop blocks only for the device→host
        snapshot (plus joining any still-running previous save); .npy
        serialization and the (possibly collective) storage upload run on a
        background thread. `sync=True` waits and returns the storage_id —
        used at preemption/exit where the process must not die with an
        upload in flight.
        """
        # Join any in-flight save BEFORE snapshotting: the old snapshot is
        # still referenced by its work() closure, and holding two full host
        # copies of model+optimizer state can OOM the host.
        self._ckpt_writer.wait()
        steps = self.steps_completed
        use_orbax = self.checkpoint_format == "orbax"
        if use_orbax:
            # Full host copy (nested, not keypath-flat): orbax serializes
            # the tree itself. device_get BEFORE submit — the step loop
            # donates the device buffers.
            snapshot = jax.device_get(self.state)
        else:
            snapshot = ckpt_io.snapshot_pytree(self.state)
        sharded = jax.process_count() > 1 or self.core.distributed.size > 1
        is_chief = self.core.distributed.is_chief
        checkpoint_ctx = self.core.checkpoint
        seed = self.seed
        data_offset = self._data_offset
        # Ledger snapshot at submit time (the work() closure runs on the
        # writer thread while the step loop keeps mutating the live one).
        timeline_md = self.timeline.to_metadata(trial_id=self._trial_id())

        def work() -> str:
            with tempfile.TemporaryDirectory() as tmp:
                if use_orbax:
                    import orbax.checkpoint as ocp

                    ckptr = ocp.StandardCheckpointer()
                    ckptr.save(os.path.join(tmp, ORBAX_SUBDIR), snapshot)
                    ckptr.wait_until_finished()
                    ckptr.close()
                    written = None  # recursive walk picks up ocdbt layout
                else:
                    written = ckpt_io.write_snapshot(snapshot, tmp)
                if is_chief:
                    with open(os.path.join(tmp, TRAINER_METADATA), "w") as f:
                        json.dump(
                            {
                                "steps_completed": steps,
                                "seed": seed,
                                # Sentinel rollbacks leave the data stream
                                # ahead of the step counter (poisoned
                                # windows skipped); a restart must fast-
                                # forward the same distance (fit()).
                                "data_offset": data_offset,
                                # Goodput ledger: a restart resumes the
                                # SAME accounting (save→restore gap is
                                # charged as restart loss on load).
                                "timeline": timeline_md,
                            },
                            f,
                        )
                    if written is not None:
                        written.append(TRAINER_METADATA)
                storage_id = checkpoint_ctx.upload(
                    tmp,
                    metadata={"steps_completed": steps},
                    shard=sharded,
                    paths=written,
                )
            logger.info("saved checkpoint %s at step %d", storage_id, steps)
            # The rollback target: collectively agreed (the sharded
            # upload broadcasts one storage_id to every rank).
            self._last_ckpt_id = storage_id
            return storage_id

        self._ckpt_writer.submit(work)
        if sync:
            return self._ckpt_writer.wait()
        return None

    def _restore_with_fallback(self, storage_id: str) -> None:
        """Restore `storage_id`; on CorruptCheckpointError (torn write,
        checksum mismatch, incomplete shards) walk back to the newest
        earlier checkpoint that verifies, rather than dying on state the
        platform can route around. Off-cluster there is no checkpoint
        registry — the corruption propagates.

        On a multi-process gang this is a COLLECTIVE: the chief's
        candidate list is broadcast (divergent per-rank listings under a
        flaky master must not send ranks down different chains), and after
        each attempt the ranks agree — all restored, or everyone moves to
        the next candidate together. A rank must never train on state its
        peers rejected."""
        from determined_tpu.storage.base import CorruptCheckpointError

        dist = self.core.distributed
        gang = dist.size > 1
        if gang:
            candidates = dist.broadcast(
                self.core.checkpoint.restore_candidates(storage_id)
                if dist.is_chief else None
            )
        else:
            candidates = self.core.checkpoint.restore_candidates(storage_id)
        last_err: Optional[Exception] = None
        for uuid_ in candidates:
            my_err: Optional[Exception] = None
            # Everything is caught here so a failing rank still reaches
            # the gather below — an uncaught exception on one rank would
            # strand its peers in the unbounded collective recv. Only
            # corruption and storage-level failures are fallback-able;
            # anything else aborts the WHOLE gang after the agreement
            # round (no rank may train on state its peers rejected).
            try:
                self._restore_checkpoint(uuid_)
                status = "ok"
            except (CorruptCheckpointError, OSError) as e:
                my_err, status = e, "fallback"
            except Exception as e:  # noqa: BLE001 — re-raised post-gather
                my_err, status = e, "fatal"
            if gang:
                statuses = dist.gather(status)
                decision = dist.broadcast(
                    (
                        "fatal" if "fatal" in statuses
                        else "ok" if all(s == "ok" for s in statuses)
                        else "fallback"
                    )
                    if dist.is_chief else None
                )
            else:
                decision = status
            if decision == "ok":
                if uuid_ != storage_id:
                    logger.warning(
                        "resumed from older verified checkpoint %s (newest "
                        "%s was corrupt)", uuid_, storage_id,
                    )
                return
            if decision == "fatal":
                if my_err is not None and status == "fatal":
                    raise my_err
                raise RuntimeError(
                    f"a peer rank failed restoring checkpoint {uuid_} with "
                    "a non-recoverable error"
                )
            last_err = my_err or CorruptCheckpointError(
                f"a peer rank failed verification of checkpoint {uuid_}"
            )
            logger.error(
                "checkpoint %s failed verification (%s); %s", uuid_, last_err,
                "trying the previous verified checkpoint"
                if uuid_ != candidates[-1] else "no older checkpoint left",
            )
        assert last_err is not None
        raise last_err

    def _restore_checkpoint(self, storage_id: str) -> None:
        self._ckpt_writer.wait()  # never read while a save is in flight
        state = self.state  # materialize to know structure + shardings
        with self.core.checkpoint.restore_path(storage_id) as path:
            orbax_dir = os.path.join(path, ORBAX_SUBDIR)
            if os.path.isdir(orbax_dir):
                # Format is a property of the CHECKPOINT, not the config:
                # a trial restarted with a different checkpoint_format must
                # still restore what it saved.
                import orbax.checkpoint as ocp

                abstract = jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(
                        x.shape, x.dtype, sharding=x.sharding
                    ),
                    state,
                )
                ckptr = ocp.StandardCheckpointer()
                self._state = ckptr.restore(orbax_dir, abstract)
                ckptr.close()
            else:
                shardings = jax.tree.map(lambda x: x.sharding, state)
                self._state = ckpt_io.load_pytree(path, state, shardings)
            md_path = os.path.join(path, TRAINER_METADATA)
            self._data_offset = 0
            if os.path.exists(md_path):
                try:
                    with open(md_path) as f:
                        md = json.load(f)
                    self._data_offset = int(md.get("data_offset", 0) or 0)
                    tl_md = md.get("timeline")
                    if tl_md and not self._restoring_for_rollback:
                        # Process restart/resume: continue the persisted
                        # goodput ledger. A rollback restore skips this —
                        # its in-memory ledger is newer than the
                        # checkpoint's. load() itself rejects foreign
                        # ledgers (warm-started fork = different trial id).
                        # The event class routes the save→resume gap into
                        # restart_lost_s vs resize_lost_s.
                        self.timeline.load(
                            tl_md, trial_id=self._trial_id(),
                            event=self._resume_event,
                        )
                        # One-shot: only the FIRST resume gap carries the
                        # resize classification.
                        self._resume_event = "restart"
                except (ValueError, OSError):
                    logger.warning(
                        "unreadable trainer metadata in %s; assuming no "
                        "data offset", storage_id,
                    )
        self._last_ckpt_id = storage_id  # verified by the restore above
        logger.info(
            "restored checkpoint %s at step %d", storage_id, self.steps_completed
        )

    # -- validation --------------------------------------------------------
    def _validate(self) -> Dict[str, float]:
        if self._eval_fn is None:
            self._eval_fn = self._build_eval_fn()
        totals: Dict[str, float] = {}
        n = 0
        for batch in self.trial.build_validation_data():
            metrics = self._eval_fn(self.state["params"], self._put_batch(batch))
            metrics = jax.device_get(metrics)
            for k, v in metrics.items():
                totals[k] = totals.get(k, 0.0) + float(v)
            n += 1
        if n == 0:
            return {}
        return {k: v / n for k, v in totals.items()}

    # -- training health sentinel (trainer/_sentinel.py) -------------------
    def _sentinel_check(self, pending: List[Any]) -> Optional[str]:
        """Flush-time sentinel pass over the window's device metrics.
        Materializes ONLY the per-step loss and skip counters (the full
        metrics flush is chief-only), accumulates the skip total,
        and returns a rollback reason when the consecutive-skip cap or
        the loss-spike z-score trips — None otherwise. Every rank runs
        this on identical replicated scalars, so the gang reaches the
        same verdict with no extra collective."""
        if not pending:
            return None
        cfg = self.sentinel
        keys = ("loss", "sentinel_skipped", "sentinel_skips")
        sent = jax.device_get(
            [{k: m[k] for k in keys if k in m} for m in pending]
        )
        window_skips = sum(int(m.get("sentinel_skipped", 0)) for m in sent)
        if window_skips:
            self._steps_skipped += window_skips
            logger.warning(
                "non-finite guard skipped %d step(s) this window "
                "(%d total)", window_skips, self._steps_skipped,
            )
        consecutive = int(sent[-1].get("sentinel_skips", 0))
        if cfg.max_consecutive_skips and consecutive >= cfg.max_consecutive_skips:
            return (
                f"{consecutive} consecutive non-finite steps "
                f"(max_consecutive_skips={cfg.max_consecutive_skips})"
            )
        if self._spike.enabled:
            for m in sent:
                if "loss" in m and self._spike.observe(float(m["loss"])):
                    return (
                        f"loss spike {float(m['loss']):.4g} beyond "
                        f"robust z-score {cfg.spike_zscore}"
                    )
        return None

    def _sentinel_rollback(self, reason: str, at_step: int) -> Optional[int]:
        """PaLM-style rollback-and-skip: restore the last verified
        checkpoint (PR 1's manifest-verified fallback chain) and leave
        the data stream where it is — the batches between the restored
        step and `at_step` ARE the poisoned window, skipped forever via
        the recorded data offset. Returns the restored step, or None when
        no checkpoint exists yet (the in-graph guard already kept the
        params clean; training continues in place with counters reset)."""
        try:
            self._ckpt_writer.wait()  # a save in flight may be the target
        except BaseException:  # noqa: BLE001 — rollback must still proceed
            logger.exception("in-flight checkpoint failed before rollback")
        target = self._last_ckpt_id
        if target is None:
            logger.error(
                "sentinel wants a rollback (%s) but no checkpoint exists "
                "yet; continuing with guarded params only", reason,
            )
            self._skips = self._zero_skips()
            self._spike.reset()
            return None
        logger.warning(
            "sentinel rollback at step %d: %s — restoring %s and skipping "
            "the poisoned data window", at_step, reason, target,
        )
        _t0 = self.timeline.pc()
        self._restoring_for_rollback = True
        try:
            with trace_mod.span("trial.rollback", {"reason": reason}):
                self._restore_with_fallback(target)
        finally:
            self._restoring_for_rollback = False
        # Ledger: the uncommitted window time trained state this restore
        # just discarded; the restore itself is pure overhead too.
        self.timeline.on_rollback(self.timeline.pc() - _t0)
        self._rollbacks += 1
        restored = self.steps_completed
        # The stream is NOT rewound: everything consumed past the restored
        # step stays consumed, which is exactly "skip the offending
        # batches". Recorded so checkpoints replay the same decision.
        self._data_offset = self._data_consumed - restored
        self._skips = self._zero_skips()
        self._spike.reset()
        logger.warning(
            "sentinel rollback done: step %d, data stream fast-forwarded "
            "%d batch(es) ahead (rollback #%d)",
            restored, self._data_offset, self._rollbacks,
        )
        return restored

    def _exit_for_resize(self, directive: Dict[str, Any], step: int) -> None:
        """Leave the step loop at this report boundary for an elastic
        resize: raise ElasticResizeExit carrying the directive and this
        gang's collectively-agreed last verified checkpoint (the reshard
        source). Uncommitted window time since that checkpoint is
        discarded by the resize — the resumed ledger charges the whole
        drain→resume wall gap as resize loss, which covers it."""
        rank = self.core.distributed.rank
        dropped = str(rank) not in (directive.get("rank_map") or {})
        if dropped and directive.get("resync_only"):
            # Unmappable straggler (directive history rotated out): exit
            # NONZERO — a clean exit from a rank the master still counts
            # as a member would complete the trial as finished work.
            raise RuntimeError(
                "resize directive could not map this rank (generation "
                f"{directive.get('generation')}); erroring out for re-sync"
            )
        logger.warning(
            "elastic resize at step %d: generation %s, %s process(es) "
            "(%s) — rank %d %s",
            step, directive.get("generation"),
            directive.get("num_processes"), directive.get("reason", ""),
            rank,
            "was DROPPED; exiting for re-sync" if dropped
            else "exits the step loop to reshard",
        )
        if self._ckpt_writer.in_flight and self.core.distributed.size > 1:
            # An in-flight SHARDED save runs collectives against peers that
            # may already be dead (that is WHY we are resizing): fit's
            # teardown join would hang forever on the chief's gather from
            # the reclaimed rank. Closing the control plane fails the
            # collective fast (ipc inbox.die wakes blocked waiters); the
            # torn upload is harmless — manifest-last commit means it never
            # verifies, and restore_from targets the last VERIFIED id.
            self.core.distributed.close()
            try:
                self._ckpt_writer.wait()
            except BaseException as e:  # noqa: BLE001 — expected abort
                logger.warning(
                    "in-flight checkpoint abandoned by the resize: %s", e
                )
        raise ElasticResizeExit(
            directive, dropped=dropped, restore_from=self._last_ckpt_id
        )

    def _divergence_audit(self) -> None:
        """Replica-divergence audit: deterministic per-shard checksums of
        the params, compared across every holder of the same logical
        region (data-parallel replicas, local and cross-host). A mismatch
        is silent data corruption — error the trial naming the offending
        rank/device rather than train on (or checkpoint) corrupt state."""
        dist = self.core.distributed
        sums = _sentinel.local_shard_checksums(self.state["params"])
        if _sentinel.divergence_fault(dist.rank):
            # Deterministic drill (DTPU_FAULT_PLAN train.divergence.rank<r>):
            # corrupt ONE device's checksum on this rank — the audit must
            # flag exactly this holder.
            key = next(iter(sums), None)
            if key is not None and sums[key]:
                device, (a, b) = sums[key][-1]
                sums[key] = sums[key][:-1] + [(device, (a + 1.0, b))]
        gathered = dist.gather((dist.rank, sums))
        verdict = dist.broadcast(
            _sentinel.compare_checksums(gathered)
            if dist.is_chief else None
        )
        if verdict:
            raise _sentinel.ReplicaDivergenceError(verdict)

    # -- the loop ----------------------------------------------------------
    def fit(
        self,
        *,
        max_length: Optional[TrainUnit] = None,
        validation_period: Optional[TrainUnit] = None,
        checkpoint_period: Optional[TrainUnit] = None,
        report_period: TrainUnit = Batch(10),
        latest_checkpoint: Optional[str] = None,
    ) -> Dict[str, float]:
        """Run the trial until the searcher closes it (or max_length off-cluster).

        Returns the last validation metrics. Mirrors pytorch.Trainer.fit
        (`_trainer.py:65`): periods are trainer-config, lengths come from
        searcher ops.
        """
        bpe = self.trial.batches_per_epoch
        val_period = to_batches(validation_period, bpe) if validation_period else 0
        ckpt_period = to_batches(checkpoint_period, bpe) if checkpoint_period else 0
        rep_period = max(1, to_batches(report_period, bpe))

        # Off-cluster: a single dummy searcher op of max_length batches.
        searcher = self.core.searcher
        if max_length is not None and isinstance(searcher, DummySearcherContext):
            searcher = DummySearcherContext(
                self.core.distributed, length=to_batches(max_length, bpe)
            )

        if (
            latest_checkpoint is None
            and self.core.info is not None
            and self.core.info.trial is not None
        ):
            latest_checkpoint = self.core.info.trial.latest_checkpoint
        if latest_checkpoint:
            if self._resume_event == "resize":
                # Drillable branch (DTPU_FAULT_PLAN `resize.restore`): a
                # failure HERE errors this rank's process, and the master's
                # elastic layer sheds the rank with infra attribution — the
                # resize path must degrade into another resize, never a
                # budget charge.
                faults.inject("resize.restore")
            self._restore_with_fallback(latest_checkpoint)

        if self._step_fn is None:
            self._step_fn = self._build_step_fn()

        # Fast-forward the stream past batches consumed before the restored
        # checkpoint, so resumed training sees the same data order as an
        # uninterrupted run (ref: pytorch/samplers.py skip-batch samplers).
        # Datasets exposing .skip(n_batches) (TokenDataset, the native
        # loader) fast-forward in O(1); otherwise assemble-and-discard.
        train_data = self.trial.build_training_data()
        resume_steps = self.steps_completed
        # Fast-forward distance = steps trained + the data offset from any
        # sentinel rollbacks before the checkpoint (poisoned windows the
        # stream skipped past): batch i depends only on (seed, i), so the
        # resumed stream is identical to the uninterrupted one.
        fast_forward = resume_steps + self._data_offset
        skipped = False
        if fast_forward and hasattr(train_data, "skip"):
            # In-place contract: skip() mutates and returns None (our
            # datasets) or self (fluent style) — both count as skipped.
            # A skip() returning a NEW object (e.g. tf.data's, which is
            # non-mutating and counts elements rather than batches) falls
            # back to discard; the probe was a no-op on the original, so
            # the fallback never double-skips.
            result = train_data.skip(fast_forward)
            if result is None or result is train_data:
                skipped = True
        train_iter = iter(train_data)
        if not skipped:
            for _ in range(fast_forward):
                next(train_iter)
        self._data_consumed = fast_forward
        pending: List[Any] = []  # on-device metrics since last report
        last_val: Dict[str, float] = {}
        t_report = time.time()
        preempted = False

        timeline = self.timeline

        def flush_report() -> None:
            # One `report` phase (trainer/_timeline.py) around the whole
            # flush, untimed: only the publishing calls add to the
            # window, the syncs before them are device time and stay in
            # the `step` residual. Nothing pending (a flush right after a
            # boundary's own): nothing to do, and no empty span.
            if pending:
                with timeline.phase("report", timed=False):
                    _flush_report()

        def _flush_report() -> None:
            nonlocal pending, t_report
            # Sentinel sees EVERY window before it is dropped — flushes
            # also happen at checkpoint/preemption/op-end boundaries that
            # are not report boundaries, and a spike (or skip count) in
            # such a window must not vanish unchecked. The verdict is
            # latched and consumed at the next boundary's rollback gate.
            with timeline.phase("report.sync", timed=False):
                with timeline.boundary_wait():
                    reason = self._sentinel_check(pending)
                if reason and self._sentinel_reason is None:
                    self._sentinel_reason = reason
                host = (
                    [jax.device_get(m) for m in pending]
                    if self.core.distributed.is_chief else []
                )
            if not host:
                pending = []
                if timeline.enabled:
                    # _sentinel_check just blocked on the device, so the
                    # window residual includes the jitted steps — the one
                    # sync the timeline is allowed to piggyback on.
                    timeline.close_window()
                return
            # Aggregate over FINITE values only: a guarded (skipped) step
            # leaves NaN in loss/grad_norm, and a NaN mean would both
            # poison the metric history and break the metrics POST (NaN
            # is not valid JSON — the master 500s, the circuit breaker
            # opens, and the trial dies reporting). A window with no
            # finite values drops the key; sentinel_skipped still tells
            # the story.
            agg = {}
            for k in host[0]:
                if np.ndim(host[0][k]) != 0:
                    continue
                vals = np.asarray([float(h[k]) for h in host], np.float64)
                finite = vals[np.isfinite(vals)]
                if finite.size:
                    agg[k] = float(finite.mean())
            dt = time.time() - t_report
            agg["batches_per_second"] = len(host) / dt if dt > 0 else 0.0
            self._last_throughput = agg["batches_per_second"]
            # Robustness tax, cumulative: how many updates the guard
            # dropped and how often the sentinel rolled back (the metrics
            # history reads these).
            agg["steps_skipped"] = float(self._steps_skipped)
            agg["rollbacks"] = float(self._rollbacks)
            steps_now = self.steps_completed
            with timeline.phase("report.publish"):
                self.core.train.report_training_metrics(steps_now, agg)
                self._tb_scalars(steps_now, agg)
            if timeline.enabled:
                # Settle the window (the device_get above was the sync),
                # then ship the step-phase breakdown + goodput ledger
                # under the `profiling` group — the same channel the
                # ProfilerAgent uses, so the WebUI/SDK read both together.
                fractions = timeline.close_window()
                prof = {**fractions, **timeline.snapshot()}
                if self._step_flops:
                    # XLA's per-step model FLOPs (cost_analysis of the
                    # compiled step) → master's dtpu_step_flops gauge.
                    prof["step_flops"] = self._step_flops
                with timeline.phase("report.publish", timed=False):
                    self.core.train.report_metrics(
                        "profiling", steps_now, prof
                    )
            if self._profiler is not None:
                self._profiler.set_steps_completed(steps_now)
            pending = []
            t_report = time.time()

        # Host-side step counter: one device sync here, none in the loop —
        # reading state["step"] per batch would block on the in-flight step
        # and kill host/device overlap.
        step = self.steps_completed
        last_ckpt_step = -1
        self._skips = self._zero_skips()
        self._sentinel_reason: Optional[str] = None
        last_div_audit = step
        # First progress beat (every rank): arms the master's gang stall
        # watchdog with this rank's identity before the first boundary.
        self.core.train.heartbeat_step(step)
        if self._profiler is not None:
            self._profiler.start()
        # Trial-lifecycle span: parents under the launch chain's
        # DTPU_TRACEPARENT (ambient via common/trace.py), so the fit loop
        # appears inside the submit trace.
        import contextlib as _contextlib

        _fit_scope = _contextlib.ExitStack()
        _fit_scope.enter_context(
            trace_mod.span("trial.fit", {"resume_step": resume_steps})
        )
        # First-step anchor for the trace plane's lifecycle critical path
        # (submit→…→first_step, master/tracestore.py): exported the moment
        # the first step's dispatch returns — jit compilation happens
        # synchronously inside that first call, so this span IS the
        # compile + dispatch cost. One int compare per step afterwards.
        _first_step_ctx = trace_mod.current()
        _first_step_t0 = time.time()
        _first_step_at = step + 1
        timeline.reset_window()
        # Continuous-profiling phase tag: the sampler (common/profiling.py)
        # reads this thread's phase on every walk, so flamegraphs split by
        # data_wait / h2d_put / step / report / checkpoint for free. Every
        # `timeline.phase` below puts "step" back when it ends.
        profiling_mod.set_phase("step")

        # The finally-join below keeps a raising step loop from abandoning
        # an in-flight background save: the daemon writer thread would
        # otherwise run its checkpoint-channel collectives against a core
        # context the caller is already tearing down, and its failure (or a
        # half-registered checkpoint) would go unreported.
        timeline.hook_gc()
        try:
            fit_error = None
            operations = iter(searcher.operations())
            op = next(operations, None)
            while op is not None:
                target = to_batches(op.length, bpe)
                while step < target:
                    with timeline.phase("data_wait"):
                        raw = next(train_iter)
                    with timeline.phase("h2d_put"):
                        batch = self._put_batch(raw)
                    self._data_consumed += 1
                    # poison: 1.0 outside fault drills (one None check);
                    # np scalar, not python float, so jit sees a stable
                    # weak-typed operand either way.
                    poison = np.float32(_sentinel.poison_factor())
                    self._state, metrics, self._skips = self._step_fn(
                        self.state, batch, poison, self._skips
                    )
                    if timeline.boundary is not None:
                        # the dispatch was the boundary's last part
                        timeline.end_boundary()
                    pending.append(metrics)
                    step += 1
                    if (
                        self._capture_until is not None
                        and step >= self._capture_until
                    ):
                        self._finish_capture(step)
                    if step == _first_step_at and _first_step_ctx is not None:
                        _first_step_at = -1
                        trace_mod.export_span(
                            "trial.first_step",
                            trace_id=_first_step_ctx[0],
                            span_id=trace_mod.new_span_id(),
                            parent_span_id=_first_step_ctx[1],
                            start=_first_step_t0, end=time.time(),
                            attributes={"step": step},
                        )

                    boundary = step % rep_period == 0 or step == target
                    if boundary:
                        # flush_report runs the sentinel pass over the
                        # window (same verdict on every rank — the inputs
                        # are replicated outputs of the SPMD step, so no
                        # extra collective); the latched verdict gates
                        # the rollback below. The boundary's span runs
                        # from here to the next step's dispatch.
                        timeline.begin_boundary()
                        flush_report()
                        rollback_reason = self._sentinel_reason
                        self._sentinel_reason = None
                        with timeline.span("boundary.control"):
                            # Progress beat from EVERY rank: the master's
                            # stall watchdog kills the gang when this
                            # counter stops advancing (hung collective →
                            # bounded-time recovery instead of
                            # forever-stuck). The response doubles as the
                            # elastic resize channel: a pending directive
                            # rides back when the master resized the gang
                            # past this rank's generation.
                            beat_resize = self.core.train.heartbeat_step(step)
                            if self.core.distributed.is_chief:
                                op.report_progress(float(step))
                                # Operator-triggered XLA capture rides the
                                # beat response (chief-only: one trace per
                                # trial).
                                cap = self.core.train.take_profile_capture()
                                if cap is not None:
                                    self._begin_capture(cap, step)
                            # Preemption is a collective (ZMQ broadcast) —
                            # checking every batch would put a TCP
                            # roundtrip in the hot loop, so it shares the
                            # report boundary (the reference's analog knob
                            # is scheduling_unit granularity). Elastic
                            # resize rides the SAME collective (the chief
                            # folds the boundary beat's directive hint into
                            # the broadcast), so every rank reaches the
                            # same resize verdict at the same boundary —
                            # and it MUST be the boundary's FIRST
                            # gather-shaped action: once a peer is dead,
                            # any other collective (joining an in-flight
                            # sharded save, a rollback restore's agreement
                            # round, the divergence audit) would hang on it
                            # forever. The resize exit is also allowed to
                            # supersede a latched sentinel rollback: both
                            # restore the same last verified checkpoint,
                            # the resize just does it on the new mesh.
                            preempt_now = self.core.preempt.should_preempt(
                                resize_hint=beat_resize
                            )
                            directive = self.core.preempt.take_resize()
                        if directive is not None:
                            self._exit_for_resize(directive, step)
                        if (
                            self._step_flops is None
                            and self.core.distributed.is_chief
                        ):
                            with timeline.span("boundary.step_flops"):
                                self._step_flops = self._compute_step_flops(
                                    batch, poison
                                )
                        if preempt_now:
                            flush_report()
                            with timeline.phase("checkpoint", timed=False):
                                self._save_checkpoint(sync=True)
                            timeline.commit()
                            last_ckpt_step = step
                            logger.info(
                                "preempted at step %d; exiting cleanly", step
                            )
                            preempted = True
                            break
                        if rollback_reason is not None:
                            restored = self._sentinel_rollback(
                                rollback_reason, step
                            )
                            if restored is not None:
                                step = restored
                                last_div_audit = min(last_div_audit, step)
                                continue
                        if (
                            self.sentinel.divergence_check_period
                            and step - last_div_audit
                            >= self.sentinel.divergence_check_period
                        ):
                            last_div_audit = step
                            self._divergence_audit()
                    if val_period and step % val_period == 0 and step < target:
                        last_val = self._validate()
                        if last_val and self.core.distributed.is_chief:
                            self.core.train.report_validation_metrics(step, last_val)
                            self._tb_scalars(step, last_val, prefix="val_")
                    if ckpt_period and step % ckpt_period == 0:
                        flush_report()
                        # Host-blocking part only (snapshot + writer
                        # join); the async upload overlaps training.
                        with timeline.phase("checkpoint"):
                            self._save_checkpoint()
                        # A durable checkpoint is the ledger's commit
                        # point: time since the last one is now goodput.
                        timeline.commit()
                        last_ckpt_step = step
                        self._tb_sync()
                if preempted:
                    break

                # The op's end and the fetch of the next op lie inside the
                # boundary the op's last step opened.
                with timeline.span("boundary.op_end"):
                    flush_report()
                    last_val = self._validate()
                    if self.core.distributed.is_chief:
                        if last_val:
                            self.core.train.report_validation_metrics(
                                self.steps_completed, last_val
                            )
                            self._tb_scalars(
                                self.steps_completed, last_val, prefix="val_"
                            )
                        # Throughput is a first-class searcher metric
                        # (mesh/batch autotuning sweeps maximize it);
                        # validation metrics win on name collision.
                        completion = {
                            "batches_per_second": getattr(
                                self, "_last_throughput", 0.0
                            ),
                            **last_val,
                        }
                        metric = completion.get(self.searcher_metric, 0.0)
                        op.report_completed(float(metric))
                    op = next(operations, None)

            if (
                (ckpt_period or preempted or self.core.info is not None)
                and last_ckpt_step != step
            ):
                with timeline.phase("checkpoint", timed=False):
                    self._save_checkpoint(sync=True)
                timeline.commit()
        except BaseException as e:
            fit_error = e
            raise
        finally:
            try:
                self._ckpt_writer.wait()  # surface any failed background save
            except BaseException:
                if fit_error is None:
                    raise
                # The loop's own exception is the primary failure; log the
                # checkpoint one rather than masking it.
                logger.exception("background checkpoint failed during teardown")
            finally:
                # the last boundary has no next dispatch to end it
                timeline.end_boundary()
                timeline.unhook_gc()
                profiling_mod.set_phase(None)
                if self._capture_dir is not None:
                    # Abandoned mid-capture exit: stop + report so the
                    # master's capture record does not stay "delivered".
                    self._finish_capture(step)
                _fit_scope.close()  # end the trial.fit span either way
        if self._profiler is not None:
            self._profiler.stop()
        # The fit's tail records (final checkpoint, searcher completion)
        # must survive a hard kill right after fit returns: drain the
        # structured log shipper now rather than relying on atexit.
        logship_mod.flush_shipping()
        self._tb_sync()
        return last_val


def optax_global_norm(tree: Any) -> jax.Array:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in leaves)
    )
