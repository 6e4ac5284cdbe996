"""Training health sentinel: the step-level defenses of the trainer.

Production TPU training treats bad steps as routine events, not
exceptions: PaLM's loss-spike mitigation is restart-from-checkpoint and
skip the offending batches; MegaScale's reliability layer turns hangs
into fast, attributable kills via per-step progress heartbeats. This
module holds the trainer-side pieces of that story:

- **Non-finite guard** (`guarded_update`): folded INTO the jitted train
  step — after a NaN/inf loss or gradient norm every leaf of the
  parameters and the optimizer state keeps its old value (a leaf-wise
  select fused into the update itself, `optax.apply_if_finite`
  semantics; no `conditional` in the step) and a consecutive-skip
  counter that rides the device-resident metrics buffer is bumped.
  No extra host sync: the host only reads the counter at report
  boundaries, where it already materializes metrics.
- **Loss-spike detector** (`SpikeDetector`): a robust z-score (median /
  MAD) over a rolling window of recent losses; a spike past
  `spike_zscore` triggers the same rollback-and-skip path as a run of
  non-finite steps. Every rank runs the detector on the identical global
  loss stream, so the rollback decision needs no extra collective.
- **Replica-divergence audit** (`local_shard_checksums` /
  `compare_checksums`): a periodic cheap deterministic checksum of every
  addressable param shard, compared across data-parallel replicas (same
  logical region = same (leaf, index) key, across devices and hosts). A
  mismatch is silent data corruption — the trial errors with the
  offending rank/device named.

Every failure mode is drivable deterministically through the PR-1 fault
plan (`DTPU_FAULT_PLAN`) at the `train.*` sites below, so the whole
sentinel is testable on CPU.
"""
from __future__ import annotations

import dataclasses
import logging
import math
import statistics
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from determined_tpu.common import faults

logger = logging.getLogger("determined_tpu.trainer")

#: Fault sites (common/faults.py). `train.nonfinite` poisons the step's
#: loss with NaN (the guard must skip it); `train.spike` scales it by
#: SPIKE_FACTOR (finite — the guard must NOT trip; the z-score must);
#: `train.divergence.rank<r>` perturbs rank r's audit checksums (the
#: audit must name that rank).
NONFINITE_SITE = "train.nonfinite"
SPIKE_SITE = "train.spike"
DIVERGENCE_SITE_PREFIX = "train.divergence.rank"

SPIKE_FACTOR = 1e6


class ReplicaDivergenceError(RuntimeError):
    """Replicated params diverged across data-parallel replicas: silent
    data corruption (flipped bit, bad HBM). The message names the
    offending host/device; the trial errors rather than train on — or
    checkpoint — corrupt state."""


@dataclasses.dataclass(frozen=True)
class SentinelConfig:
    """Per-trial health knobs (experiment config `health:` section)."""

    #: consecutive in-graph skips before rollback-and-skip; 0 = guard
    #: only (never roll back).
    max_consecutive_skips: int = 3
    #: robust z-score above which a finite loss counts as a spike and
    #: triggers rollback; 0 disables the detector.
    spike_zscore: float = 0.0
    #: losses kept in the spike baseline window.
    spike_window: int = 64
    #: observations required before the detector may fire (a cold
    #: detector judging step 2 against a 1-sample baseline is noise).
    spike_min_history: int = 16
    #: batches between replica-divergence audits; 0 disables.
    divergence_check_period: int = 0
    #: master-side stall watchdog knob; carried here so one object
    #: describes the trial's whole health contract.
    stall_timeout_s: float = 0.0

    @classmethod
    def from_config(cls, health: Optional[Dict[str, Any]]) -> "SentinelConfig":
        health = health or {}
        return cls(
            max_consecutive_skips=int(health.get("max_consecutive_skips", 3)),
            spike_zscore=float(health.get("spike_zscore", 0.0) or 0.0),
            spike_window=int(health.get("spike_window", 64)),
            spike_min_history=int(health.get("spike_min_history", 16)),
            divergence_check_period=int(
                health.get("divergence_check_period", 0)
            ),
            stall_timeout_s=float(health.get("stall_timeout_s", 0.0) or 0.0),
        )


# -- in-graph non-finite guard ------------------------------------------------
def guarded_update(
    old_state: Dict[str, Any],
    new_state: Dict[str, Any],
    loss: jax.Array,
    grad_norm: jax.Array,
    skips_in: jax.Array,
) -> Tuple[Dict[str, Any], jax.Array, jax.Array]:
    """Select the post-step state in-graph, leaf by leaf: `new_state`'s
    leaf where loss AND grad norm are finite, else `old_state`'s, the
    step counter advanced either way (the batch was consumed;
    params/optimizer must not absorb the poison, and a select takes the
    old value whatever the new one holds, NaN included). Elementwise on
    purpose: XLA fuses the select into the update that forms each leaf,
    so the state is read and written once, in the layout it has at the
    step's boundary. A `lax.cond` over the whole state is not the same
    program on the chip: the compiler sinks the update into the branch
    and copies parameters and moments in and out of it (PERF.md section
    6, PR 31; `tests/test_tpu_compile.py` holds the compiled step to no
    `conditional` and no such copy). A skipped step runs the update's
    arithmetic and discards it: skipped steps are faults, not traffic.

    Returns (state, ok, skips_out): `ok` is a device bool (1 = applied),
    `skips_out` the consecutive-skip counter (resets on a healthy step).
    All three stay on device — callers must not materialize them per
    step.
    """
    ok = jnp.isfinite(loss) & jnp.isfinite(grad_norm)
    state = jax.tree.map(
        lambda new, old: jnp.where(ok, new, old), new_state, old_state
    )
    state["step"] = new_state["step"]
    skips_out = jnp.where(ok, jnp.int32(0), skips_in.astype(jnp.int32) + 1)
    return state, ok, skips_out


# -- fault-drill hooks --------------------------------------------------------
def poison_factor() -> float:
    """Host-side fault hook consulted once per step: 1.0 normally; NaN
    when the plan schedules a `train.nonfinite` injection for this call
    (the wire-shape of a poisoned batch — the loss and every grad go
    non-finite); SPIKE_FACTOR for `train.spike` (finite but wild — only
    the z-score detector can catch it). One `None` check when no plan is
    active."""
    plan = faults.active()
    if plan is None:
        return 1.0
    try:
        plan.decide(NONFINITE_SITE)
    except faults.InjectedFault:
        return float("nan")
    try:
        plan.decide(SPIKE_SITE)
    except faults.InjectedFault:
        return SPIKE_FACTOR
    return 1.0


def divergence_fault(rank: int) -> bool:
    """True when the plan schedules a replica bit-flip drill for `rank`
    (site `train.divergence.rank<r>` — per-rank site names because the
    env-inherited plan is identical in every process, and a perturbation
    applied by ALL ranks would cancel out of the comparison)."""
    plan = faults.active()
    if plan is None:
        return False
    try:
        plan.decide(f"{DIVERGENCE_SITE_PREFIX}{rank}")
    except faults.InjectedFault:
        return True
    return False


# -- loss-spike detection -----------------------------------------------------
class SpikeDetector:
    """Robust z-score loss-spike detector (median/MAD over a rolling
    window). Median and MAD instead of mean/std so the baseline is not
    dragged by the very spikes it must flag; confirmed spikes are NOT
    added to the history for the same reason."""

    def __init__(self, config: SentinelConfig) -> None:
        self.z = float(config.spike_zscore)
        self.min_history = max(2, int(config.spike_min_history))
        self._hist: Deque[float] = deque(maxlen=max(4, config.spike_window))

    @property
    def enabled(self) -> bool:
        return self.z > 0

    def observe(self, loss: float) -> bool:
        """Feed one step loss; returns True when it is a spike.
        Non-finite losses are the guard's jurisdiction — ignored here."""
        if not self.enabled or not math.isfinite(loss):
            return False
        spike = False
        if len(self._hist) >= self.min_history:
            med = statistics.median(self._hist)
            mad = statistics.median(abs(x - med) for x in self._hist)
            # 1.4826 * MAD ≈ σ for a normal baseline; the floor keeps a
            # perfectly-flat loss window (MAD 0) from flagging normal
            # float jitter as infinite-z spikes.
            scale = max(1.4826 * mad, 1e-3 * max(abs(med), 1e-8))
            spike = (loss - med) / scale > self.z
        if not spike:
            self._hist.append(loss)
        return spike

    def reset(self) -> None:
        """Drop the baseline (after a rollback: the poisoned window's
        losses must not seed the fresh run's statistics)."""
        self._hist.clear()


# -- replica-divergence audit -------------------------------------------------
def _shard_sums(x: jax.Array) -> Tuple[float, float]:
    """Deterministic two-component projection of one device shard:
    (Σx, Σx²) in float32. Replicas hold bit-identical data and run the
    identical reduction, so equality is EXACT — any difference is
    corruption, not float noise."""
    x32 = jnp.asarray(x).astype(jnp.float32)
    return (
        float(jax.device_get(jnp.sum(x32))),
        float(jax.device_get(jnp.sum(x32 * x32))),
    )


def _index_key(index: Any) -> str:
    parts = []
    for sl in index if isinstance(index, tuple) else (index,):
        if isinstance(sl, slice):
            parts.append(f"{sl.start or 0}:{sl.stop}")
        else:
            parts.append(str(sl))
    return ",".join(parts) or "scalar"


def local_shard_checksums(
    params: Any,
) -> Dict[str, List[Tuple[str, Tuple[float, float]]]]:
    """Checksums of every addressable shard of `params`, keyed by the
    shard's logical region ("<leaf-path>|<index>"). Two devices — on the
    same host or different hosts — holding the same key are data-parallel
    replicas of the same bytes and MUST checksum identically; different
    regions (fsdp/tensor shards) get different keys and are never
    compared. Values are (device-label, (Σx, Σx²)) pairs."""
    out: Dict[str, List[Tuple[str, Tuple[float, float]]]] = {}
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    for path, leaf in leaves:
        name = jax.tree_util.keystr(path)
        arr = leaf if isinstance(leaf, jax.Array) else jnp.asarray(leaf)
        for shard in arr.addressable_shards:
            key = f"{name}|{_index_key(shard.index)}"
            out.setdefault(key, []).append(
                (str(shard.device), _shard_sums(shard.data))
            )
    return out


def compare_checksums(
    gathered: List[Tuple[int, Dict[str, List[Tuple[str, Tuple[float, float]]]]]],
    addrs: Optional[Dict[int, str]] = None,
) -> Optional[str]:
    """Chief-side comparison of per-rank shard checksums. Returns None
    when every replica group agrees, else a diagnostic naming the
    minority holder(s) — the flipped-bit host/device, not just "some
    mismatch". `addrs` (rank -> host address) enriches the message."""
    groups: Dict[str, List[Tuple[int, str, Tuple[float, float]]]] = {}
    for rank, sums in gathered:
        for key, entries in sums.items():
            for device, val in entries:
                groups.setdefault(key, []).append((rank, device, val))
    for key, entries in sorted(groups.items()):
        values = {val for _, _, val in entries}
        if len(values) <= 1:
            continue
        # Majority value = healthy; minority holders are the suspects.
        counts: Dict[Tuple[float, float], int] = {}
        for _, _, val in entries:
            counts[val] = counts.get(val, 0) + 1
        majority = max(counts.values())
        suspects = [
            (rank, device)
            for rank, device, val in entries
            if counts[val] < majority
        ] or [(rank, device) for rank, device, _ in entries]
        named = ", ".join(
            f"rank {rank}"
            + (f" ({addrs[rank]})" if addrs and rank in addrs else "")
            + f" device {device}"
            for rank, device in suspects
        )
        return (
            f"replica divergence on {key}: {len(values)} distinct "
            f"checksums across {len(entries)} replicas; suspect {named} "
            "(silent data corruption — flipped bit or bad HBM)"
        )
    return None
