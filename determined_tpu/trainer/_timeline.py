"""Step-phase timer + goodput ledger for the trainer.

Answers the two operability questions the metrics history alone cannot:
*where does a step's wall-clock go* (data-wait vs host→device put vs the
jitted step vs reporting vs checkpointing) and *how much of the trial's
lifetime was productive* (vs lost to rollbacks, restarts and stalls —
goodput %, the MegaScale/PaLM reliability headline number).

Discipline — no per-step host sync (the PR 3 sentinel-counter contract):

- per step the host records only `perf_counter` deltas around work the
  host ALREADY does synchronously (pulling the next batch, device_put);
- the jitted-step time is the window RESIDUAL, settled at report
  boundaries where the metrics flush already blocks on the device
  (`_sentinel_check`'s device_get): residual = window wall − data-wait −
  put − report − checkpoint. Async dispatch means per-step host timers
  cannot see device time; the boundary sync sees exactly all of it.

Ledger semantics:

- window time accrues as *uncommitted* until a checkpoint lands
  (`commit()` → productive): work that a later rollback discards was
  never goodput, and this is how that shows up without bookkeeping every
  batch;
- `on_rollback(restore_s)` moves the uncommitted time plus the restore
  itself to the lost side;
- the ledger rides the trainer metadata (`to_metadata`/`load`), so a
  process restart resumes the SAME ledger and the save→restore gap —
  scheduler queue, reschedule, re-init — is charged as restart loss.

Kill switch: ``DTPU_TIMELINE=0``.

`Timeline.phase(name)` is the one way the trainer marks a phase: it tags
the thread for the sampling profiler, opens a `TraceAnnotation`
``dtpu.trainer.<name>`` (a host span on the profiler's clock, so a
capture shows whether the device waited inside it; free when no capture
runs, and not switched off by the kill switch) and adds the elapsed time
to the window. ``report`` has two children, ``report.sync`` (the
boundary's `device_get`s) and ``report.publish`` (the metric reports).

Two spans are no phase (no sampler tag, no `*_frac` key):

- ``dtpu.trainer.boundary``, the whole report boundary, from the start
  of its `flush_report` until the next step's dispatch returns
  (`begin_boundary` / `end_boundary`). It encloses the phases that fall
  in it and its children: ``wait`` (`boundary_wait`, inside
  ``report.sync``), ``control``, ``step_flops``, ``op_end``
  (`Timeline.span`). The host reaches a boundary while the device still
  runs the window's steps (dispatch is asynchronous), so the boundary
  starts with ``wait``, the first sync, which returns once the device
  has run the window's last step. ``boundary_s``, in the profiling
  report of the window the boundary opened, is the rest on `pc`: from
  the end of ``wait`` to the next dispatch, the host work the device
  waits through.
- ``dtpu.trainer.gc``, each pause of Python's collector while
  `hook_gc` is in force: ``gc_s`` and ``gc_collections`` a window.
"""
from __future__ import annotations

import contextlib
import gc
import os
import time
from typing import Any, Dict, Iterator, Optional

from jax.profiler import TraceAnnotation

from determined_tpu.common.profiling import set_phase

#: Window phases the host measures directly; "step" is the residual.
PHASES = ("data_wait", "h2d_put", "report", "checkpoint")
ALL_PHASES = PHASES + ("step",)
#: Prefix of the phases' host spans in a profiler capture.
SPAN_PREFIX = "dtpu.trainer."
#: Host spans that are no phase (see the module docstring).
BOUNDARY = "boundary"
GC = "gc"


class _Phase:
    """One entry into a phase (see `Timeline.phase`). A class and not a
    generator: the hot loop enters two a step."""

    __slots__ = ("_timeline", "_key", "_timed", "_span", "_prev", "_t0")

    def __init__(self, timeline: "Timeline", name: str, timed: bool) -> None:
        self._timeline = timeline
        # `report.sync` is tagged and accumulated as `report`.
        self._key = name.partition(".")[0]
        self._timed = timed and timeline.enabled
        self._span = TraceAnnotation(SPAN_PREFIX + name)

    def __enter__(self) -> None:
        self._prev = set_phase(self._key)
        self._span.__enter__()
        if self._timed:
            self._t0 = self._timeline.pc()

    def __exit__(self, *exc: Any) -> None:
        if self._timed:
            timeline = self._timeline
            timeline.window[self._key] += timeline.pc() - self._t0
        self._span.__exit__(*exc)
        set_phase(self._prev)


class Timeline:
    def __init__(self, enabled: Optional[bool] = None) -> None:
        if enabled is None:
            enabled = os.environ.get("DTPU_TIMELINE", "1") != "0"
        self.enabled = enabled
        self.pc = time.perf_counter
        # -- window accumulators (reset every report boundary) --------------
        self.window: Dict[str, float] = {p: 0.0 for p in PHASES}
        self._window_start = self.pc()
        #: the open report boundary's span (`begin_boundary`), else None
        self.boundary: Optional[TraceAnnotation] = None
        self._boundary_t0 = 0.0
        #: the host part of the boundary that opened this window, once closed
        self._boundary_s: Optional[float] = None
        self._gc_hooked = False
        self._gc_span: Optional[TraceAnnotation] = None
        self._gc_t0 = 0.0
        # Lifetime (seconds, collections), replaced whole by the
        # collecting thread alone (one collection runs at a time); a
        # window reports them against the totals it started at, so no
        # collection is lost or counted twice across windows.
        self._gc_totals = (0.0, 0)
        self._gc_at_window = self._gc_totals
        # -- cumulative phase totals (lifetime, this process + restores) ----
        self.phase_totals: Dict[str, float] = {p: 0.0 for p in ALL_PHASES}
        # -- goodput ledger --------------------------------------------------
        self.productive_s = 0.0       # window time behind a checkpoint
        self.lost_s = 0.0             # rollback + restart + resize time
        self.rollback_lost_s = 0.0
        self.restart_lost_s = 0.0
        #: elastic resize event class: drain→resume wall time of in-place
        #: gang resizes (spot reclaim survived WITHOUT a restart). Charged
        #: as lost time like a restart, but in its own bucket so bench can
        #: publish resize_cost_s against the measured full-restart cost.
        self.resize_lost_s = 0.0
        self.rollbacks = 0
        self.restarts = 0
        self.resizes = 0
        #: window time since the last commit point — tentatively
        #: productive; a rollback reclassifies it as lost wholesale.
        self.uncommitted_s = 0.0

    # -- window -------------------------------------------------------------
    def phase(self, name: str, timed: bool = True) -> _Phase:
        """Context manager around one phase of the loop (a name of
        `PHASES`, or a child `<phase>.<part>`): the sampler's tag, the
        host span, and — unless the site passes ``timed=False`` or the
        timeline is disabled — the elapsed time into ``window[<phase>]``.
        The previous tag comes back at exit, so phases nest."""
        return _Phase(self, name, timed)

    @staticmethod
    def span(name: str) -> TraceAnnotation:
        """A host span `dtpu.trainer.<name>` that is no phase: a part of
        the boundary (``boundary.control``, ...)."""
        return TraceAnnotation(SPAN_PREFIX + name)

    def begin_boundary(self) -> None:
        """A report boundary starts (its `flush_report`); `end_boundary`
        closes it once the next step's dispatch has returned, or on the
        way out of `fit`. Its clock starts at the end of `boundary_wait`."""
        self.boundary = TraceAnnotation(SPAN_PREFIX + BOUNDARY)
        self.boundary.__enter__()

    @contextlib.contextmanager
    def boundary_wait(self) -> Iterator[None]:
        """Around the open boundary's first sync, which returns once the
        device has run the window's last step: the span
        ``boundary.wait``, and ``boundary_s`` counts from its end.
        Nothing outside a boundary."""
        if self.boundary is None:
            yield
            return
        with self.span(BOUNDARY + ".wait"):
            yield
        if self.enabled:
            self._boundary_t0 = self.pc()

    def end_boundary(self) -> None:
        span, self.boundary = self.boundary, None
        if span is None:
            return
        if self.enabled:
            self._boundary_s = self.pc() - self._boundary_t0
        span.__exit__(None, None, None)

    def hook_gc(self) -> None:
        """Time every collection until `unhook_gc`: ``gc_s`` and
        ``gc_collections`` in each window, a ``dtpu.trainer.gc`` span on
        the thread that collects. Nothing under the kill switch."""
        if self.enabled and not self._gc_hooked:
            gc.callbacks.append(self._on_gc)
            self._gc_hooked = True

    def unhook_gc(self) -> None:
        if self._gc_hooked:
            gc.callbacks.remove(self._on_gc)
            self._gc_hooked = False

    def _on_gc(self, phase: str, _info: Dict[str, Any]) -> None:
        if phase == "start":
            self._gc_span = TraceAnnotation(SPAN_PREFIX + GC)
            self._gc_span.__enter__()
            self._gc_t0 = self.pc()
            return
        span, self._gc_span = self._gc_span, None
        if span is None:
            return
        gc_s, gc_n = self._gc_totals
        self._gc_totals = (gc_s + self.pc() - self._gc_t0, gc_n + 1)
        span.__exit__(None, None, None)

    def reset_window(self) -> None:
        for p in PHASES:
            self.window[p] = 0.0
        self._window_start = self.pc()
        self._boundary_s = None
        self._gc_at_window = self._gc_totals

    def close_window(self) -> Dict[str, float]:
        """Settle the window at a report boundary (the caller has already
        blocked on the device, so the residual includes the jitted steps).
        Returns the window's phase fractions for the profiling report."""
        wall = max(self.pc() - self._window_start, 0.0)
        measured = sum(self.window.values())
        step_s = max(wall - measured, 0.0)
        # Denominator guards the clamp: measured sub-intervals can exceed
        # the wall reading by clock jitter; fractions must still sum to 1.
        denom = max(wall, measured)
        out: Dict[str, float] = {"window_s": wall}
        if denom > 0:
            for p in PHASES:
                self.phase_totals[p] += self.window[p]
                out[f"{p}_frac"] = self.window[p] / denom
            self.phase_totals["step"] += step_s
            out["step_frac"] = step_s / denom
        if self._boundary_s is not None:
            out["boundary_s"] = self._boundary_s
        gc_now = self._gc_totals
        if self._gc_hooked:
            out["gc_s"] = gc_now[0] - self._gc_at_window[0]
            out["gc_collections"] = float(gc_now[1] - self._gc_at_window[1])
        self.uncommitted_s += wall
        self.reset_window()
        self._gc_at_window = gc_now     # the next window starts where this one ended
        return out

    # -- ledger -------------------------------------------------------------
    def commit(self) -> None:
        """A checkpoint landed: everything since the previous commit is now
        durable — real goodput."""
        self.productive_s += self.uncommitted_s
        self.uncommitted_s = 0.0

    def on_rollback(self, restore_s: float) -> None:
        """Sentinel rollback: the uncommitted window time trained state the
        restore just discarded, and the restore itself is overhead."""
        lost = self.uncommitted_s + max(restore_s, 0.0)
        self.lost_s += lost
        self.rollback_lost_s += lost
        self.rollbacks += 1
        self.uncommitted_s = 0.0
        self.reset_window()

    def on_restart(self, gap_s: float) -> None:
        """Process restart resumed this ledger: the save→restore wall gap
        (crash, reschedule, stall-kill requeue) was not training."""
        gap = max(gap_s, 0.0)
        self.lost_s += gap
        self.restart_lost_s += gap
        self.restarts += 1

    def on_resize(self, gap_s: float) -> None:
        """Elastic resize resumed this ledger IN PLACE (same allocation,
        same process): the save→resume gap covers the drained window, the
        re-rendezvous and the reshard-restore — the whole drain→resume
        cost of surviving a reclaim, with the restart budget charged 0."""
        gap = max(gap_s, 0.0)
        self.lost_s += gap
        self.resize_lost_s += gap
        self.resizes += 1

    @property
    def goodput_pct(self) -> float:
        good = self.productive_s + self.uncommitted_s
        total = good + self.lost_s
        return 100.0 * good / total if total > 0 else 100.0

    # -- reporting / persistence ---------------------------------------------
    def snapshot(self) -> Dict[str, float]:
        """Cumulative ledger view for the `profiling` metric group."""
        out: Dict[str, float] = {
            "goodput_pct": self.goodput_pct,
            "productive_s": self.productive_s + self.uncommitted_s,
            "lost_s": self.lost_s,
            "rollback_lost_s": self.rollback_lost_s,
            "restart_lost_s": self.restart_lost_s,
            "resize_lost_s": self.resize_lost_s,
            "ledger_rollbacks": float(self.rollbacks),
            "ledger_restarts": float(self.restarts),
            "ledger_resizes": float(self.resizes),
        }
        lifetime = sum(self.phase_totals.values())
        if lifetime > 0:
            for p in ALL_PHASES:
                out[f"total_{p}_frac"] = self.phase_totals[p] / lifetime
        return out

    def to_metadata(self, trial_id: int = 0) -> Dict[str, Any]:
        return {
            # Ledger owner: a warm-started FORK restores this checkpoint
            # under a different trial id and must start a fresh ledger —
            # inheriting the source's losses (and the save→fork wall gap)
            # would report garbage goodput for work it never did.
            "trial_id": int(trial_id),
            "productive_s": self.productive_s + self.uncommitted_s,
            "lost_s": self.lost_s,
            "rollback_lost_s": self.rollback_lost_s,
            "restart_lost_s": self.restart_lost_s,
            "resize_lost_s": self.resize_lost_s,
            "rollbacks": self.rollbacks,
            "restarts": self.restarts,
            "resizes": self.resizes,
            "phase_totals": dict(self.phase_totals),
            # wall-clock stamp: the resume charges save→restore as loss
            "saved_at": time.time(),
        }

    def load(
        self,
        md: Dict[str, Any],
        *,
        now: Optional[float] = None,
        trial_id: int = 0,
        event: str = "restart",
    ) -> None:
        """Resume the ledger from checkpoint metadata — SAME-TRIAL process
        restarts only. A trial-id mismatch (warm-started fork, continue
        into a new trial) keeps the fresh ledger: the new trial owes
        nothing to the source's history.

        `event` classifies the save→resume gap: "restart" (a new process
        resumed the trial) or "resize" (an elastic in-place resize —
        drain, re-rendezvous, reshard-restore — resumed it; its gap is
        the `resize_cost_s` bench publishes)."""
        try:
            if int(md.get("trial_id", 0)) != int(trial_id):
                return
            self.productive_s = float(md.get("productive_s", 0.0))
            self.lost_s = float(md.get("lost_s", 0.0))
            self.rollback_lost_s = float(md.get("rollback_lost_s", 0.0))
            self.restart_lost_s = float(md.get("restart_lost_s", 0.0))
            self.resize_lost_s = float(md.get("resize_lost_s", 0.0))
            self.rollbacks = int(md.get("rollbacks", 0))
            self.restarts = int(md.get("restarts", 0))
            self.resizes = int(md.get("resizes", 0))
            totals = md.get("phase_totals") or {}
            for p in ALL_PHASES:
                self.phase_totals[p] = float(totals.get(p, 0.0))
            self.uncommitted_s = 0.0
            saved_at = float(md.get("saved_at", 0.0))
            if saved_at:
                gap = (now if now is not None else time.time()) - saved_at
                if event == "resize":
                    self.on_resize(gap)
                else:
                    self.on_restart(gap)
            self.reset_window()
        except (TypeError, ValueError):
            pass  # corrupt ledger metadata must never block a restore
