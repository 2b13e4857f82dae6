"""Fault-tolerant training loop.

Design for 1000+ nodes:
  * the step is a pure jitted function of (state, batch); the data
    pipeline is a pure function of (config, step)  =>  restart from any
    committed checkpoint is bit-exact (tested by killing mid-run);
  * checkpoints are atomic + keep-N (repro.train.checkpoint);
  * a straggler monitor tracks per-step wall time EWMA and flags outliers
    (on a multi-host deployment the controller would re-slice around the
    slow host; here the signal is logged and surfaced in TrainResult);
  * preemption is injected via an optional hook for tests (the loop
    raises exactly as a SIGTERM handler would).

Throughput engine (PR2):
  * ``prefetch=k`` overlaps host-side ``batch_at(step)`` collation and
    H2D transfer with device compute via a background
    :class:`repro.train.pipeline.Prefetcher`, and the loop stops
    hard-syncing every step — it only blocks on ``log_every``/checkpoint
    boundaries (plus the first and last step), letting the runtime queue
    dispatches ahead.  Determinism is untouched: the prefetcher evaluates
    the same pure ``batch_at`` stream in order, so resume stays bit-exact.
  * ``donate=True`` donates the state argument to the jitted step
    (``donate_argnums=0``): params and optimizer state update in place
    instead of being copied each step.  The caller's initial ``state``
    buffers are consumed by the first step — thread the returned
    ``TrainResult.state``, never the original.
  * step-time accounting is per COMMITTED step: between hard syncs the
    loop measures wall-clock for the whole span and attributes the
    average to each step in it, so ``TrainResult.throughput()`` reports
    real tasks/sec, not per-dispatch latency (which under async dispatch
    would be a meaningless few microseconds).

Fault tolerance (PR7) — every path is drivable deterministically by a
:class:`repro.faults.FaultPlan` and has a paired test:

  * **non-finite updates**: steps built with ``skip_nonfinite`` report
    ``metrics['nonfinite']``; a skipped step leaves state bit-identical.
    The loop counts CONSECUTIVE skips (``TrainResult.nonfinite_steps``
    lists them all) and more than ``max_nonfinite`` in a row is treated
    as divergence: restore the latest committed checkpoint and replay
    (at most ``max_rollbacks`` times), else raise
    :class:`DivergenceError`.  Replay reuses the already-jitted step —
    no recompile — and, because ``batch_at`` is pure in the step, a
    replay past a since-healed data fault is bit-exact with a run that
    never faulted.
  * **transient data faults**: ``batch_at`` failures retry with bounded
    exponential backoff (``data_retries`` / ``data_backoff_s``) — in the
    prefetcher's worker for ``prefetch>0``, inline here for sync mode.
    Retries spent are surfaced as ``TrainResult.data_retries``; only an
    error outliving every retry propagates.
  * **graceful preemption**: a :class:`repro.faults.PreemptionSignal`
    (``preempt=``, set by a real SIGTERM or by a ``train.preempt``
    fault) is polled every step boundary: the loop flushes a checkpoint
    at the current step and raises :class:`PreemptedError` — nonzero but
    resumable, and the resumed run is bit-exact with an uninterrupted
    one.
  * **injectable clock**: all timing reads ``clock()`` (default
    ``time.time``); an injected ``train.straggler`` fault advances the
    clock by its payload, so straggler detection is testable with a
    FakeClock and zero real sleeps.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from repro.train.checkpoint import CheckpointManager
from repro.train.pipeline import Prefetcher

PyTree = Any


class PreemptedError(RuntimeError):
    """Graceful preemption: a checkpoint at ``step`` was flushed before
    raising, so rerunning the same command resumes bit-exactly.  Launchers
    exit nonzero-but-resumable (75, EX_TEMPFAIL) on this."""

    def __init__(self, step: int, flushed: bool):
        self.step = step
        self.flushed = flushed
        where = f"checkpoint flushed at step {step}" if flushed else \
            "no checkpoint manager — progress since start is lost"
        super().__init__(f"preempted at step {step} ({where})")


class DivergenceError(RuntimeError):
    """More than ``max_nonfinite`` consecutive non-finite (skipped) steps
    and no rollback budget/checkpoint left to recover with."""


class _Diverged(Exception):
    """Internal: consecutive-skip budget exceeded at ``step`` — caught by
    the rollback driver in :func:`train`."""

    def __init__(self, step: int):
        self.step = step


@dataclasses.dataclass
class StragglerMonitor:
    """EWMA step-time tracker; flags steps slower than ratio x the EWMA."""

    alpha: float = 0.1
    ratio: float = 3.0
    ewma: Optional[float] = None
    flagged: List[int] = dataclasses.field(default_factory=list)

    def observe(self, step: int, dt: float) -> bool:
        if self.ewma is None:
            self.ewma = dt
            return False
        slow = dt > self.ratio * self.ewma
        if slow:
            self.flagged.append(step)
        # slow steps do not poison the EWMA
        self.ewma = (1 - self.alpha) * self.ewma + self.alpha * min(dt, self.ratio * self.ewma)
        return slow


@dataclasses.dataclass
class TrainResult:
    state: PyTree
    step: int
    metrics_history: List[Dict]
    straggler_steps: List[int]
    resumed_from: Optional[int]
    step_times: List[float] = dataclasses.field(default_factory=list)
    nonfinite_steps: List[int] = dataclasses.field(default_factory=list)
    rollbacks: int = 0
    data_retries: int = 0

    def throughput(self, items_per_step: int = 1, skip: int = 1) -> float:
        """items/sec over the run, excluding the first ``skip`` (compile)
        steps — the task-batched launcher reports tasks/sec with this.

        ``step_times[i]`` is wall-clock per COMMITTED step: under async
        dispatch (``train(prefetch=...)``) the loop only syncs at span
        boundaries and spreads the measured span time uniformly over its
        steps, so this ratio reflects end-to-end throughput rather than
        dispatch latency.  The first step is always its own span (hard
        sync), so ``skip=1`` cleanly drops compile time."""
        times = self.step_times[skip:] or self.step_times
        if not times:
            return 0.0
        return items_per_step * len(times) / sum(times)


def train(state: PyTree,
          train_step: Callable,
          batch_at: Callable[[int], Dict],
          num_steps: int,
          *,
          ckpt: Optional[CheckpointManager] = None,
          ckpt_every: int = 50,
          state_template: Optional[PyTree] = None,
          preemption_hook: Optional[Callable[[int], None]] = None,
          log_every: int = 0,
          prefetch: int = 0,
          donate: bool = False,
          batch_put: Optional[Callable] = None,
          max_span: int = 64,
          fault_plan=None,
          preempt=None,
          max_nonfinite: int = 8,
          max_rollbacks: int = 1,
          data_retries: int = 2,
          data_backoff_s: float = 0.05,
          clock: Optional[Callable[[], float]] = None) -> TrainResult:
    """Run (and resume) training.  ``batch_at(step)`` must be deterministic
    in ``step`` — together with checkpointed state that is what makes
    restarts exact.

    ``prefetch > 0`` builds batches on a background thread ``prefetch``
    steps ahead and switches the loop to async dispatch: hard sync only on
    log/checkpoint boundaries, bounded by ``max_span`` so dispatch
    run-ahead (queued executions + their pinned batch buffers + pending
    metrics) can never grow with ``num_steps``.  Within a span the
    straggler monitor only sees the span-average step time — a single
    slow step inside a long span is smeared out, and non-finite skips are
    only DETECTED at span commits; shorten ``log_every`` / ``max_span``
    where per-step attribution matters.  ``donate=True`` donates the
    state to the jitted step so params/opt state update in place — the
    caller's input ``state`` is consumed by the first step.  ``batch_put``
    overrides the prefetcher's H2D transfer (e.g. a sharded
    ``device_put`` matching a two-level mesh layout).

    Fault-tolerance knobs (see module docstring): ``fault_plan`` injects
    deterministic faults at the documented sites; ``preempt`` is a
    :class:`repro.faults.PreemptionSignal` polled each step;
    ``max_nonfinite`` bounds consecutive skipped steps before a rollback
    (``max_rollbacks`` of them, needing ``ckpt`` + ``state_template``)
    or :class:`DivergenceError`; ``data_retries``/``data_backoff_s``
    bound the transient-data retry; ``clock`` overrides ``time.time``
    for all timing (tests pass a FakeClock)."""
    from repro.faults.plan import (TRAIN_PREEMPT, TRAIN_STRAGGLER,
                                   advance_clock)

    _clock = clock if clock is not None else time.time
    if fault_plan is not None:
        batch_at = fault_plan.wrap_batch_at(batch_at)

    start = 0
    resumed_from = None
    if ckpt is not None and state_template is not None:
        restored = ckpt.restore_latest(state_template)
        if restored is not None:
            start, state, _ = restored
            resumed_from = start
    base_start = start
    step_fn = jax.jit(train_step, donate_argnums=(0,) if donate else ())
    monitor = StragglerMonitor()
    history: List[Dict] = []
    step_times: List[float] = []
    nonfinite_steps: List[int] = []
    consecutive_nonfinite = 0
    rollbacks_done = 0
    retries_spent = 0

    def fetch_sync(s: int):
        """Sync-mode ``batch_at`` with the same bounded-backoff retry the
        prefetcher applies in its worker.  Backoff passes through
        ``advance_clock`` so a FakeClock makes it instant and
        deterministic."""
        nonlocal retries_spent
        delay = data_backoff_s
        for attempt in range(data_retries + 1):
            try:
                return batch_at(s)
            except Exception:
                if attempt == data_retries:
                    raise
                retries_spent += 1
                if delay > 0:
                    advance_clock(_clock, delay)
                    delay *= 2

    def run_from(attempt_start: int, state: PyTree) -> PyTree:
        """One attempt: steps [attempt_start, num_steps) on the shared
        jitted step.  Raises :class:`_Diverged` when the consecutive-skip
        budget blows; the driver below rolls back and calls again."""
        nonlocal consecutive_nonfinite, retries_spent
        pf = None
        source = fetch_sync
        if prefetch > 0 and attempt_start < num_steps:
            pf = Prefetcher(batch_at, attempt_start, num_steps,
                            depth=prefetch, put=batch_put,
                            retries=data_retries, backoff_s=data_backoff_s)
            source = pf.get
        try:
            pending: List[tuple] = []    # (step, metrics) dispatched, uncommitted
            span_t0: Optional[float] = None
            span_start = attempt_start
            for step in range(attempt_start, num_steps):
                if preemption_hook is not None:
                    preemption_hook(step)    # may raise (simulated SIGTERM)
                preempted = preempt is not None and preempt.requested
                if fault_plan is not None and \
                        fault_plan.fire(TRAIN_PREEMPT, step) is not None:
                    preempted = True
                if preempted:
                    # state reflects completion through step-1: flush a
                    # checkpoint AT step so the rerun resumes right here.
                    if ckpt is not None:
                        ckpt.save(step, state)
                    raise PreemptedError(step, flushed=ckpt is not None)
                if span_t0 is None:
                    span_t0 = _clock()
                    span_start = step
                state, metrics = step_fn(state, source(step))
                if fault_plan is not None:
                    spec = fault_plan.fire(TRAIN_STRAGGLER, step)
                    if spec is not None:
                        advance_clock(_clock, float(spec.payload or 1.0))
                pending.append((step, metrics))
                # In sync mode every step is a span; async mode syncs only
                # on the first step (isolates compile), log/ckpt
                # boundaries, and the final step.
                sync = (prefetch == 0 or step == attempt_start
                        or step == num_steps - 1
                        or (log_every and step % log_every == 0)
                        or (ckpt is not None and (step + 1) % ckpt_every == 0)
                        or len(pending) >= max(max_span, 1))
                if sync:
                    jax.block_until_ready(jax.tree.leaves(state)[0])
                    per = (_clock() - span_t0) / (step - span_start + 1)
                    diverged_at = None
                    for s, m in pending:
                        step_times.append(per)
                        monitor.observe(s, per)
                        fm = {k: float(v) for k, v in m.items()}
                        history.append(fm)
                        if fm.get("nonfinite", 0.0) >= 0.5:
                            nonfinite_steps.append(s)
                            consecutive_nonfinite += 1
                            if consecutive_nonfinite > max_nonfinite and \
                                    diverged_at is None:
                                diverged_at = s
                        else:
                            consecutive_nonfinite = 0
                    pending.clear()
                    span_t0 = None
                    if diverged_at is not None:
                        raise _Diverged(diverged_at)
                    if log_every and step % log_every == 0:
                        print(f"step {step}: {history[-1]}", flush=True)
                if ckpt is not None and (step + 1) % ckpt_every == 0:
                    ckpt.save(step + 1, state)
            return state
        finally:
            if pf is not None:
                retries_spent += pf.retries_used
                pf.close()

    attempt_start = start
    while True:
        try:
            state = run_from(attempt_start, state)
            break
        except _Diverged as d:
            can_roll = (ckpt is not None and state_template is not None
                        and rollbacks_done < max_rollbacks)
            restored = ckpt.restore_latest(state_template) if can_roll else None
            if restored is None:
                raise DivergenceError(
                    f"{consecutive_nonfinite} consecutive non-finite steps "
                    f"(> max_nonfinite={max_nonfinite}) ending at step "
                    f"{d.step}; rollbacks used {rollbacks_done}/"
                    f"{max_rollbacks}" + (
                        "" if ckpt is not None and state_template is not None
                        else " and no checkpoint manager/template to roll "
                             "back with")) from None
            r, state, _ = restored
            rollbacks_done += 1
            consecutive_nonfinite = 0
            # rewind bookkeeping to the restore point; the replayed steps
            # re-commit their entries so the final result is contiguous.
            del history[r - base_start:]
            del step_times[r - base_start:]
            nonfinite_steps[:] = [s for s in nonfinite_steps if s < r]
            monitor.flagged[:] = [s for s in monitor.flagged if s < r]
            print(f"divergence at step {d.step}: rolled back to committed "
                  f"checkpoint at step {r} "
                  f"(rollback {rollbacks_done}/{max_rollbacks})", flush=True)
            attempt_start = r

    if ckpt is not None:
        ckpt.save(num_steps, state)
    return TrainResult(state=state, step=num_steps, metrics_history=history,
                       straggler_steps=monitor.flagged,
                       resumed_from=resumed_from, step_times=step_times,
                       nonfinite_steps=nonfinite_steps,
                       rollbacks=rollbacks_done, data_retries=retries_spent)
