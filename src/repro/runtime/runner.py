"""Fault-tolerant training runner.

Wraps the phase-specialized steps with the operational machinery a
1000+-node deployment needs, scaled to this container:

* **period fusion** — with ``RunnerConfig.fused_period`` the runner
  executes one donated executable per whole synchronization period
  (:func:`repro.runtime.step.make_period_step`) instead of one jitted
  call per iteration: phase boundaries stop being host round-trips,
  XLA's latency-hiding scheduler can float phase *h*'s parameter
  all-reduce under phase *h+1*'s compute, metrics stay device-resident
  until the ``log_every`` drain, and the next period's data is
  prefetched while the current one runs (see DESIGN.md here).  The
  per-step path remains the oracle — bitwise-identical ``TrainState``;
* **checkpoint/restart** — periodic async checkpoints; any exception inside
  a step restores the last checkpoint and replays (bounded retries);
* **straggler mitigation** — a sync phase (per-step path) or period
  (fused path) whose wall-clock exceeds ``deadline_factor x`` the
  running median has its layer units re-queued into a makeup sync at
  the next period boundary.  Sound because partial-sync tolerates
  per-layer staleness <= 2H (Lemma 4 with ``H_l <= 2H``);
* **elasticity** — ``restore(n_workers=...)`` reshapes the worker axis via
  :func:`repro.checkpoint.reshard_workers` and re-solves the SyncPlan for
  the new worker count (the schedule is data, not code).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable

import jax

from ..checkpoint import CheckpointManager, reshard_workers
from ..core.plans import SyncPlan, local_plan
from ..lint import hot_path
from ..parallel.sharding import place_worker_axis
from . import spans
from .pipeline import PeriodPrefetcher
from .step import (StepConfig, TrainState, compose_makeup_step,
                   make_period_step, make_train_step)

__all__ = ["RunnerConfig", "Runner", "reshard_train_state"]

PyTree = Any


def reshard_train_state(state: TrainState, n_workers: int) -> TrainState:
    """Map a worker-stacked TrainState onto a new worker count.

    Replicas are averaged and re-broadcast (see
    :func:`repro.checkpoint.reshard_workers`) — a synchronization point,
    so Lemma 4's bounded-staleness argument survives membership changes.
    Shared by :meth:`Runner.restore_elastic` and ``Session.replan``.
    """
    return TrainState(
        params=reshard_workers(state.params, n_workers),
        opt_state=reshard_workers(state.opt_state, n_workers),
        step=state.step,
        ef=None if state.ef is None else
        reshard_workers(state.ef, n_workers),
        outer=None if state.outer is None else jax.tree.map(
            lambda x: reshard_workers(x, n_workers), state.outer),
    )


@dataclass(frozen=True)
class RunnerConfig:
    ckpt_every: int = 200
    max_retries: int = 3
    deadline_factor: float = 3.0       # straggler: skip sync if > 3x median
    min_history: int = 8               # steps/periods before deadlines fire
    log_every: int = 10                # fused: periods between metric drains
    fused_period: bool = False         # period-granularity execution
    # how a fused period is executed (see DESIGN.md):
    #  "pipeline" — H donated per-phase executables dispatched back-to-back
    #               with ONE host sync per period; bitwise-identical to the
    #               per-step oracle by construction (same executables)
    #  "compiled" — one donated make_period_step executable (lax.scan over
    #               the pre-batched period); maximum fusion — XLA may
    #               re-round across phase boundaries (~1-2 ULP vs oracle)
    period_exec: str = "pipeline"
    # depth-k data staging (pipeline.py): how many future periods to keep
    # staged, and whether a daemon thread builds them off the train thread.
    # Batch VALUES are bitwise-identical across depths/modes by
    # construction — pure function of the step index.
    prefetch_depth: int = 1
    prefetch_background: bool = False


@dataclass
class Runner:
    model: Any
    optimizer: Any
    plan: SyncPlan
    data: Any                           # .batch(step) -> pytree
    ckpt: CheckpointManager | None = None
    step_cfg: StepConfig = field(default_factory=StepConfig)
    run_cfg: RunnerConfig = field(default_factory=RunnerConfig)

    def __post_init__(self):
        self._build_steps()
        self._times: list[float] = []
        self.period_times: list[float] = []
        self.history: list[dict] = []
        self.pending_units: set[int] = set()
        self.skipped_syncs = 0
        self.retries = 0
        self._undrained: list[tuple[int, float, dict]] = []

    def _build_steps(self) -> None:
        """(Re)compile the phase-specialized steps for the current plan.

        Every step donates its input state (``donate_argnums=0``), so a
        phase updates the state buffers in place.  The per-step path and
        the fused ``pipeline`` executor dispatch these SAME executables,
        which is what makes the two bitwise-identical by construction.
        """
        self._steps = [jax.jit(make_train_step(
            self.model, self.optimizer, self.plan, h, cfg=self.step_cfg),
            donate_argnums=0) for h in range(self.plan.H)]
        # a pure local step (no sync) for straggler-skipped phases
        local = make_train_step(self.model, self.optimizer,
                                local_plan(self.plan.n_units), 0,
                                cfg=self.step_cfg)
        local.__name__ = local.__qualname__ = "local_step"
        self._local = jax.jit(local, donate_argnums=0)
        self._makeup_cache: dict[tuple, Callable] = {}
        # whole-period programs keyed by makeup-unit tuple ("compiled"
        # mode), built lazily on first fused run
        self._period_cache: dict[tuple, Callable] = {}
        self._prefetch: PeriodPrefetcher | None = None

    def replan(self, new_plan: SyncPlan) -> None:
        """Hot-swap the schedule mid-run (elasticity / bandwidth drift).

        Pending straggler make-ups are kept — unit ids refer to the same
        network-order layout — but the phase executables are rebuilt so
        every subsequent step runs the new partition.
        """
        if new_plan.n_units != self.plan.n_units:
            raise ValueError(
                f"replan changed the unit count ({self.plan.n_units} -> "
                f"{new_plan.n_units}); the model layout must be stable")
        self.plan = new_plan
        self._build_steps()

    # ------------------------------------------------------------------ util
    def _median_time(self) -> float:
        xs = sorted(self._times[-64:])
        return xs[len(xs) // 2] if xs else float("inf")

    def _median_period_time(self) -> float:
        xs = sorted(self.period_times[-64:])
        return xs[len(xs) // 2] if xs else float("inf")

    def _makeup_step(self, units: tuple[int, ...]):
        if units not in self._makeup_cache:
            self._makeup_cache[units] = compose_makeup_step(
                self._local, units, self.model.unit_layout())
        return self._makeup_cache[units]

    def _period_step(self, makeup: tuple[int, ...]):
        if makeup not in self._period_cache:
            self._period_cache[makeup] = make_period_step(
                self.model, self.optimizer, self.plan, cfg=self.step_cfg,
                makeup_units=makeup)
        return self._period_cache[makeup]

    def _can_restore(self) -> bool:
        """Only swallow a failure if a checkpoint actually exists to
        restart from — otherwise a restore FileNotFoundError would mask
        the real error.  latest_step() itself may raise (it surfaces a
        failed async save); never let that replace the training
        exception."""
        if self.ckpt is None or self.retries >= self.run_cfg.max_retries:
            return False
        try:
            return self.ckpt.latest_step() is not None
        except Exception:                             # noqa: BLE001
            return False

    @hot_path
    def _drain_metrics(self) -> None:
        """Convert device-resident period metrics into history rows.

        Fused periods stash ``(first_step, period_dt, metrics[H])``
        device-side; this is the only host transfer on the fused path
        and runs every ``log_every`` periods (plus at run end / before
        a checkpoint restore).  ONE batched ``jax.device_get`` covers
        every undrained period — not one sync per key per period — so
        a drain costs a single host round-trip regardless of cadence."""
        if not self._undrained:
            return
        with spans.span(spans.DRAIN, self._undrained[0][0]):
            drained = jax.device_get([m for _, _, m in self._undrained])
        for (r0, dt, _), metrics in zip(self._undrained, drained, strict=True):
            if isinstance(metrics, list):      # pipeline: H per-phase dicts
                host = [{k: float(v) for k, v in m.items()}
                        for m in metrics]
            else:                              # compiled: dict of [H] arrays
                h_count = len(next(iter(metrics.values())))
                host = [{k: float(v[h]) for k, v in metrics.items()}
                        for h in range(h_count)]
            for h, row in enumerate(host):
                self.history.append({
                    "step": r0 + h,
                    "phase": self.plan.phase_of_iteration(r0 + h),
                    "time": dt / len(host), **row})
        self._undrained.clear()

    # ------------------------------------------------------------------- run
    def run(self, state: TrainState, n_steps: int, *,
            start_step: int = 0, fused: bool | None = None,
            inject_failure_at: int | None = None,
            inject_straggler_at: tuple[int, float] | None = None
            ) -> TrainState:
        """Train; ``inject_*`` hooks are for fault-tolerance tests.

        The runner takes ownership of ``state``: every step donates its
        input buffers, so the caller's ``state`` is invalid afterwards —
        keep the returned state instead (or pass a copy).

        ``fused=None`` follows ``RunnerConfig.fused_period`` — except
        when an injection hook is supplied, which drops to the per-step
        oracle (hooks address individual iterations).  Pass
        ``fused=True`` to keep the fused path with hooks re-expressed
        at period granularity (a failure/straggler lands on the period
        containing the named step).
        """
        if fused is None:
            fused = (self.run_cfg.fused_period
                     and inject_failure_at is None
                     and inject_straggler_at is None)
        if not fused:
            return self._run_per_step(state, n_steps,
                                      start_step=start_step,
                                      inject_failure_at=inject_failure_at,
                                      inject_straggler_at=inject_straggler_at)
        return self._run_fused(state, n_steps, start_step=start_step,
                               inject_failure_at=inject_failure_at,
                               inject_straggler_at=inject_straggler_at)

    # -------------------------------------------------------- per-step path
    @hot_path
    def _run_per_step(self, state: TrainState, n_steps: int, *,
                      start_step: int = 0,
                      inject_failure_at: int | None = None,
                      inject_straggler_at: tuple[int, float] | None = None
                      ) -> TrainState:
        r = start_step
        while r < start_step + n_steps:
            with spans.step_span(spans.STEP, r):
                phase = self.plan.phase_of_iteration(r)
                batch = place_worker_axis(self.data.batch(r))
                t0 = time.perf_counter()
                try:
                    if inject_failure_at == r:
                        inject_failure_at = None
                        raise RuntimeError("injected node failure")

                    if self.pending_units and phase == 0:
                        fn = self._makeup_step(
                            tuple(sorted(self.pending_units)))
                        self.pending_units.clear()
                    else:
                        fn = self._steps[phase]
                    state, metrics = fn(state, batch)
                    # block on the COMPLETED step — params included —
                    # before stamping the deadline clock.  Blocking only
                    # on the loss (the old behaviour) measured dispatch +
                    # forward but let the phase's parameter all-reduce
                    # keep running, so a stalled link never tripped
                    # `deadline_factor`.
                    jax.block_until_ready((state, metrics))
                except Exception:                         # noqa: BLE001
                    if not self._can_restore():
                        raise
                    self.retries += 1
                    r0, state, _ = self._restore_into(state)
                    r = r0
                    continue

                dt = time.perf_counter() - t0
                if inject_straggler_at is not None and \
                        inject_straggler_at[0] == r:
                    dt += inject_straggler_at[1]
                    inject_straggler_at = None
                # straggler policy: if this was a sync phase and it blew
                # the deadline, requeue its units and remember to
                # skip-equivalent (the sync already happened here; the
                # policy matters when the *link* stalls — we model it by
                # requeueing the NEXT occurrence)
                if (len(self._times) >= self.run_cfg.min_history
                        and self.plan.is_parameter_sync
                        and self.plan.units_for_phase(phase)
                        and dt > self.run_cfg.deadline_factor
                        * self._median_time()):
                    self.pending_units.update(
                        self.plan.units_for_phase(phase))
                    self.skipped_syncs += 1
                self._times.append(dt)

                # the block above already synced; one device_get batches the
                # (cheap, already-computed) metric transfers per step
                row = jax.device_get(metrics)
                self.history.append({"step": r, "phase": phase,
                                     "time": dt,
                                     **{k: float(v) for k, v in
                                        row.items()}})
                if self.ckpt is not None and (r + 1) % \
                        self.run_cfg.ckpt_every == 0:
                    with spans.span(spans.CHECKPOINT, r):
                        self.ckpt.save(r + 1, state,
                                       meta={"plan": self.plan.to_json()})
                r += 1
        if self.ckpt is not None:
            self.ckpt.wait()
        return state

    # ----------------------------------------------------------- fused path
    @hot_path
    def _run_fused(self, state: TrainState, n_steps: int, *,
                   start_step: int = 0,
                   inject_failure_at: int | None = None,
                   inject_straggler_at: tuple[int, float] | None = None
                   ) -> TrainState:
        """One donated executable per whole synchronization period.

        Iterations that don't fill a whole period — a mis-aligned start
        (elastic restore / replan landing mid-period) or the run's tail
        — fall through to the per-step oracle, so any ``start_step`` /
        ``n_steps`` combination is exact.
        """
        mode = self.run_cfg.period_exec
        if mode not in ("pipeline", "compiled"):
            raise ValueError(f"period_exec must be 'pipeline' or "
                             f"'compiled', got {mode!r}")
        H = self.plan.H
        r, end = start_step, start_step + n_steps
        stacked = mode == "compiled"
        cfg = self.run_cfg
        if self._prefetch is None or self._prefetch.data is not self.data \
                or self._prefetch.h != H or self._prefetch.stacked != stacked \
                or self._prefetch.depth != max(1, cfg.prefetch_depth) \
                or self._prefetch.background != cfg.prefetch_background:
            self._prefetch = PeriodPrefetcher(
                self.data, H, stacked=stacked, depth=cfg.prefetch_depth,
                background=cfg.prefetch_background)
        pipe = self._prefetch

        def in_period(step):
            return step is not None and r <= step < r + H

        while r < end:
            if r % H != 0 or r + H > end:
                # partial period: per-step oracle up to the next period
                # boundary (or the end of the run).  Drain first so
                # history rows stay in step order.
                self._drain_metrics()
                n = min(end - r, H - r % H if r % H else end - r)
                fail = strag = None
                if inject_failure_at is not None and \
                        r <= inject_failure_at < r + n:
                    fail, inject_failure_at = inject_failure_at, None
                if inject_straggler_at is not None and \
                        r <= inject_straggler_at[0] < r + n:
                    strag, inject_straggler_at = inject_straggler_at, None
                state = self._run_per_step(state, n, start_step=r,
                                           inject_failure_at=fail,
                                           inject_straggler_at=strag)
                r += n
                continue

            with spans.step_span(spans.PERIOD, r):
                with spans.span(spans.STAGE, r):
                    batch = pipe.get(r)
                t0 = time.perf_counter()
                try:
                    if in_period(inject_failure_at):
                        inject_failure_at = None
                        raise RuntimeError("injected node failure")

                    makeup = ()
                    if self.pending_units:
                        makeup = tuple(sorted(self.pending_units))
                        self.pending_units.clear()
                    with spans.span(spans.DISPATCH, r):
                        if mode == "compiled":
                            fn = self._period_step(makeup)
                            state, metrics = fn(state, batch)  # async dispatch
                        else:
                            # back-to-back async dispatch of the per-step
                            # oracle's own phase executables: no host
                            # round-trip between phases, one block at the
                            # period boundary
                            metrics = []
                            for h in range(H):
                                if h == 0 and makeup:
                                    fn = self._makeup_step(makeup)
                                else:
                                    fn = self._steps[h]
                                state, m = fn(state, batch[h])
                                metrics.append(m)
                    if r + 2 * H <= end:
                        # stage p+1..p+depth under p's compute; never past
                        # the last full period of this run
                        with spans.span(spans.STAGE, r):
                            pipe.prefetch(r + H, last=end - H)
                    # blocking on (state, metrics) times the COMPLETED period
                    # — parameter syncs included — with one host sync per H
                    # steps instead of per step
                    with spans.span(spans.WAIT, r):
                        jax.block_until_ready((state, metrics))
                except Exception:                         # noqa: BLE001
                    if not self._can_restore():
                        raise
                    self.retries += 1
                    self._drain_metrics()
                    pipe.invalidate()
                    r0, state, _ = self._restore_into(state)
                    r = r0
                    continue

                dt = time.perf_counter() - t0
                if inject_straggler_at is not None and \
                        in_period(inject_straggler_at[0]):
                    dt += inject_straggler_at[1]
                    inject_straggler_at = None
                # straggler deadline at period granularity: a blown period
                # can't be attributed to one phase from outside the
                # executable, so every unit the period syncs is re-queued
                # for make-up (a superset of the oracle's requeue — extra
                # syncs only tighten Lemma 4's staleness bound)
                if (len(self.period_times) >= self.run_cfg.min_history
                        and self.plan.is_parameter_sync
                        and dt > self.run_cfg.deadline_factor
                        * self._median_period_time()):
                    self.pending_units.update(self.plan.all_sync_units())
                    self.skipped_syncs += 1
                self.period_times.append(dt)

                self._undrained.append((r, dt, metrics))
                if len(self._undrained) >= self.run_cfg.log_every:
                    self._drain_metrics()
                if self.ckpt is not None and \
                        (r + H) // self.run_cfg.ckpt_every > \
                        r // self.run_cfg.ckpt_every:
                    with spans.span(spans.CHECKPOINT, r):
                        self.ckpt.save(r + H, state,
                                       meta={"plan": self.plan.to_json()})
                r += H
        self._drain_metrics()
        if self.ckpt is not None:
            self.ckpt.wait()
        return state

    def _restore_into(self, template: TrainState):
        step, state, meta = self.ckpt.restore(template)
        return step, state, meta

    def restore_elastic(self, template: TrainState, n_workers: int,
                        new_plan: SyncPlan) -> tuple[int, TrainState]:
        """Restore onto a different worker count (elastic membership)."""
        step, state, _ = self.ckpt.restore(template)
        state = reshard_train_state(state, n_workers)
        self.replan(new_plan)
        return int(state.step), state
