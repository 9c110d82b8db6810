"""Phase-specialized train/serve step builders.

DreamDDP compiles **one executable per phase** of the synchronization
period: the phase's layer interval is baked in as static slices, so the
emitted HLO contains exactly the scheduled collective bytes, and the block
stack is split (``segment_cuts``) at the interval boundary so the phase's
parameter all-reduce is data-independent of the remaining backward segments
— the overlap window XLA's latency-hiding scheduler uses (DESIGN.md §2).

The step builder is algorithm-agnostic: the plan's ``comm`` field (data,
set by the :class:`~repro.api.SyncStrategy` that built it) says whether
gradients are worker-averaged before the optimizer (classic DDP) or the
phase's layer units are parameter-averaged after the local update (Eq. 5),
and the *how* of each parameter sync is a composable
:class:`~repro.core.sync_policies.SyncPolicy` (plain mean / int8+EF /
DiLoCo outer step) resolved once per step build — there is no per-algorithm
branching here.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from ..core.outer_opt import OuterConfig, OuterState
from ..core.partial_sync import (UnitLayout, contiguous_ranges, divergence,
                                 sync_units, tree_worker_mean)
from ..core.plans import SyncPlan, local_plan
from ..core.sync_policies import SyncPolicy, resolve_policy
from ..optim.optimizers import Optimizer
from ..parallel.sharding import (constrain_worker_axis, worker_mesh,
                                 worker_shardings)

__all__ = ["TrainState", "StepConfig", "init_train_state",
           "make_train_step", "make_phase_steps", "make_period_step",
           "make_prefill_step", "make_decode_step",
           "make_slot_prefill_step", "make_slot_prefill_step_batched",
           "make_slot_refeed_step", "make_slot_refeed_step_batched",
           "make_slot_decode_step", "make_slot_decode_step_paged"]

PyTree = Any


class TrainState(NamedTuple):
    params: PyTree                    # worker-stacked [W, ...]
    opt_state: PyTree
    step: jax.Array
    ef: PyTree | None = None          # int8 error-feedback residuals
    outer: OuterState | None = None   # DiLoCo outer state


@dataclass(frozen=True)
class StepConfig:
    n_microbatches: int = 1
    policy: SyncPolicy | None = None  # explicit sync policy (wins)
    compress: str | None = None       # legacy flag: None | "int8_ef"
    outer: bool = False               # legacy flag: DiLoCo outer optimizer
    outer_cfg: OuterConfig = field(default_factory=OuterConfig)
    track_divergence: bool = False
    segment_cuts: bool = True         # split scans at the sync interval


def init_train_state(model, optimizer: Optimizer, key, n_workers: int,
                     *, cfg: StepConfig = StepConfig()) -> TrainState:
    """Identical initial replicas (workers start at a sync point).

    With several devices the worker axis is spread over them
    (:func:`~repro.parallel.sharding.worker_shardings`), so a phase's
    partial sync runs as a cross-device all-reduce.  The state is then
    built in place: each device makes only its own replicas, and the
    whole stack never sits on one device first."""
    from ..core.partial_sync import worker_stack

    def build() -> TrainState:
        params = worker_stack(model.init(key), n_workers)
        opt_state = optimizer.init(params)
        ef, outer = resolve_policy(cfg).init_state(params)
        return TrainState(params, opt_state, jnp.zeros((), jnp.int32), ef,
                          outer)

    if worker_mesh(n_workers) is None:
        return build()
    shardings = worker_shardings(jax.eval_shape(build))
    return jax.jit(build, out_shardings=shardings)()


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------

def _cuts_for(units, layout: UnitLayout) -> tuple[int, ...]:
    """Segment-cut unit ids: boundaries of the synced intervals."""
    cuts = set()
    for lo, hi in contiguous_ranges(list(units)):
        cuts.add(lo)
        cuts.add(hi)
    return tuple(sorted(cuts))


def make_train_step(model, optimizer: Optimizer, plan: SyncPlan, phase: int,
                    *, cfg: StepConfig = StepConfig(),
                    donate: bool = True):
    """Build the jittable step for one phase (phase is STATIC).

    The step is named ``phase_<phase>`` (its jitted module is
    ``jit_phase_<phase>``), and its parts run under named scopes that
    the compiled HLO's ``op_name`` metadata carries: ``fwd`` around the
    differentiated loss (forward ``jvp(fwd)``, backward
    ``transpose(jvp(fwd))``, remat recompute beneath that in
    ``rematted_computation``), ``optimizer`` around the update (clip
    included) and ``sync`` around the gradient mean and the parameter
    sync."""
    layout = model.unit_layout()
    units = plan.units_for_phase(phase)
    cuts = _cuts_for(units, layout) if cfg.segment_cuts else ()
    policy = resolve_policy(cfg)

    def per_worker_grads(params, batch):
        """Per-worker loss+grads.  With ``n_microbatches > 1`` the batch
        arrives PRE-microbatched ``[n_micro, B_micro, ...]`` (the data
        pipeline / cell builder adds the axis, keeping shardings static
        through the accumulation scan)."""
        def loss_fn(params, batch):
            with jax.named_scope("fwd"):
                return model.loss(params, batch, segment_cuts=cuts)

        if cfg.n_microbatches == 1:
            return jax.value_and_grad(loss_fn)(params, batch)

        def body(acc, mbatch):
            l, g = jax.value_and_grad(loss_fn)(params, mbatch)
            return (acc[0] + l,
                    jax.tree.map(jnp.add, acc[1], g)), None

        zero = (jnp.zeros((), jnp.float32),
                jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32),
                             params))
        (loss, grads), _ = jax.lax.scan(body, zero, batch)
        inv = 1.0 / cfg.n_microbatches
        return loss * inv, jax.tree.map(lambda g: g * inv, grads)

    def worker_grads(params, batch):
        """Every worker's loss and grads.  With the worker axis spread
        over devices, each device runs its own workers under
        ``shard_map``: worker-local work needs no collective, and the
        Pallas kernels inside (which XLA cannot partition) see one
        device's share."""
        grads_fn = jax.vmap(per_worker_grads)
        mesh = worker_mesh(jax.tree_util.tree_leaves(params)[0].shape[0])
        if mesh is None:
            return grads_fn(params, batch)
        spec = jax.sharding.PartitionSpec("data")
        return jax.shard_map(grads_fn, mesh=mesh, in_specs=(spec, spec),
                             out_specs=spec, check_vma=False)(params, batch)

    def train_step(state: TrainState, batch: PyTree
                   ) -> tuple[TrainState, dict]:
        losses, grads = worker_grads(state.params, batch)
        metrics = {"loss": jnp.mean(losses)}

        if not plan.is_parameter_sync:
            with jax.named_scope("sync"):
                grads = tree_worker_mean(grads)  # DDP: gradient all-reduce

        with jax.named_scope("optimizer"):
            new_params, new_opt = optimizer.update(grads, state.opt_state,
                                                   state.params, state.step)
        new_ef, new_outer = state.ef, state.outer
        if plan.is_parameter_sync and units:
            with jax.named_scope("sync"):
                new_params, new_ef, new_outer = policy.apply(
                    new_params, state.ef, state.outer, units, layout)
        if cfg.track_divergence:
            metrics["divergence"] = divergence(new_params)
        new_state = TrainState(new_params, new_opt, state.step + 1,
                               new_ef, new_outer)
        return constrain_worker_axis(new_state), metrics

    train_step.__name__ = train_step.__qualname__ = f"phase_{phase}"
    return train_step


def make_phase_steps(model, optimizer: Optimizer, plan: SyncPlan, *,
                     cfg: StepConfig = StepConfig()):
    """One step function per phase of the period (all static)."""
    return [make_train_step(model, optimizer, plan, h, cfg=cfg)
            for h in range(plan.H)]


def compose_makeup_step(local_step, units, layout: UnitLayout):
    """Straggler make-up body: a pure local step followed by an extra
    sync of exactly ``units`` — the ONE definition of make-up semantics,
    shared by the runner's per-step cache and the fused period builder.
    """
    units = tuple(sorted(units))

    def makeup_step(state: TrainState, batch: PyTree):
        new_state, m = local_step(state, batch)
        with jax.named_scope("sync"):
            params = sync_units(new_state.params, list(units), layout)
        return new_state._replace(params=params), m

    return makeup_step


def make_period_step(model, optimizer: Optimizer, plan: SyncPlan, *,
                     cfg: StepConfig = StepConfig(),
                     makeup_units: tuple[int, ...] = (),
                     donate: bool = True):
    """Roll ALL ``H`` phase steps of ``plan`` into ONE jitted executable.

    The per-step path dispatches one jitted call per iteration from
    Python, so phase boundaries are host round-trips and XLA can only
    overlap collectives with compute *inside* a single step's HLO.  The
    period step takes the whole period's data pre-batched on a leading
    phase axis (``{tokens: [H, W, B, S], ...}``) and composes the
    phase-specialized bodies statically: consecutive phases with an
    identical unit set (``plan.phase_segments()``) become one
    ``lax.scan`` segment over their batch slice; distinct phases are
    chained directly.  Each phase keeps its exact scheduled collective
    bytes and ``segment_cuts`` overlap windows (the phase index is
    static per segment), and because the whole period is one program,
    XLA's latency-hiding scheduler can float phase *h*'s parameter
    all-reduce across phase *h+1*'s forward — the cross-iteration
    overlap DreamDDP's schedule is designed for.

    ``makeup_units`` (straggler make-up at a period boundary) replaces
    phase 0's body with the oracle's make-up semantics: a pure local
    step followed by an extra sync of exactly those units.

    Metrics come back device-resident with a leading ``[H]`` phase axis
    — the runner drains them on its ``log_every`` cadence instead of
    blocking every step.  The input state's buffers are donated by
    default (the period executable updates parameters in place).
    """
    layout = model.unit_layout()
    segments = list(plan.phase_segments())
    if makeup_units:
        # phase 0 gets its own body; split it out of its segment
        s0, l0 = segments[0]
        segments = [(0, 1)] + ([(1, l0 - 1)] if l0 > 1 else []) \
            + segments[1:]

    bodies: dict[int, Any] = {}
    for start, _ in segments:
        if start == 0 and makeup_units:
            local = make_train_step(model, optimizer,
                                    local_plan(plan.n_units), 0, cfg=cfg)
            bodies[0] = compose_makeup_step(local, makeup_units, layout)
        else:
            bodies[start] = make_train_step(model, optimizer, plan, start,
                                            cfg=cfg)

    def period_step(state: TrainState, batch: PyTree
                    ) -> tuple[TrainState, dict]:
        per_seg = []
        for start, length in segments:
            body = bodies[start]
            if length == 1:
                b = jax.tree.map(lambda x, s=start: x[s], batch)
                state_, m = body(state, b)
                state = state_
                per_seg.append(jax.tree.map(lambda v: v[None], m))
            else:
                seg = jax.tree.map(
                    lambda x, s=start, n=length: x[s:s + n], batch)
                state, ms = jax.lax.scan(body, state, seg)
                per_seg.append(ms)
        if len(per_seg) == 1:
            metrics = per_seg[0]
        else:
            metrics = jax.tree.map(
                lambda *xs: jnp.concatenate(xs, axis=0), *per_seg)
        return state, metrics

    return jax.jit(period_step, donate_argnums=(0,) if donate else ())


# ---------------------------------------------------------------------------
# Serve steps
# ---------------------------------------------------------------------------

def make_prefill_step(model, *, with_frontend: str | None = None):
    if with_frontend == "audio":
        def prefill(params, tokens, cache, frames):
            return model.prefill(params, tokens, cache, frames)
    elif with_frontend == "vision":
        def prefill(params, tokens, cache, embeds):
            return model.prefill(params, tokens, cache, embeds=embeds)
    else:
        def prefill(params, tokens, cache):
            return model.prefill(params, tokens, cache)
    return prefill


def make_decode_step(model):
    def decode(params, cache, token, pos):
        return model.decode_step(params, cache, token, pos)
    return decode


# ---------------------------------------------------------------------------
# Slot-pooled serve steps (continuous batching; see repro.serve)
# ---------------------------------------------------------------------------
#
# Cache leaves are [layers, slots, ...] across every model family, so a
# "slot" is one lane of axis 1.  The legacy decode path shares one write
# position across the whole batch (``write_pos[0]``); these variants vmap
# the model's own single-sequence step over the slot axis instead, which
# gives every slot an independent write position and sequence length — the
# property continuous batching needs — without touching the models.

_SLOT_AXIS = 1


def _slot_view(arena, slot):
    """One-lane view ``[layers, 1, ...]`` of the arena at ``slot`` (traced
    index: no recompile per slot)."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=_SLOT_AXIS),
        arena)


def _slot_write(arena, new, slot):
    """Scatter a one-lane cache back into the arena at ``slot``."""
    return jax.tree.map(
        lambda a, n: jax.lax.dynamic_update_slice_in_dim(
            a, n.astype(a.dtype), slot, axis=_SLOT_AXIS), arena, new)


def _slots_view(arena, slots):
    """K-lane view ``[layers, K, ...]`` of the arena at ``slots [K]``
    (traced index vector: no recompile per slot assignment)."""
    return jax.tree.map(lambda a: jnp.take(a, slots, axis=_SLOT_AXIS),
                        arena)


def _slots_write(arena, new, slots):
    """Scatter a K-lane cache back into the arena at ``slots [K]``."""
    return jax.tree.map(
        lambda a, n: a.at[:, slots].set(n.astype(a.dtype)), arena, new)


def make_slot_prefill_step(model, *, with_frontend: str | None = None):
    """Prefill one request into arena slot ``slot``.

    ``tokens`` is ``[1, S]``; compiles once per distinct prompt length
    (``slot`` is a traced scalar).  Returns (last-token logits ``[1, 1,
    V]``, updated arena).
    """
    prefill = make_prefill_step(model, with_frontend=with_frontend)

    def slot_prefill(params, arena, tokens, slot, *extra):
        logits, new = prefill(params, tokens, _slot_view(arena, slot),
                              *extra)
        return logits, _slot_write(arena, new, slot)

    return slot_prefill


def make_slot_refeed_step(model):
    """Re-decode the last prompt token of one slot at position ``pos``.

    Used by chunked prefill: after a right-padded prefill the returned
    logits belong to a pad position, so the true last-token logits are
    recovered by one decode step (which rewrites the identical KV entry at
    ``pos`` and attends the same causal window the unpadded prefill would
    have).
    """
    def refeed(params, arena, slot, token, pos):
        logits, new = model.decode_step(params, _slot_view(arena, slot),
                                        token[None, None], pos[None])
        return logits, _slot_write(arena, new, slot)

    return refeed


def make_slot_prefill_step_batched(model, *,
                                   with_frontend: str | None = None):
    """Prefill K same-length requests into arena slots ``slots`` in ONE
    call.

    ``tokens`` is ``[K, S]`` (one row per admitted request, all padded to
    the same bucket length), ``slots [K]`` a traced index vector, and any
    frontend ``extra`` inputs arrive stacked ``[K, ...]``.  The model's
    own batched ``prefill`` runs over the K gathered lanes (every lane
    writes from position 0, which is exactly the native prefill
    contract), so the whole admission group costs one executable launch
    instead of K.  Compiles once per ``(K, S)`` — both are bounded
    (``K <= max_batch``, ``S`` by the prompt-length buckets), so the
    compile-cache contract of the serial path is preserved.

    Returns (last-token logits ``[K, V]``, updated arena).
    """
    prefill = make_prefill_step(model, with_frontend=with_frontend)

    def slot_prefill_batched(params, arena, tokens, slots, *extra):
        logits, new = prefill(params, tokens, _slots_view(arena, slots),
                              *extra)
        return logits[:, 0], _slots_write(arena, new, slots)

    return slot_prefill_batched


def make_slot_refeed_step_batched(model):
    """Re-decode the last prompt token of K slots in ONE call.

    The batched counterpart of :func:`make_slot_refeed_step`: ``slots
    [K]`` / ``tokens [K]`` / ``pos [K]`` — each lane rewrites its own KV
    entry at its own position (vmapped over the gathered lanes, same
    per-lane semantics as the serial refeed).  Returns (logits ``[K,
    V]``, updated arena).
    """
    def one(cache_i, token, pos, params):
        cache_i = jax.tree.map(lambda a: a[:, None], cache_i)
        logits, new = model.decode_step(params, cache_i, token[None, None],
                                        pos[None])
        return logits[0, 0], jax.tree.map(lambda a: a[:, 0], new)

    def slot_refeed_batched(params, arena, slots, tokens, pos):
        sub = _slots_view(arena, slots)
        axes = jax.tree.map(lambda _: _SLOT_AXIS, sub)
        logits, new = jax.vmap(
            one, in_axes=(axes, 0, 0, None),
            out_axes=(0, axes))(sub, tokens, pos, params)
        return logits, _slots_write(arena, new, slots)

    return slot_refeed_batched


def make_slot_decode_step(model):
    """Batched one-token decode with PER-SLOT write positions.

    ``tokens [S]`` / ``pos [S]`` -> (logits ``[S, V]``, arena).  The
    model's ``decode_step`` is vmapped over the slot axis, so each lane
    advances at its own position (and recurrent families update each
    lane's state independently).
    """
    def one(cache_i, token, pos, params):
        # vmap strips the slot axis; reinsert a singleton batch axis for the
        # model's [layers, batch, ...] cache contract and strip it again on
        # the way out (out_axes restores the slot axis).
        cache_i = jax.tree.map(lambda a: a[:, None], cache_i)
        logits, new = model.decode_step(params, cache_i, token[None, None],
                                        pos[None])
        return logits[0, 0], jax.tree.map(lambda a: a[:, 0], new)

    def slot_decode(params, arena, tokens, pos):
        axes = jax.tree.map(lambda _: _SLOT_AXIS, arena)
        logits, new_arena = jax.vmap(
            one, in_axes=(axes, 0, 0, None),
            out_axes=(0, axes))(arena, tokens, pos, params)
        return logits, new_arena

    return slot_decode


def make_slot_decode_step_paged(model):
    """Batched one-token decode against a **paged** KV pool.

    Same contract as :func:`make_slot_decode_step` (``tokens [S]`` /
    ``pos [S]`` -> logits ``[S, V]``), but the arena is the model's page
    pool and two extra per-tick inputs route the KV traffic: the
    per-slot ``block_tables [S, max_blocks]`` and the ``active [S]``
    mask (inactive lanes park their writes on the trash page so a
    retired slot's stale table can never corrupt re-allocated pages).
    KV-cache families (transformer / moe / mla) implement
    ``decode_step_paged``; recurrent-state families (mamba2 / rglru)
    have no position-addressed KV to page and keep their fixed-size
    state lanes on the contiguous path.
    """
    if not getattr(model, "supports_paged_kv", False):
        raise ValueError(
            f"{type(model).__name__} does not support a paged KV cache "
            "(recurrent state lanes / cross-attention KV are fixed-size "
            "per slot) — use the contiguous backend")

    def slot_decode(params, pages, tokens, pos, block_tables, active):
        logits, new_pages = model.decode_step_paged(
            params, pages, tokens[:, None], pos, block_tables, active)
        return logits[:, 0], new_pages

    return slot_decode
