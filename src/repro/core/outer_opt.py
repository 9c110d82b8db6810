"""Beyond-paper: DiLoCo-style outer optimization on partial syncs.

The paper averages parameters at each sync (``w <- mean_k w_k``).  DiLoCo
[Douillard et al., 2024] instead treats the averaged *delta* since the last
sync as a pseudo-gradient and applies an outer Nesterov-momentum step — known
to improve local-SGD convergence at the same communication cost.  DreamDDP's
layer-wise decoupling composes naturally: we keep per-unit outer state and
apply the outer update only to the units synchronized in the current phase.

Communication cost is identical to plain averaging (the all-reduce of the
unit's parameters); the outer params/momentum live *sharded the same way as
the params*, adding 2x the synced units' bytes in HBM — amortized over the
stack this is 2x params, so we default it OFF and enable via config
(``outer_opt=True``).  Recorded separately in EXPERIMENTS.md as beyond-paper.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, NamedTuple, Sequence

import jax
import jax.numpy as jnp

from .partial_sync import UnitLayout, contiguous_ranges

__all__ = ["OuterState", "outer_init", "outer_sync_units"]

PyTree = Any


class OuterState(NamedTuple):
    """Per-parameter outer-optimizer state (worker-stacked like params,
    but numerically identical across the worker axis)."""

    outer_params: PyTree   # the slow/global weights
    momentum: PyTree       # Nesterov momentum on pseudo-gradients


@dataclass(frozen=True)
class OuterConfig:
    lr: float = 0.7
    beta: float = 0.9
    nesterov: bool = True


def outer_init(worker_params: PyTree) -> OuterState:
    """Outer weights start at the (identical) initial replicas.

    ``jnp.array`` copies: the outer weights own their buffers even when
    the params are already float32, so a step that donates the whole
    train state never sees one buffer twice."""
    return OuterState(
        outer_params=jax.tree.map(lambda x: jnp.array(x, jnp.float32),
                                  worker_params),
        momentum=jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32),
                              worker_params),
    )


def _outer_step(outer: jax.Array, mom: jax.Array, avg: jax.Array,
                cfg: OuterConfig) -> tuple[jax.Array, jax.Array]:
    """One Nesterov step on the pseudo-gradient ``outer - avg``."""
    pseudo_grad = outer - avg.astype(jnp.float32)
    mom_new = cfg.beta * mom + pseudo_grad
    direction = pseudo_grad + cfg.beta * mom_new if cfg.nesterov else mom_new
    return outer - cfg.lr * direction, mom_new


def outer_sync_units(params: PyTree, state: OuterState,
                     unit_ids: Sequence[int], layout: UnitLayout,
                     cfg: OuterConfig = OuterConfig(),
                     ) -> tuple[PyTree, OuterState]:
    """Partial sync with outer optimization.

    For each synced unit: workers all-reduce (mean) their parameters, the
    outer optimizer consumes the mean as a pseudo-gradient, and every worker
    resets that unit to the new outer weights (a synchronization point, as in
    plain averaging — so Lemma 4's bounded-staleness argument still applies).
    """
    if not unit_ids:
        return params, state
    grouped = layout.by_group(unit_ids)
    new_params = dict(params)
    new_outer = dict(state.outer_params)
    new_mom = dict(state.momentum)

    for group, idxs in grouped.items():
        p, o, m = params[group], state.outer_params[group], state.momentum[group]
        if idxs == [None]:
            def full(p_, o_, m_):
                avg = jnp.mean(p_.astype(jnp.float32), axis=0, keepdims=True)
                o2, m2 = _outer_step(o_, m_, avg, cfg)
                return jnp.broadcast_to(o2.astype(p_.dtype), p_.shape), o2, m2
            trip = jax.tree.map(full, p, o, m)
            new_params[group] = jax.tree.map(lambda t: t[0], trip,
                                             is_leaf=lambda t: isinstance(t, tuple))
            new_outer[group] = jax.tree.map(lambda t: t[1], trip,
                                            is_leaf=lambda t: isinstance(t, tuple))
            new_mom[group] = jax.tree.map(lambda t: t[2], trip,
                                          is_leaf=lambda t: isinstance(t, tuple))
            continue
        ranges = contiguous_ranges([i for i in idxs if i is not None])

        def sliced(p_, o_, m_):
            for lo, hi in ranges:
                avg = jnp.mean(p_[:, lo:hi].astype(jnp.float32), axis=0,
                               keepdims=True)
                o2, m2 = _outer_step(o_[:, lo:hi], m_[:, lo:hi], avg, cfg)
                p_ = p_.at[:, lo:hi].set(
                    jnp.broadcast_to(o2.astype(p_.dtype), p_[:, lo:hi].shape))
                o_ = o_.at[:, lo:hi].set(o2)
                m_ = m_.at[:, lo:hi].set(m2)
            return p_, o_, m_

        trip = jax.tree.map(sliced, p, o, m)
        is_trip = lambda t: isinstance(t, tuple) and len(t) == 3 and all(
            isinstance(x, jax.Array) for x in t)
        new_params[group] = jax.tree.map(lambda t: t[0], trip, is_leaf=is_trip)
        new_outer[group] = jax.tree.map(lambda t: t[1], trip, is_leaf=is_trip)
        new_mom[group] = jax.tree.map(lambda t: t[2], trip, is_leaf=is_trip)

    return new_params, OuterState(new_outer, new_mom)
