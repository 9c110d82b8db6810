"""Logical-axis -> mesh sharding rules.

Models annotate every parameter leaf with logical axis names
(``'vocab' | 'heads' | 'ff' | 'expert' | 'layers' | None``).  This module
turns those into :class:`jax.sharding.PartitionSpec`s for a given mesh:

* tensor/expert parallel: ``vocab/heads/ff/expert -> 'model'``;
* the worker axis (divergent local-SGD replicas) is **prepended** to every
  spec — ``('data',)`` / ``('pod','data')`` for small archs, ``('pod',)``
  for large ones, ``()`` when W == 1;
* FSDP (large archs): the first unsharded non-layer dim of every >=2D leaf
  is sharded over ``'data'`` (ZeRO-3-style storage; GSPMD all-gathers per
  layer inside the scan).

Batch specs: training batches are ``[W, B/W, S]`` -> ``P(worker_axes,
leftover_data_axes)``; serving batches shard over ``'data'`` and activations
inherit from the einsums.
"""

from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["RULES", "leaf_spec", "param_shardings", "batch_shardings",
           "named", "cache_shardings", "maybe_constrain", "worker_mesh",
           "worker_shardings", "place_worker_axis", "constrain_worker_axis"]


def maybe_constrain(x, *dims):
    """`with_sharding_constraint` that degrades to identity when no mesh
    (or a mesh without the named axes) is ambient — model code stays
    runnable on bare CPU while dry-run lowering (under ``jax.set_mesh``)
    gets the constraint.  Used to pin activation shardings where GSPMD's
    solver otherwise picks contraction-dim partial sums (§Perf).

    ``None`` dims are left UNCONSTRAINED (a ``None`` in a raw
    with_sharding_constraint means *replicated*, which would force
    gathers on batch dims — measured as +78% FLOPs in the dsv3 cell).
    Named dims are dropped when the dim size does not divide the axis.
    """
    mesh = jax.sharding.get_abstract_mesh()    # empty when none is set
    names = mesh.axis_names
    want = {d for dd in dims if dd is not None
            for d in ((dd,) if isinstance(dd, str) else dd)}
    if not names or not want.issubset(set(names)):
        return x
    sizes = dict(mesh.shape)

    def ax_size(dd):
        if isinstance(dd, str):
            return sizes.get(dd, 1)
        n = 1
        for a in dd:
            n *= sizes.get(a, 1)
        return n

    spec = []
    for i, dd in enumerate(dims):
        if dd is None:
            spec.append(P.UNCONSTRAINED)
        elif x.shape[i] % ax_size(dd) == 0:
            spec.append(dd)
        else:
            spec.append(P.UNCONSTRAINED)
    return jax.lax.with_sharding_constraint(x, P(*spec))

PyTree = Any

RULES: dict[str | None, str | None] = {
    "vocab": "model",
    "heads": "model",
    "ff": "model",
    "expert": "model",
    "layers": None,
    None: None,
}


def leaf_spec(logical: tuple, *, worker_axes: tuple[str, ...] = (),
              fsdp: bool = False, fsdp_axis: str = "data",
              with_lead: bool = True, shape: tuple[int, ...] | None = None,
              mesh: Mesh | None = None,
              rules: dict | None = None) -> P:
    """One leaf's PartitionSpec from its logical axes.

    Each mesh axis may appear at most once: the first logical dim claiming
    it wins (e.g. MoE ``('expert', None, 'ff')`` -> expert-parallel over
    ``model``, ``ff`` left unsharded).  ``with_lead`` prepends the worker
    axis entry (worker-stacked training trees); serving trees have no
    worker dim and pass ``with_lead=False``.  With ``shape``/``mesh`` a dim
    is only sharded when divisible by the mesh axis (explicitly-sharded jit
    arguments must divide evenly; e.g. vocab 50280 over model=16 falls back
    to replicated — noted in DESIGN.md)."""
    used = set(worker_axes)
    off = 1 if with_lead else 0
    rules = RULES if rules is None else rules

    def axes_of(m) -> tuple[str, ...]:
        return (m,) if isinstance(m, str) else tuple(m)

    def divisible(i: int, m) -> bool:
        if shape is None or mesh is None:
            return True
        size = 1
        for a in axes_of(m):
            size *= mesh.shape[a]
        return shape[i + off] % size == 0

    dims: list = []
    for i, ax in enumerate(logical):
        m = rules.get(ax, None)
        if m is not None and (any(a in used for a in axes_of(m))
                              or not divisible(i, m)):
            m = None
        if m is not None:
            used.update(axes_of(m))
        dims.append(m)
    if fsdp and fsdp_axis not in used:
        # shard the first unsharded, non-layer dim over `data`
        for i, (ax, d) in enumerate(zip(logical, dims, strict=True)):
            if d is None and ax != "layers" and len(logical) >= 2 \
                    and divisible(i, fsdp_axis):
                dims[i] = fsdp_axis
                break
    if not with_lead:
        return P(*dims)
    lead = (worker_axes if len(worker_axes) != 1 else worker_axes[0]) \
        if worker_axes else None
    return P(lead, *dims)


RULES_FSDP_MODEL: dict[str | None, str | None] = {
    # intra-worker ZeRO-3: no tensor parallel; weights sharded over the
    # model axis via the fsdp mechanism, batch sharded over `model`.
    # Expert dim keeps EP (weights already partitioned by expert).
    "vocab": None, "heads": None, "ff": None, "expert": "model",
    "layers": None, None: None,
}

RULES_EP2: dict[str | None, object] = {
    # two-axis expert parallel: expert dim over (`data` x `model`) jointly
    # (256 experts / 256 chips = 1 expert/device, weights fully local —
    # no FSDP gathers or partial sums on the expert matmuls; token
    # redistribution rides the dispatch einsums).  §Perf dsv3 iteration.
    "vocab": "model", "heads": "model", "ff": None,
    "expert": ("data", "model"), "layers": None, None: None,
}


def param_shardings(spec_tree: PyTree, mesh: Mesh, *,
                    worker_axes: tuple[str, ...] = (),
                    fsdp: bool = False, with_lead: bool = True,
                    shapes: PyTree | None = None,
                    rules: dict | None = None,
                    fsdp_axis: str = "data") -> PyTree:
    """NamedShardings for a (worker-stacked) parameter tree.

    ``spec_tree`` mirrors the *unstacked* params (logical tuples at leaves);
    with ``with_lead`` the worker axis is assumed prepended to every leaf.
    ``shapes`` (a matching ShapeDtypeStruct tree) enables divisibility
    checks."""
    is_spec = lambda x: isinstance(x, tuple) and all(
        isinstance(e, (str, type(None))) for e in x)

    def one(sp, sds=None):
        return NamedSharding(
            mesh, leaf_spec(tuple(sp), worker_axes=worker_axes, fsdp=fsdp,
                            with_lead=with_lead,
                            shape=None if sds is None else tuple(sds.shape),
                            mesh=mesh, rules=rules, fsdp_axis=fsdp_axis))

    if shapes is None:
        return jax.tree.map(one, spec_tree, is_leaf=is_spec)
    return jax.tree.map(one, spec_tree, shapes, is_leaf=is_spec)


def worker_mesh(n_workers: int) -> Mesh | None:
    """1-D ``data`` mesh over the devices this process computes on, when
    there is more than one and ``n_workers`` divides evenly over them;
    ``None`` otherwise (the worker axis then stays on one device).  A
    device pinned with ``jax.default_device`` counts as the only one."""
    pinned = jax.config.jax_default_device
    devices = [pinned] if isinstance(pinned, jax.Device) else jax.devices()
    if len(devices) < 2 or n_workers % len(devices):
        return None
    return Mesh(np.asarray(devices), ("data",))


def worker_shardings(tree: PyTree, *, axis: int = 0) -> PyTree | None:
    """Per-leaf shardings that spread axis ``axis`` (the worker axis)
    over :func:`worker_mesh` and replicate leaves without it (the step
    counter); ``None`` when the worker axis stays on one device."""
    lead = [x for x in jax.tree_util.tree_leaves(tree) if np.ndim(x) > axis]
    mesh = worker_mesh(lead[0].shape[axis]) if lead else None
    if mesh is None:
        return None
    return jax.tree.map(lambda x: NamedSharding(
        mesh, P(*(None,) * axis, "data") if np.ndim(x) > axis else P()),
        tree)


def place_worker_axis(tree: PyTree, *, axis: int = 0) -> PyTree:
    """Put ``tree`` on the devices with its worker axis spread over them
    (see :func:`worker_shardings`), or on the default device when the
    worker axis stays on one."""
    return jax.device_put(tree, worker_shardings(tree, axis=axis))


def constrain_worker_axis(tree: PyTree) -> PyTree:
    """Inside a jitted step: keep a worker-stacked tree's axis 0 where
    :func:`place_worker_axis` put it.  Without this XLA replicates a leaf
    whose replicas were just averaged, which moves the state's placement
    (and recompiles every step that reads it)."""
    shardings = worker_shardings(tree)
    return tree if shardings is None else \
        jax.lax.with_sharding_constraint(tree, shardings)


def named(mesh: Mesh, *dims) -> NamedSharding:
    return NamedSharding(mesh, P(*dims))


def batch_shardings(batch_spec: PyTree, mesh: Mesh, *,
                    worker_axes: tuple[str, ...],
                    data_axes_left: tuple[str, ...]) -> PyTree:
    """Training batch ``[W, B/W, ...]``: worker axis + leftover data axes."""
    lead = (worker_axes if len(worker_axes) != 1 else worker_axes[0]) \
        if worker_axes else None
    sub = (data_axes_left if len(data_axes_left) != 1 else
           data_axes_left[0]) if data_axes_left else None

    def one(s):
        rest = (None,) * (len(s.shape) - 2)
        return NamedSharding(mesh, P(lead, sub, *rest))

    return jax.tree.map(one, batch_spec)


def cache_shardings(cache_spec: PyTree, mesh: Mesh, *,
                    batch_axes=("data",)) -> PyTree:
    """Serving caches ``[n_layers, B, S, ...]``: shard batch over data, and
    the head/state trailing dims over 'model' when present (>=4D leaves)."""
    ba = batch_axes if len(batch_axes) != 1 else batch_axes[0]

    def one(s):
        nd = len(s.shape)
        if nd >= 4:
            # [layers, B, S, heads, ...] -> heads over model
            dims = [None, ba, None, "model"] + [None] * (nd - 4)
        elif nd == 3:
            dims = [None, ba, None]
        else:
            dims = [None] * nd
        return NamedSharding(mesh, P(*dims))

    return jax.tree.map(one, cache_spec)
