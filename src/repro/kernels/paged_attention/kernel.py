"""Blockwise paged-KV decode attention (Pallas TPU).

One query token per slot attends a KV stream stored in fixed-size
**pages** of a global pool: slot ``b``'s logical positions
``[i * page_size, (i + 1) * page_size)`` live in pool page
``block_tables[b, i]``.  The grid is ``(slots, max_blocks)`` with the
page dimension innermost — TPU grid steps execute sequentially, so the
online-softmax running state (max ``m``, normalizer ``l``, accumulator
``acc``) lives in VMEM scratch across page steps, exactly like the
flash-attention forward next door.

The page gather is done by the *index maps*: ``block_tables`` (and the
per-slot valid length ``kv_len``) are scalar-prefetch operands
(``pltpu.PrefetchScalarGridSpec``), available before the kernel body
runs, so the k/v BlockSpecs can DMA page ``block_tables[b, ik]`` directly
— no repacked contiguous KV is ever materialized.

Each grid step takes a page's whole ``[page_size, n_kv, hd]`` slab (the
last two block dims are the array's own, which is what Mosaic's tiling
rule asks for) and every query head of the slot.  The slab is flattened
to ``[page_size * n_kv, hd]`` rows (row ``p * n_kv + h`` is position
``p`` of kv head ``h``) and scored against all ``n_q`` query heads in one
matmul; a head mask keeps query head ``i`` on kv head ``i // group``
(GQA), so pairs across heads score ``-1e30`` and weigh exactly 0.

See DESIGN.md in this directory for the grid/layout rationale.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_attention_fwd"]

_NEG_INF = -1e30


def _kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
            l_ref, *, scale: float, page_size: int, n_kv: int,
            window: int | None, skip_pages: bool):
    b = pl.program_id(0)
    ik = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _page_step():
        q = q_ref[0].astype(jnp.float32)              # [n_q, hd]
        rows = page_size * n_kv
        k = k_ref[0].astype(jnp.float32).reshape(rows, -1)  # [ps*n_kv, hd]
        v = v_ref[0].astype(jnp.float32).reshape(rows, -1)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale

        n_q = q.shape[0]
        group = n_q // n_kv
        q_head = jax.lax.broadcasted_iota(jnp.int32, (n_q, rows), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (n_q, rows), 1)
        kv_len = len_ref[b]                           # valid positions
        k_pos = ik * page_size + col // n_kv
        mask = (q_head // group == col % n_kv) & (k_pos < kv_len)
        if window is not None:
            mask &= k_pos > kv_len - 1 - window       # q pos = kv_len-1
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[...]                           # [n_q, 1]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_cur

    if skip_pages:
        # page skip: slot b's stream ends at page ceil(kv_len/ps) - 1;
        # later grid steps are pure no-ops for this slot (a fully-masked
        # page contributes alpha=1, p=0, so skipping is bitwise-neutral)
        # and their k/v index maps re-request the previous page, so the
        # DMA is elided too — the innermost loop effectively stops at
        # ceil(kv_len / page_size) instead of scanning all max_blocks.
        pl.when(ik * page_size < len_ref[b])(_page_step)
    else:
        _page_step()

    @pl.when(ik == nk - 1)
    def _finish():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = out.astype(o_ref.dtype)


def paged_attention_fwd(q: jax.Array, k_pages: jax.Array,
                        v_pages: jax.Array, block_tables: jax.Array,
                        kv_len: jax.Array, *, scale: float | None = None,
                        window: int | None = None,
                        skip_pages: bool = True,
                        interpret: bool = False) -> jax.Array:
    """Single-token decode attention through a per-slot block table.

    q ``[slots, n_q, hd]``; k/v pages ``[n_pages, page_size, n_kv, hd]``;
    ``block_tables [slots, max_blocks]`` int32 page ids; ``kv_len
    [slots]`` int32 — positions ``< kv_len[b]`` are attended (the query
    sits at position ``kv_len[b] - 1``).  Returns ``[slots, n_q, hd]``.

    ``skip_pages`` (default on) stops slot ``b``'s innermost page loop
    at ``ceil(kv_len[b] / page_size)`` pages instead of scanning all
    ``max_blocks``: past-the-stream grid steps skip the compute body
    (bitwise-neutral — their pages would be fully masked anyway) and
    clamp the k/v index maps to the slot's last valid page, so Mosaic's
    revisiting check elides the DMA.  Ragged short-``kv_len`` slots in
    a deep pool stop paying the long tail's page traffic.
    """
    slots, n_q, hd = q.shape
    n_pages, page_size, n_kv, _ = k_pages.shape
    max_blocks = block_tables.shape[1]
    assert n_q % n_kv == 0, (n_q, n_kv)
    scale = (hd ** -0.5) if scale is None else scale

    if skip_pages:
        def kv_page(b, ik, bt, kl):
            # clamp to the slot's last valid page: grid steps past the
            # stream re-request the previous block, eliding the copy
            last = jnp.maximum((kl[b] - 1) // page_size, 0)
            return (bt[b, jnp.minimum(ik, last)], 0, 0, 0)
    else:
        def kv_page(b, ik, bt, kl):
            return (bt[b, ik], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,               # block_tables, kv_len
        grid=(slots, max_blocks),
        in_specs=[
            pl.BlockSpec((1, n_q, hd), lambda b, ik, bt, kl: (b, 0, 0)),
            pl.BlockSpec((1, page_size, n_kv, hd), kv_page),
            pl.BlockSpec((1, page_size, n_kv, hd), kv_page),
        ],
        out_specs=pl.BlockSpec((1, n_q, hd),
                               lambda b, ik, bt, kl: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((n_q, hd), jnp.float32),
            pltpu.VMEM((n_q, 1), jnp.float32),
            pltpu.VMEM((n_q, 1), jnp.float32),
        ],
    )

    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, page_size=page_size,
                          n_kv=n_kv, window=window, skip_pages=skip_pages),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((slots, n_q, hd), q.dtype),
        interpret=interpret,
        name="repro_paged_attention",
    )(block_tables.astype(jnp.int32), kv_len.astype(jnp.int32),
      q, k_pages, v_pages)
