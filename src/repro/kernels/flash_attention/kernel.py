"""Blockwise causal GQA flash-attention forward (Pallas TPU).

TPU adaptation of the classic algorithm: the grid is
``(batch*q_heads, q_blocks, k_blocks)`` with the k dimension innermost —
TPU grid steps execute *sequentially*, so the online-softmax running state
(max ``m``, normalizer ``l``, accumulator ``acc``) lives in VMEM scratch
across k steps instead of CUDA-style thread-block shared memory (the
hardware-adaptation note in DESIGN.md §2).

Blocks are VMEM tiles: q ``[block_q, head_dim]``, k/v
``[block_k, head_dim]`` — block sizes default to 128/256, multiples of the
MXU's 128 lanes.  GQA is handled in the kv index map (query head ``h``
reads kv head ``h // group``), so no repeated-KV materialization.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_fwd"]

_NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, block_q: int, block_k: int, seq_k: int,
            causal: bool):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)                  # [bq, hd]
    k = k_ref[0].astype(jnp.float32)                  # [bk, hd]
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 0)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 1)
    mask = k_pos < seq_k
    if causal:
        mask &= k_pos <= q_pos
    s = jnp.where(mask, s, _NEG_INF)

    m_prev = m_ref[...]                               # [bq]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, None])
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_cur

    @pl.when(ik == nk - 1)
    def _finish():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0] = out.astype(o_ref.dtype)


def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, scale: float | None = None,
                        block_q: int = 128, block_k: int = 256,
                        interpret: bool = False) -> jax.Array:
    """q ``[b, sq, n_q, hd]``, k/v ``[b, sk, n_kv, hd]`` -> ``[b, sq, n_q,
    hd]``.  Forward only (serving / prefill hot path)."""
    b, sq, n_q, hd = q.shape
    _, sk, n_kv, _ = k.shape
    assert n_q % n_kv == 0
    g = n_q // n_kv
    scale = (hd ** -0.5) if scale is None else scale

    qf = jnp.moveaxis(q, 2, 1).reshape(b * n_q, sq, hd)
    kf = jnp.moveaxis(k, 2, 1).reshape(b * n_kv, sk, hd)
    vf = jnp.moveaxis(v, 2, 1).reshape(b * n_kv, sk, hd)

    pad_q = (-sq) % block_q
    pad_k = (-sk) % block_k
    if pad_q:
        qf = jnp.pad(qf, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kf = jnp.pad(kf, ((0, 0), (0, pad_k), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad_k), (0, 0)))
    nq_blk = qf.shape[1] // block_q
    nk_blk = kf.shape[1] // block_k

    def kv_index(bh, iq, ik):
        return ((bh // n_q) * n_kv + (bh % n_q) // g, ik, 0)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, seq_k=sk, causal=causal),
        grid=(b * n_q, nq_blk, nk_blk),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, block_k, hd), kv_index),
            pl.BlockSpec((1, block_k, hd), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd),
                               lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * n_q, qf.shape[1], hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        interpret=interpret,
        name="repro_flash_attention",
    )(qf, kf, vf)

    out = out[:, :sq].reshape(b, n_q, sq, hd)
    return jnp.moveaxis(out, 1, 2)
