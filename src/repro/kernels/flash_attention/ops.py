"""Differentiable blockwise GQA flash attention: splash attention on TPU.

The kernels are JAX's splash attention
(``jax.experimental.pallas.ops.tpu.splash_attention``): a forward and one
fused backward that yields dq, dk and dv.  Each keeps its score tiles in
VMEM and skips the blocks a causal mask hides entirely; the backward
recomputes scores block by block from the forward's log-sum-exp.  One
MQA kernel runs per (batch row, KV head) over that head's group of query
heads, so repeated KV is never materialized.

On TPU the kernels lower to Mosaic; on any other platform the same
kernels run in Pallas interpret mode (the tests' path).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import splash_attention as splash

__all__ = ["flash_attention"]

_LANES = 128        # a block is a whole number of the MXU's lanes
_MAX_BLOCK = 1024   # the largest block, in tokens (tuned: PERF.md §6)


def _block(seq: int) -> int:
    """Query and key block for a ``seq``-token sequence."""
    return min(_MAX_BLOCK, -(-seq // _LANES) * _LANES)


@functools.lru_cache(maxsize=None)
def _kernel(heads: int, seq: int, causal: bool, interpret: bool):
    """Splash MQA kernel for ``heads`` query heads over one KV head of a
    ``seq``-token sequence (a whole number of blocks).  Its block tables
    are made concrete, so one kernel serves every trace."""
    block = _block(seq)
    one = splash.CausalMask((seq, seq)) if causal \
        else splash.FullMask((seq, seq))
    sizes = splash.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=block,
        block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=block,
        use_fused_bwd_kernel=True)
    with jax.ensure_compile_time_eval():
        return splash.make_splash_mqa_single_device(
            splash.MultiHeadMask([one] * heads), block_sizes=sizes,
            interpret=interpret)


def _splash(q, k, v, segment_ids, *, causal: bool, interpret: bool):
    """q ``[m, g, s, hd]``; k, v ``[m, s, hd]``, one KV head each."""
    _, g, s, _ = q.shape
    kernel = _kernel(g, s, causal, interpret)
    return jax.vmap(kernel, in_axes=(0, 0, 0, None))(q, k, v, segment_ids)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True,
                    scale: float | None = None) -> jax.Array:
    """Self-attention over one sequence of positions ``0 .. s-1``.

    q ``[b, s, n_q, hd]``; k, v ``[b, s, n_kv, hd]`` with ``n_kv``
    dividing ``n_q`` -> ``[b, s, n_q, hd]`` in q's dtype.  Scores are
    scaled by ``scale`` (``hd ** -0.5`` when None).  Differentiable.  A sequence that is not a whole number of blocks is
    padded at its end; a padded key is hidden from every real query (by
    causality, or by a segment of its own when ``causal`` is False).
    """
    b, s, n_q, hd = q.shape
    n_kv = k.shape[2]
    assert n_q % n_kv == 0 and k.shape == v.shape == (b, s, n_kv, hd), \
        (q.shape, k.shape, v.shape)
    g = n_q // n_kv
    block = _block(s)
    sp = -(-s // block) * block

    with jax.named_scope("repro_flash_attention"):
        q = (q * (hd ** -0.5 if scale is None else scale)).astype(q.dtype)
        q = q.reshape(b, s, n_kv, g, hd).transpose(0, 2, 3, 1, 4)
        k, v = (x.transpose(0, 2, 1, 3) for x in (k, v))
        if sp != s:
            q = jnp.pad(q, ((0, 0),) * 3 + ((0, sp - s), (0, 0)))
            k, v = (jnp.pad(x, ((0, 0), (0, 0), (0, sp - s), (0, 0)))
                    for x in (k, v))
        segment_ids = None
        if sp != s and not causal:
            ids = (jnp.arange(sp) >= s).astype(jnp.int32)
            segment_ids = splash.SegmentIds(q=ids, kv=ids)
        args = (q.reshape(b * n_kv, g, sp, hd), k.reshape(b * n_kv, sp, hd),
                v.reshape(b * n_kv, sp, hd), segment_ids)
        out = jax.lax.platform_dependent(
            *args,
            tpu=functools.partial(_splash, causal=causal, interpret=False),
            default=functools.partial(_splash, causal=causal,
                                      interpret=True))
        out = out.reshape(b, n_kv, g, sp, hd)[:, :, :, :s]
        return out.transpose(0, 3, 1, 2, 4).reshape(b, s, n_q, hd)
