"""Fused AdamW step (Pallas TPU) — the HBM-bound optimizer hot spot.

The unfused update streams p, g, m, v through HBM several times (one pass
per elementwise op XLA fails to fuse across the dtype boundaries: bf16
params, f32 moments).  This kernel makes ONE pass: each grid step loads a
``[rows, 128*k]`` VMEM tile of all four tensors, computes the update in
registers and writes p', m', v' — 7 HBM transfers per element total, the
streaming lower bound.

Hyper-parameters arrive as a ``[6]`` float32 operand (lr, beta1, beta2,
eps, weight-decay, step) so a changing learning rate never recompiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["fused_adamw"]


def _kernel(h_ref, p_ref, g_ref, m_ref, v_ref, p_out, m_out, v_out):
    lr, b1, b2, eps, wd, t = (h_ref[i] for i in range(6))
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    m = m_ref[...]
    v = v_ref[...]
    m2 = b1 * m + (1.0 - b1) * g
    v2 = b2 * v + (1.0 - b2) * g * g
    bc1 = 1.0 - jnp.power(b1, t)
    bc2 = 1.0 - jnp.power(b2, t)
    upd = (m2 / bc1) / (jnp.sqrt(v2 / bc2) + eps)
    p2 = p * (1.0 - lr * wd) - lr * upd
    p_out[...] = p2.astype(p_out.dtype)
    m_out[...] = m2
    v_out[...] = v2


def fused_adamw(p: jax.Array, g: jax.Array, m: jax.Array, v: jax.Array, *,
                lr: float | jax.Array, beta1: float = 0.9,
                beta2: float = 0.999, eps: float = 1e-8,
                weight_decay: float = 0.0, step: jax.Array | int = 0,
                block: int = 1024, interpret: bool = False):
    """One fused AdamW step on a flat (any-shape) tensor quartet."""
    shape, dtype = p.shape, p.dtype
    n = p.size
    pad = (-n) % block
    flat = lambda x, dt: jnp.pad(x.reshape(-1).astype(dt), (0, pad))
    pf = flat(p, dtype)
    gf = flat(g, jnp.float32)
    mf = flat(m, jnp.float32)
    vf = flat(v, jnp.float32)
    hyper = jnp.asarray([lr, beta1, beta2, eps, weight_decay,
                         jnp.asarray(step, jnp.float32) + 1.0], jnp.float32)

    grid = (pf.size // block,)
    p2, m2, v2 = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((6,), lambda i: (0,)),         # hyper (broadcast)
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
        ],
        out_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(pf.shape, dtype),
            jax.ShapeDtypeStruct(mf.shape, jnp.float32),
            jax.ShapeDtypeStruct(vf.shape, jnp.float32),
        ],
        interpret=interpret,
        name="repro_fused_adam",
    )(hyper, pf, gf, mf, vf)
    unflat = lambda x, dt: x[:n].reshape(shape).astype(dt)
    return unflat(p2, dtype), unflat(m2, jnp.float32), \
        unflat(v2, jnp.float32)
