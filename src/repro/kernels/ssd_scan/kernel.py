"""Mamba-2 SSD chunk-local core (Pallas TPU).

The quadratic intra-chunk work — ``(C B^T ∘ L) X`` plus the chunk-state
contraction — is the MXU hot spot of the SSD layer.  One grid step
processes one ``(batch, chunk, head)`` cell entirely in VMEM:

    y_diag[i] = sum_{j<=i} exp(cum_i - cum_j) * (c_i . b_j) * x_j
    state     = X^T (B * exp(cum_last - cum))          [p, n]

The O(n_chunks) inter-chunk recurrence stays in jnp (it is tiny and
sequential); ``repro.models.mamba2.ssd_chunked`` is the oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["ssd_chunk_fwd"]


def _kernel(x_ref, b_ref, c_ref, da_ref, y_ref, s_ref):
    x = x_ref[0, 0, 0].astype(jnp.float32)            # [cs, p]
    b = b_ref[0, 0, 0].astype(jnp.float32)            # [cs, n]
    c = c_ref[0, 0, 0].astype(jnp.float32)            # [cs, n]
    da = da_ref[0, 0, 0].astype(jnp.float32)          # [cs]
    cs = x.shape[0]

    cum = jnp.cumsum(da)                              # [cs]
    seg = cum[:, None] - cum[None, :]
    tril = jax.lax.broadcasted_iota(jnp.int32, (cs, cs), 1) <= \
        jax.lax.broadcasted_iota(jnp.int32, (cs, cs), 0)
    L = jnp.where(tril, jnp.exp(seg), 0.0)            # [cs, cs]

    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    y = jax.lax.dot_general(cb * L, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    y_ref[0, 0, 0] = y.astype(y_ref.dtype)

    decay = jnp.exp(cum[-1] - cum)[:, None]           # [cs, 1]
    s = jax.lax.dot_general(x, b * decay, (((0,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s_ref[0, 0, 0] = s                                 # [p, n]


def ssd_chunk_fwd(x: jax.Array, b: jax.Array, c: jax.Array,
                  da: jax.Array, *, interpret: bool = False
                  ) -> tuple[jax.Array, jax.Array]:
    """x ``[B, NC, H, cs, p]``; b/c ``[B, NC, H, cs, n]``; da ``[B, NC, H,
    cs]`` -> (y_diag ``[B, NC, H, cs, p]``, states ``[B, NC, H, p, n]``)."""
    B, NC, H, cs, p = x.shape
    n = b.shape[-1]
    grid = (B, NC, H)
    idx5 = lambda i, j, k: (i, j, k, 0, 0)
    idx4 = lambda i, j, k: (i, j, k, 0)
    y, s = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, cs, p), idx5),
            pl.BlockSpec((1, 1, 1, cs, n), idx5),
            pl.BlockSpec((1, 1, 1, cs, n), idx5),
            pl.BlockSpec((1, 1, 1, cs), idx4),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, cs, p), idx5),
            pl.BlockSpec((1, 1, 1, p, n), idx5),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, NC, H, cs, p), x.dtype),
            jax.ShapeDtypeStruct((B, NC, H, p, n), jnp.float32),
        ],
        interpret=interpret,
        name="repro_ssd_scan",
    )(x, b, c, da)
    return y, s
