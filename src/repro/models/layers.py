"""Neural-net primitives shared by the model zoo (pure functional JAX).

Conventions
-----------
* Parameters are plain dicts of ``jax.Array``; every builder returns
  ``(params, spec)`` where ``spec`` mirrors the params structure with
  *logical axis names* (strings or ``None``) used by
  :mod:`repro.parallel.sharding` to derive mesh shardings.
* Compute dtype is the params dtype (bf16 by default); softmax, norms and
  losses accumulate in float32.
* Attention is GQA throughout (MHA = ``n_kv == n_heads``); RoPE is the
  rotate-half convention.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from ..kernels.flash_attention import flash_attention

__all__ = [
    "Init",
    "dense_init", "dense",
    "norm_init", "rms_norm", "layer_norm",
    "embed_init",
    "rope_freqs", "apply_rope",
    "gqa_attention", "causal_self_attention",
    "mlp_init", "mlp_apply",
    "softmax_xent",
    "count_params",
]

PyTree = Any


class Init:
    """Keyed initializer stream (splits deterministically on demand)."""

    def __init__(self, key: jax.Array):
        self._key = key

    def next(self) -> jax.Array:
        self._key, sub = jax.random.split(self._key)
        return sub

    def normal(self, shape, scale: float, dtype) -> jax.Array:
        return (jax.random.normal(self.next(), shape, jnp.float32)
                * scale).astype(dtype)


# ---------------------------------------------------------------------------
# Linear / norm / embedding
# ---------------------------------------------------------------------------

def dense_init(init: Init, d_in: int, d_out: int, *, bias: bool = False,
               dtype=jnp.bfloat16, scale: float | None = None,
               in_axis: str | None = None, out_axis: str | None = None):
    """Weight ``[d_in, d_out]`` (+ optional bias); returns (params, spec)."""
    scale = (d_in ** -0.5) if scale is None else scale
    p = {"w": init.normal((d_in, d_out), scale, dtype)}
    s = {"w": (in_axis, out_axis)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
        s["b"] = (out_axis,)
    return p, s


def dense(p: PyTree, x: jax.Array) -> jax.Array:
    y = x @ p["w"]
    if "b" in p:
        y = y + p["b"]
    return y


def norm_init(d: int, *, dtype=jnp.bfloat16, bias: bool = False):
    p = {"scale": jnp.ones((d,), dtype)}
    s = {"scale": (None,)}
    if bias:
        p["bias"] = jnp.zeros((d,), dtype)
        s["bias"] = (None,)
    return p, s


def rms_norm(p: PyTree, x: jax.Array, *, eps: float = 1e-6) -> jax.Array:
    """Moments in float32, normalized tensor in the storage dtype.

    Deliberately avoids materializing a full f32 copy of ``x``: with
    Megatron-TP the residual stream crosses per-layer all-reduces, and
    XLA's convert-sinking otherwise promotes those collectives to f32 —
    2x the wire bytes (measured in the §Perf granite hillclimb)."""
    ms = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                  keepdims=True)
    inv = jax.lax.rsqrt(ms + eps).astype(x.dtype)
    return x * inv * p["scale"]


def layer_norm(p: PyTree, x: jax.Array, *, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (x - mu.astype(x.dtype)) \
        * jax.lax.rsqrt(var + eps).astype(x.dtype) * p["scale"]
    if "bias" in p:
        y = y + p["bias"]
    return y


def embed_init(init: Init, vocab: int, d: int, *, dtype=jnp.bfloat16):
    p = {"table": init.normal((vocab, d), 1.0, dtype)}
    s = {"table": ("vocab", None)}
    return p, s


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    """Inverse frequencies ``[head_dim // 2]`` (float32)."""
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, jnp.float32)
                            / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array,
               inv_freq: jax.Array) -> jax.Array:
    """Rotate-half RoPE.  ``x: [b, s, n, hd]``, ``positions: [b, s]``."""
    ang = positions[..., None].astype(jnp.float32) * inv_freq  # [b, s, hd/2]
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA, causal / local-window / cross, cached decode)
# ---------------------------------------------------------------------------

def gqa_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  q_positions: jax.Array | None = None,
                  kv_positions: jax.Array | None = None,
                  causal: bool = True,
                  window: int | None = None,
                  kv_valid_len: jax.Array | None = None,
                  scale: float | None = None,
                  q_chunk: int = 1024) -> jax.Array:
    """Grouped-query attention.

    q ``[b, sq, n_q, hd]``; k, v ``[b, sk, n_kv, hd]`` with
    ``n_q % n_kv == 0``.  KV heads are repeated to ``n_q`` so the head axis
    shards cleanly over the ``model`` mesh axis.  Masks are position-based,
    so the same code serves full-sequence training, windowed attention and
    one-token cached decode (``kv_valid_len`` masks unwritten cache slots).

    Long queries are processed in ``q_chunk`` blocks under ``jax.remat`` —
    the score tensor peaks at ``[b, n_q, q_chunk, sk]`` instead of
    ``[b, n_q, sq, sk]`` (memory-efficient attention; required for the
    32k-prefill cells).
    """
    b, sq, n_q, hd = q.shape
    _, sk, n_kv, _ = k.shape
    assert n_q % n_kv == 0, (n_q, n_kv)
    if n_kv != n_q:
        k = jnp.repeat(k, n_q // n_kv, axis=2)
        v = jnp.repeat(v, n_q // n_kv, axis=2)
    scale = (hd ** -0.5) if scale is None else scale

    # Pin the head dim to the tensor-parallel axis: GSPMD's solver
    # otherwise shards the 64-192-wide contraction dim and partial-sums
    # the full score map over `model` (3.3 TB/step in the whisper 32k
    # prefill cell — §Perf).  No-op without an ambient mesh.
    from ..parallel.sharding import maybe_constrain
    q = maybe_constrain(q, None, None, "model", None)
    k = maybe_constrain(k, None, None, "model", None)
    v = maybe_constrain(v, None, None, "model", None)

    if q_positions is None:
        q_positions = jnp.broadcast_to(jnp.arange(sq), (b, sq))
    if kv_positions is None:
        kv_positions = jnp.broadcast_to(jnp.arange(sk), (b, sk))

    def attend(qc: jax.Array, qp: jax.Array) -> jax.Array:
        # qc [b, c, n, hd]; scores [b, n, c, sk]
        scores = jnp.einsum("bqnh,bsnh->bnqs", qc, k,
                            preferred_element_type=jnp.float32) * scale
        qpm = qp[:, None, :, None]
        kpm = kv_positions[:, None, None, :]
        mask = jnp.ones((b, 1, qc.shape[1], sk), bool)
        if causal:
            mask &= kpm <= qpm
        if window is not None:
            mask &= kpm > qpm - window
        if kv_valid_len is not None:
            mask &= kpm < kv_valid_len[:, None, None, None]
        scores = jnp.where(mask, scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(qc.dtype)
        return jnp.einsum("bnqs,bsnh->bqnh", probs, v)

    if q_chunk is None or sq <= q_chunk:
        return attend(q, q_positions)

    pad = (-sq) % q_chunk
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q_positions = jnp.pad(q_positions, ((0, 0), (0, pad)))
    nc = q.shape[1] // q_chunk
    qs = q.reshape(b, nc, q_chunk, n_q, hd)
    ps = q_positions.reshape(b, nc, q_chunk)

    def body(_, xs):
        qc, pc = xs
        return None, jax.checkpoint(attend)(qc, pc)

    _, out = jax.lax.scan(body, None,
                          (jnp.moveaxis(qs, 1, 0), jnp.moveaxis(ps, 1, 0)))
    out = jnp.moveaxis(out, 0, 1).reshape(b, -1, n_q, hd)
    return out[:, :sq]


def causal_self_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                          positions: jax.Array, *,
                          scale: float | None = None) -> jax.Array:
    """Causal GQA self-attention of a sequence over its own positions:
    ``positions [b, s]`` must be ``0 .. s-1`` in every row (shapes and
    ``scale`` as :func:`gqa_attention`).

    Where the program lowers for TPU this is the flash kernel, forward
    and backward, which needs no positions; elsewhere it is
    :func:`gqa_attention` over ``positions``.  The branch is chosen at
    lowering, so a compile for a described TPU takes the kernel too."""
    def flash(q, k, v, _):
        return flash_attention(q, k, v, scale=scale)

    def einsum(q, k, v, positions):
        return gqa_attention(q, k, v, q_positions=positions,
                             kv_positions=positions, scale=scale)

    return jax.lax.platform_dependent(q, k, v, positions, tpu=flash,
                                      default=einsum)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def mlp_init(init: Init, d_model: int, d_ff: int, *, kind: str = "swiglu",
             dtype=jnp.bfloat16):
    """SwiGLU (gate+up+down) or GELU (up+down) feed-forward."""
    p, s = {}, {}
    if kind == "swiglu":
        p["gate"], s["gate"] = dense_init(init, d_model, d_ff, dtype=dtype,
                                          out_axis="ff")
        p["up"], s["up"] = dense_init(init, d_model, d_ff, dtype=dtype,
                                      out_axis="ff")
    elif kind == "gelu":
        p["up"], s["up"] = dense_init(init, d_model, d_ff, dtype=dtype,
                                      out_axis="ff")
    else:
        raise ValueError(kind)
    p["down"], s["down"] = dense_init(
        init, d_ff, d_model, dtype=dtype,
        scale=d_ff ** -0.5, in_axis="ff")
    return p, s


def mlp_apply(p: PyTree, x: jax.Array, *, kind: str = "swiglu") -> jax.Array:
    if kind == "swiglu":
        h = jax.nn.silu(dense(p["gate"], x)) * dense(p["up"], x)
    else:
        h = jax.nn.gelu(dense(p["up"], x))
    return dense(p["down"], h)


# ---------------------------------------------------------------------------
# Loss / misc
# ---------------------------------------------------------------------------

def softmax_xent(logits: jax.Array, labels: jax.Array,
                 ignore_index: int = -100) -> jax.Array:
    """Mean token cross-entropy in float32; ``labels == ignore_index`` masked."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    nll = logz - gold
    ok = labels != ignore_index
    return jnp.sum(nll * ok) / jnp.maximum(jnp.sum(ok), 1)


def count_params(tree: PyTree) -> int:
    return sum(x.size for x in jax.tree_util.tree_leaves(tree))
