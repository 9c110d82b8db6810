"""Plain decoder, its loss, Adam steps and DreamDDP partial averaging.

Computed in float32 under ``default_matmul_precision("highest")`` (the
TPU otherwise multiplies float32 in bf16 passes).  ``prec="fp8"`` is the
control: every matmul's operands are rounded to float8 e4m3 with a
per-tensor scale, forward and backward, the step below the bf16 that the
configurations state.  Parameters are stored in the configuration's
dtype (``torch_dtype``) between steps, as the deployment keeps them;
the optimizer's moments are float32.

The block follows the configuration file's numbers: RMSNorm with
``rms_norm_eps``, rotate-half RoPE on the first
``partial_rotary_factor * head_dim`` dims at ``rope_theta``, grouped-query
causal attention scaled by ``attention_multiplier``, SwiGLU, residual
branches times ``residual_multiplier``, embeddings times
``embedding_multiplier``, tied logits divided by ``logits_scaling``.  A
key the file does not give takes the plain Llama-style value
(:data:`PLAIN`; attention by ``head_dim ** -0.5``).  Attention runs in
blocks of query rows so a long sequence fits.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

__all__ = ["forward_hidden", "loss", "adam_steps", "worker_sharding",
           "leaf_norms", "average", "round_fp8"]

F32 = jnp.float32
E4M3_MAX = 448.0
Q_BLOCK = 512
PLAIN = {"embedding_multiplier": 1.0, "residual_multiplier": 1.0,
         "logits_scaling": 1.0, "partial_rotary_factor": 1.0}


def knob(cfg: dict, key: str) -> float:
    """The file's value of a scale or share, else the plain block's."""
    if key in cfg:
        return cfg[key]
    if key == "attention_multiplier":
        return cfg["head_dim"] ** -0.5
    return PLAIN[key]


def round_fp8(x: jax.Array) -> jax.Array:
    """Round to float8 e4m3 with a per-tensor scale (back in float32)."""
    x = x.astype(F32)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


@jax.custom_vjp
def _mm8(a, b):
    return jnp.matmul(round_fp8(a), round_fp8(b))


def _mm8_fwd(a, b):
    return _mm8(a, b), (a, b)


def _mm8_bwd(res, g):
    a, b = res
    ga = jnp.matmul(round_fp8(g), round_fp8(jnp.swapaxes(b, -1, -2)))
    gb = jnp.einsum("...ij,...ik->jk", round_fp8(a), round_fp8(g))
    return ga, gb.reshape(b.shape)


_mm8.defvjp(_mm8_fwd, _mm8_bwd)


def mm(a, b, prec: str):
    """``a @ b`` (``b`` 2-D) in float32, or with fp8-rounded operands."""
    if prec == "fp8":
        return _mm8(a.astype(F32), b.astype(F32))
    return jnp.matmul(a.astype(F32), b.astype(F32))


def _rms(x, scale, eps):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * scale.astype(F32)


def _rope(x, pos, cfg):
    hd = x.shape[-1]
    rot = int(round(hd * knob(cfg, "partial_rotary_factor")))
    inv = 1.0 / (cfg["rope_theta"] ** (jnp.arange(0, rot, 2, dtype=F32)
                                       / rot))
    ang = pos[:, None].astype(F32) * inv            # [s, rot/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = jnp.split(xr, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos, xp],
                           axis=-1)


def _attention(q, k, v, scale):
    """Causal GQA over one sequence: q [s, h, hd], k/v [s, kv, hd]."""
    s, h, hd = q.shape
    kv = k.shape[1]
    qg = q.reshape(s, kv, h // kv, hd)
    pos = jnp.arange(s)

    def block(qb, pb):
        sc = jnp.einsum("qkgd,skd->kgqs", qb, k) * scale
        sc = jnp.where(pos[None, None, None, :] <= pb[None, None, :, None],
                       sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", p, v)

    nb = -(-s // Q_BLOCK)
    pad = nb * Q_BLOCK - s
    qp = jnp.pad(qg, ((0, pad), (0, 0), (0, 0), (0, 0)))
    pp = jnp.pad(pos, (0, pad))
    out = jax.lax.map(lambda a: jax.checkpoint(block)(*a),
                      (qp.reshape(nb, Q_BLOCK, kv, h // kv, hd),
                       pp.reshape(nb, Q_BLOCK)))
    return out.reshape(nb * Q_BLOCK, h, hd)[:s]


def _layer(cfg, prec, x, lp):
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps, rm = cfg["rms_norm_eps"], knob(cfg, "residual_multiplier")
    s = x.shape[0]
    pos = jnp.arange(s)
    a = _rms(x, lp["ln1"]["scale"], eps)
    q = mm(a, lp["attn"]["wq"]["w"], prec).reshape(s, h, hd)
    k = mm(a, lp["attn"]["wk"]["w"], prec).reshape(s, kv, hd)
    v = mm(a, lp["attn"]["wv"]["w"], prec).reshape(s, kv, hd)
    q, k = _rope(q, pos, cfg), _rope(k, pos, cfg)
    o = _attention(q, k, v, knob(cfg, "attention_multiplier"))
    o = o.reshape(s, h * hd)
    x = x + rm * mm(o, lp["attn"]["wo"]["w"], prec)
    b = _rms(x, lp["ln2"]["scale"], eps)
    m = jax.nn.silu(mm(b, lp["mlp"]["gate"]["w"], prec)) \
        * mm(b, lp["mlp"]["up"]["w"], prec)
    return x + rm * mm(m, lp["mlp"]["down"]["w"], prec)


def forward_hidden(cfg: dict, params, tokens, prec: str = "f32"):
    """Final normed hidden states ``[s, d]`` of one sequence."""
    x = params["embed"]["table"][tokens].astype(F32) \
        * knob(cfg, "embedding_multiplier")

    def body(x, lp):
        return jax.checkpoint(functools.partial(_layer, cfg, prec))(x, lp), \
            None

    x, _ = jax.lax.scan(body, x, params["blocks"])
    return _rms(x, params["head"]["norm"]["scale"], cfg["rms_norm_eps"])


def _logits(cfg, params, hidden, prec):
    return mm(hidden, params["embed"]["table"].T, prec) \
        / knob(cfg, "logits_scaling")


def loss(cfg: dict, params, tokens, prec: str = "f32",
         keep: float = 1.0):
    """Mean next-token cross-entropy of a batch ``[B, S]``.  ``keep < 1``
    is a planted fault: the mean over the first ``keep`` share of each
    row only."""
    def one(row):
        hidden = forward_hidden(cfg, params, row, prec)
        lg = _logits(cfg, params, hidden[:-1], prec)
        nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
            lg, row[1:, None], -1)[:, 0]
        n = int(round(nll.shape[0] * keep))
        return jnp.mean(nll[:n])

    return jnp.mean(jax.vmap(one)(tokens))


def _lr(opt: dict, step: int) -> float:
    """Linear warm-up then cosine decay to ``min_lr_ratio``."""
    import math
    warm = min(1.0, (step + 1.0) / max(opt["warmup_steps"], 1))
    prog = min(max((step - opt["warmup_steps"])
                   / max(opt["decay_steps"] - opt["warmup_steps"], 1), 0.0),
               1.0)
    frac = opt["min_lr_ratio"] + (1 - opt["min_lr_ratio"]) * 0.5 \
        * (1 + math.cos(math.pi * prog))
    return opt["lr"] * warm * frac


def leaf_norms(tree) -> dict:
    """``{path: float32 norm}`` of every leaf (device scalars)."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): jnp.sqrt(jnp.sum(jnp.square(
        x.astype(F32)))) for p, x in flat}


def worker_sharding(chips: int) -> NamedSharding:
    """The worker axis (axis 0) spread over the first ``chips`` devices,
    one worker's replica (or an equal share of them) on each."""
    mesh = Mesh(np.array(jax.devices()[:chips]), ("w",))
    return NamedSharding(mesh, P("w"))


def make_step(cfg: dict, opt: dict, shard: NamedSharding, *,
              prec: str = "f32", keep: float = 1.0):
    """One jitted Adam step over the worker stack ``[W, ...]`` that
    donates its parameters and moments:
    ``(p, m, v, batch [W, B, S], lr, t) -> (p, m, v, loss, each
    worker's gradient norm before the clip)``.  Each device computes the
    gradients of its own workers (``shard_map``); nothing crosses chips
    until :func:`average`.
    """
    b1, b2, eps = opt["beta1"], opt["beta2"], opt["eps"]

    def worker(pw, bw):
        lval, g = jax.value_and_grad(
            lambda q: loss(cfg, q, bw, prec, keep))(pw)
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(F32)))
                          for x in jax.tree_util.tree_leaves(g)))
        sc = jnp.minimum(1.0, opt["grad_clip"] / (gn + 1e-9))
        return lval, gn, jax.tree.map(lambda x: x.astype(F32) * sc, g)

    local = jax.shard_map(
        lambda p, b: jax.lax.map(lambda a: worker(*a), (p, b)),
        mesh=shard.mesh, in_specs=(P("w"), P("w")),
        out_specs=(P("w"), P("w"), P("w")))

    def step(p, m, v, batch, lr, t):
        dtype = jax.tree_util.tree_leaves(p)[0].dtype
        losses, gns, g = local(p, batch)
        bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
        m2 = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
        v2 = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
        p2 = jax.tree.map(
            lambda p_, m_, v_: (p_.astype(F32) - lr * (m_ / bc1)
                                / (jnp.sqrt(v_ / bc2) + eps)).astype(dtype),
            p, m2, v2)
        return p2, m2, v2, jnp.mean(losses), gns

    return jax.jit(step, donate_argnums=(0, 1, 2))


def adam_steps(cfg: dict, opt: dict, params, batches, phases, *,
               chips: int = 1, prec: str = "f32", keep: float = 1.0,
               sync: bool = True):
    """Run ``len(batches)`` DreamDDP steps from ``params`` (one replica,
    shared by every worker at step 0).  ``params`` is consumed.

    ``batches[t]`` is ``[W, B, S]``; ``phases[t]`` lists the top-level
    groups and layer indices whose parameters the W workers average
    after step t's local update: ``[("embed", None), ("blocks", 3), ...]``.
    The worker stack lies over ``chips`` devices.  Each worker clips its
    gradient by its own global norm and runs Adam on its own float32
    moments.  Returns the mean loss over workers of each step, the
    parameters and the moments ``m``, ``v`` after the last step, stacked
    ``[W, ...]``, and each step's per-worker gradient norms before the
    clip.
    """
    w = batches[0].shape[0]
    shard = worker_sharding(chips)
    stack = jax.jit(lambda t: jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (w,) + x.shape), t),
        out_shardings=shard)(params)
    del params
    m = jax.jit(lambda t: jax.tree.map(lambda x: jnp.zeros(x.shape, F32),
                                       t), out_shardings=shard)(stack)
    v = jax.jit(lambda t: jax.tree.map(lambda x: jnp.zeros(x.shape, F32),
                                       t), out_shardings=shard)(stack)
    step = make_step(cfg, opt, shard, prec=prec, keep=keep)

    losses, raw = [], []
    with jax.default_matmul_precision("highest"):
        for t, batch in enumerate(batches):
            stack, m, v, lval, pre = step(
                stack, m, v, jax.device_put(batch, shard),
                jnp.float32(_lr(opt, t)), jnp.float32(t + 1))
            raw.append([float(x) for x in pre])
            if sync and w > 1:
                stack = average(stack, phases[t])
            losses.append(float(lval))
    return losses, stack, m, v, raw


def average(stack, units):
    """Average the listed units over the worker axis (axis 0)."""
    out = dict(stack)
    for group, idx in units:
        def mean(x, idx=idx):
            if idx is None:
                return jnp.broadcast_to(jnp.mean(x.astype(F32), 0)
                                        .astype(x.dtype), x.shape)
            y = jnp.mean(x[:, idx].astype(F32), 0).astype(x.dtype)
            return x.at[:, idx].set(y[None])
        out[group] = jax.tree.map(mean, out[group])
    return out
