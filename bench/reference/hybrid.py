"""Plain hybrid decoder: Mamba-2 and attention layers in a published order,
its loss, Adam steps and seeded weights (granite-4.0-h, ``model_type``
``granitemoehybrid`` without experts).

Computed in float32 under ``default_matmul_precision("highest")``;
``prec="fp8"`` is the control of :mod:`bench.reference.decoder` (every
matmul's operands rounded to float8 e4m3).  Parameters are stored in the
configuration's dtype between steps, except ``A_log``, ``dt_bias`` and
``D``, which are float32, as the Mamba-2 reference keeps them.

Each layer is ``x + r * mixer(rms(x))`` then ``x + r * mlp(rms(x))`` with
``r = residual_multiplier``; the mixer is the layer's entry of
``layer_types[:num_hidden_layers]``:

- ``attention``: grouped-query causal attention with no positional
  encoding, scores times ``attention_multiplier``
  (:func:`bench.reference.decoder._attention`);
- ``mamba``: ``in_proj`` to ``[z, x, B, C, dt]``; a causal depthwise conv
  of width ``mamba_d_conv`` with bias over ``[x, B, C]``, then SiLU;
  ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the state-space
  recurrence step by step (not the chunked form)::

      h_t = exp(dt_t * A) * h_{t-1} + dt_t * (x_t outer B_t)
      y_t = h_t . C_t + D * x_t

  then ``rms(y * silu(z))`` per group, and ``out_proj``.

Embeddings are times ``embedding_multiplier``, the tied logits divided by
``logits_scaling``.  The recurrence is scanned in checkpointed blocks of
:data:`SCAN_BLOCK` steps and the head and its cross-entropy in
checkpointed blocks of :data:`ROW_BLOCK` rows, so a long sequence's
backward fits.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench.reference.decoder import (_attention, _lr, _rms, average, mm,
                                     worker_sharding)

__all__ = ["kinds", "shapes", "make", "forward_hidden", "loss",
           "ssd_recurrence", "adam_steps"]

F32 = jnp.float32
SCAN_BLOCK = 256
ROW_BLOCK = 512
# published Mamba-2 initializer (mamba_ssm ``Mamba2``): A ~ U(1, 16);
# dt log-uniform in [1e-3, 1e-1], floored at 1e-4, stored as the inverse
# softplus in dt_bias
A_RANGE = (1.0, 16.0)
DT_RANGE = (1e-3, 1e-1)
DT_FLOOR = 1e-4


def kinds(cfg: dict) -> list[str]:
    """Each layer's mixer, in network order."""
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def _mixer_dims(cfg: dict) -> dict:
    d_inner = cfg["mamba_expand"] * cfg["hidden_size"]
    h, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    g, n = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    if h * p != d_inner:
        raise ValueError(f"{h} heads of {p} do not fill d_inner {d_inner}")
    return {"d_inner": d_inner, "heads": h, "head_dim": p, "groups": g,
            "state": n, "conv": cfg["mamba_d_conv"],
            "conv_dim": d_inner + 2 * g * n,
            "in_proj": 2 * d_inner + 2 * g * n + h}


def shapes(cfg: dict) -> dict:
    """The tree of ``(shape, kind)``: kind ``w`` is drawn N(0,
    initializer_range), ``1`` ones, ``0`` zeros (all in the stored
    dtype); ``a_log``, ``dt_bias`` and ``d`` are the float32 Mamba-2
    parameters."""
    d, ff, v = cfg["hidden_size"], cfg["intermediate_size"], \
        cfg["vocab_size"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    if not cfg["tie_word_embeddings"]:
        raise NotImplementedError("untied heads are not in this layout")
    ks = kinds(cfg)
    n_m, n_a = ks.count("mamba"), ks.count("attention")

    def mlp(n):
        return {"gate": {"w": ((n, d, ff), "w")},
                "up": {"w": ((n, d, ff), "w")},
                "down": {"w": ((n, ff, d), "w")}}

    out: dict = {"embed": {"table": ((v, d), "w")}}
    if n_m:
        m = _mixer_dims(cfg)
        out["mamba_blocks"] = {
            "ln1": {"scale": ((n_m, d), "1")},
            "mixer": {
                "in_proj": {"w": ((n_m, d, m["in_proj"]), "w")},
                "conv": ((n_m, m["conv"], m["conv_dim"]), "w"),
                "conv_bias": ((n_m, m["conv_dim"]), "0"),
                "a_log": ((n_m, m["heads"]), "a_log"),
                "dt_bias": ((n_m, m["heads"]), "dt_bias"),
                "d_skip": ((n_m, m["heads"]), "d"),
                "out_norm": {"scale": ((n_m, m["d_inner"]), "1")},
                "out_proj": {"w": ((n_m, m["d_inner"], d), "w")}},
            "ln2": {"scale": ((n_m, d), "1")},
            "mlp": mlp(n_m)}
    if n_a:
        out["blocks"] = {
            "ln1": {"scale": ((n_a, d), "1")},
            "attn": {"wq": {"w": ((n_a, d, h * hd), "w")},
                     "wk": {"w": ((n_a, d, kv * hd), "w")},
                     "wv": {"w": ((n_a, d, kv * hd), "w")},
                     "wo": {"w": ((n_a, h * hd, d), "w")}},
            "ln2": {"scale": ((n_a, d), "1")},
            "mlp": mlp(n_a)}
    out["head"] = {"norm": {"scale": ((d,), "1")}}
    return out


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], str)


def _draw(kind: str, shape, key, dtype, std: float) -> jax.Array:
    if kind == "w":
        return (jax.random.normal(key, shape, F32) * std).astype(dtype)
    if kind in ("1", "0"):
        return jnp.full(shape, float(kind), dtype)
    if kind == "d":
        return jnp.ones(shape, F32)
    u = jax.random.uniform(key, shape, F32)
    if kind == "a_log":
        return jnp.log(A_RANGE[0] + u * (A_RANGE[1] - A_RANGE[0]))
    lo, hi = math.log(DT_RANGE[0]), math.log(DT_RANGE[1])
    dt = jnp.maximum(jnp.exp(lo + u * (hi - lo)), DT_FLOOR)
    return dt + jnp.log(-jnp.expm1(-dt))            # softplus^-1(dt)


def make(cfg: dict, key: jax.Array):
    """The weights, drawn in one jitted call (:func:`shapes`)."""
    dtype = jnp.dtype(cfg["torch_dtype"])
    std = float(cfg["initializer_range"])
    leaves, treedef = jax.tree_util.tree_flatten(shapes(cfg),
                                                 is_leaf=_is_spec)

    def build(key):
        return jax.tree_util.tree_unflatten(treedef, [
            _draw(kind, shape, jax.random.fold_in(key, i), dtype, std)
            for i, (shape, kind) in enumerate(leaves)])

    return jax.jit(build)(key)


def ssd_recurrence(x, dt, a, b, c):
    """The state-space recurrence, one step at a time.  x ``[s, H, P]``,
    dt ``[s, H]``, a ``[H]`` (``A``, negative), b / c ``[s, G, N]`` ->
    y ``[s, H, P]`` (without the ``D`` skip).  A tail that is not a
    whole block is run as steps with ``dt = 0``, which leave the state
    unchanged."""
    s, heads, p = x.shape
    n = b.shape[-1]
    rep = heads // b.shape[1]
    bh, ch = jnp.repeat(b, rep, axis=1), jnp.repeat(c, rep, axis=1)
    nb = -(-s // SCAN_BLOCK)
    pad = nb * SCAN_BLOCK - s

    def blocks(t):
        t = jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1))
        return t.reshape((nb, SCAN_BLOCK) + t.shape[1:])

    def step(hstate, inp):
        xt, dtt, bt, ct = inp
        hstate = jnp.exp(dtt * a)[:, None, None] * hstate \
            + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :]
        return hstate, jnp.einsum("hpn,hn->hp", hstate, ct)

    def block(hstate, inp):
        return jax.lax.scan(step, hstate, inp)

    _, y = jax.lax.scan(jax.checkpoint(block),
                        jnp.zeros((heads, p, n), F32),
                        tuple(blocks(t) for t in (x, dt, bh, ch)))
    return y.reshape(nb * SCAN_BLOCK, heads, p)[:s]


def _mixer(cfg, prec, a, mp):
    """One Mamba-2 mixer over the normed ``a [s, d]``."""
    m = _mixer_dims(cfg)
    s = a.shape[0]
    heads, p, g, n = m["heads"], m["head_dim"], m["groups"], m["state"]
    z, xbc, dt = jnp.split(mm(a, mp["in_proj"]["w"], prec),
                           [m["d_inner"], m["d_inner"] + m["conv_dim"]], -1)
    w = mp["conv"].astype(F32)
    padded = jnp.pad(xbc, ((m["conv"] - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(padded[i:i + s] * w[i] for i in range(m["conv"]))
                      + mp["conv_bias"].astype(F32))
    xs, b, c = jnp.split(xbc, [m["d_inner"], m["d_inner"] + g * n], -1)
    xs = xs.reshape(s, heads, p)
    dt = jax.nn.softplus(dt + mp["dt_bias"])
    y = ssd_recurrence(xs, dt, -jnp.exp(mp["a_log"]), b.reshape(s, g, n),
                       c.reshape(s, g, n))
    y = y + mp["d_skip"][:, None] * xs
    gated = (y.reshape(s, m["d_inner"]) * jax.nn.silu(z)).reshape(s, g, -1)
    gated = _rms(gated, mp["out_norm"]["scale"].reshape(g, -1),
                 cfg["rms_norm_eps"]).reshape(s, m["d_inner"])
    return mm(gated, mp["out_proj"]["w"], prec)


def _attend(cfg, prec, a, ap):
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    s = a.shape[0]
    q = mm(a, ap["wq"]["w"], prec).reshape(s, h, hd)
    k = mm(a, ap["wk"]["w"], prec).reshape(s, kv, hd)
    v = mm(a, ap["wv"]["w"], prec).reshape(s, kv, hd)
    o = _attention(q, k, v, cfg["attention_multiplier"])
    return mm(o.reshape(s, h * hd), ap["wo"]["w"], prec)


def _layer(cfg, prec, kind, x, lp):
    eps, r = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    a = _rms(x, lp["ln1"]["scale"], eps)
    if kind == "mamba":
        x = x + r * _mixer(cfg, prec, a, lp["mixer"])
    else:
        x = x + r * _attend(cfg, prec, a, lp["attn"])
    b = _rms(x, lp["ln2"]["scale"], eps)
    mlp = lp["mlp"]
    hid = jax.nn.silu(mm(b, mlp["gate"]["w"], prec)) \
        * mm(b, mlp["up"]["w"], prec)
    return x + r * mm(hid, mlp["down"]["w"], prec)


def forward_hidden(cfg: dict, params, tokens, prec: str = "f32"):
    """Final normed hidden states ``[s, d]`` of one sequence."""
    if cfg["position_embedding_type"] != "nope":
        raise NotImplementedError("attention here has no positional "
                                  "encoding")
    x = params["embed"]["table"][tokens].astype(F32) \
        * cfg["embedding_multiplier"]
    seen = {"mamba": 0, "attention": 0}
    for kind in kinds(cfg):
        group = "mamba_blocks" if kind == "mamba" else "blocks"
        lp = jax.tree.map(lambda t, i=seen[kind]: t[i], params[group])
        seen[kind] += 1
        x = jax.checkpoint(functools.partial(_layer, cfg, prec, kind))(x, lp)
    return _rms(x, params["head"]["norm"]["scale"], cfg["rms_norm_eps"])


def loss(cfg: dict, params, tokens, prec: str = "f32",
         keep: float = 1.0):
    """Mean next-token cross-entropy of a batch ``[B, S]``.  ``keep < 1``
    is a planted fault: the mean over the first ``keep`` share of each
    row only."""
    table = params["embed"]["table"]

    def nll_sum(hidden, labels, live):
        lg = mm(hidden, table.T, prec) / cfg["logits_scaling"]
        nll = jax.nn.logsumexp(lg, -1) \
            - jnp.take_along_axis(lg, labels[:, None], -1)[:, 0]
        return jnp.sum(nll * live)

    def one(row):
        hidden = forward_hidden(cfg, params, row, prec)[:-1]
        n = int(round(hidden.shape[0] * keep))
        nb = -(-n // ROW_BLOCK)
        pad = nb * ROW_BLOCK - n
        hb = jnp.pad(hidden[:n], ((0, pad), (0, 0)))
        lb = jnp.pad(row[1:n + 1], (0, pad))
        live = (jnp.arange(nb * ROW_BLOCK) < n).astype(F32)
        parts = jax.lax.map(
            lambda t: jax.checkpoint(nll_sum)(*t),
            (hb.reshape(nb, ROW_BLOCK, -1), lb.reshape(nb, ROW_BLOCK),
             live.reshape(nb, ROW_BLOCK)))
        return jnp.sum(parts) / n

    return jnp.mean(jax.vmap(one)(tokens))


def make_step(cfg: dict, opt: dict, shard, *, prec: str = "f32",
              keep: float = 1.0):
    """One jitted Adam step over the worker stack, as
    :func:`bench.reference.decoder.make_step` makes for the dense decoder,
    each leaf stored back in its own dtype."""
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
        mesh=shard.mesh, in_specs=(shard.spec, shard.spec),
        out_specs=(shard.spec,) * 3, check_vma=False)

    def step(p, m, v, batch, lr, t):
        losses, gns, g = local(p, batch)
        bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
        m2 = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
        v2 = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
        p2 = jax.tree.map(
            lambda p_, m_, v_: (p_.astype(F32) - lr * (m_ / bc1)
                                / (jnp.sqrt(v_ / bc2) + eps)).astype(p_.dtype),
            p, m2, v2)
        return p2, m2, v2, jnp.mean(losses), gns

    return jax.jit(step, donate_argnums=(0, 1, 2))


def adam_steps(cfg: dict, opt: dict, params, batches, phases, *,
               chips: int = 1, prec: str = "f32", keep: float = 1.0,
               sync: bool = True):
    """:func:`bench.reference.decoder.adam_steps` for the hybrid: the
    same DreamDDP steps, worker stack, per-worker clip and partial
    averaging, over this module's loss."""
    w = batches[0].shape[0]
    shard = worker_sharding(chips)
    stack = jax.jit(lambda t: jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (w,) + x.shape), t),
        out_shardings=shard)(params)
    del params
    zeros = jax.jit(lambda t: jax.tree.map(lambda x: jnp.zeros(x.shape, F32),
                                           t), out_shardings=shard)
    m, v = zeros(stack), zeros(stack)
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

