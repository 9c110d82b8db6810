"""Mamba-2 (state-space duality / SSD) language model.

The SSD forward is the chunked algorithm of arXiv:2405.21060: quadratic
attention-like compute inside chunks (MXU-friendly) + a linear recurrence
across chunk states.  Decode is the O(1)-state recurrent update, which is
what makes the ``long_500k`` cell runnable (no KV cache, constant memory in
sequence length).

Layout follows the reference implementation: ``in_proj`` emits
``[z, x, B, C, dt]``; a causal depthwise conv (width 4) runs over
``[x, B, C]``; the SSD core uses per-head scalar decay ``A``; output is
gated-RMSNormed and projected back.  That mixer
(:func:`mamba2_mixer_init` / :func:`mamba2_mixer_apply`) is shared with
the hybrid :class:`~repro.models.transformer.DecoderLM`, where an MLP
follows it in every layer.

A Pallas kernel for the chunk-local core lives in
``repro.kernels.ssd_scan`` (this module is its ``ref`` semantics).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from ..core.partial_sync import UnitEntry, UnitLayout
from .layers import Init, dense, norm_init, rms_norm, softmax_xent

__all__ = ["MixerConfig", "Mamba2Config", "Mamba2LM", "ssd_chunked",
           "ssd_decode_step", "mamba2_mixer_init", "mamba2_mixer_apply",
           "mamba2_mixer_param_count", "mamba2_mixer_fwd_flops"]

PyTree = Any


@dataclass(frozen=True)
class MixerConfig:
    """Widths of one Mamba-2 mixer (``in_proj`` -> causal conv -> SSD ->
    gated RMSNorm -> ``out_proj``)."""

    d_model: int
    d_state: int = 128
    head_dim: int = 64
    expand: int = 2
    n_groups: int = 1
    conv_width: int = 4
    chunk: int = 128
    norm_eps: float = 1e-6              # the gated RMSNorm's epsilon

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def n_heads(self) -> int:
        return self.d_inner // self.head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.d_state

    @property
    def d_in_proj(self) -> int:
        return 2 * self.d_inner + 2 * self.n_groups * self.d_state \
            + self.n_heads


@dataclass(frozen=True)
class Mamba2Config:
    name: str
    n_layers: int
    d_model: int
    vocab: int
    d_state: int = 128
    head_dim: int = 64
    expand: int = 2
    n_groups: int = 1
    conv_width: int = 4
    chunk: int = 128
    param_dtype: str = "float32"
    remat: bool = True
    tie_embeddings: bool = True
    norm_eps: float = 1e-6

    @property
    def mixer(self) -> MixerConfig:
        return MixerConfig(self.d_model, self.d_state, self.head_dim,
                           self.expand, self.n_groups, self.conv_width,
                           self.chunk, self.norm_eps)

    @property
    def dtype(self):
        return jnp.dtype(self.param_dtype)


# ---------------------------------------------------------------------------
# SSD core (chunked, pure jnp — the kernel oracle)
# ---------------------------------------------------------------------------

def _segsum(x: jax.Array) -> jax.Array:
    """Lower-triangular segment sums: out[..., i, j] = sum_{j<k<=i} x[..., k]."""
    t = x.shape[-1]
    c = jnp.cumsum(x, -1)
    diff = c[..., :, None] - c[..., None, :]
    mask = jnp.tril(jnp.ones((t, t), bool))
    return jnp.where(mask, diff, -jnp.inf)


def ssd_chunked(x: jax.Array, dt: jax.Array, a_log: jax.Array,
                b: jax.Array, c: jax.Array, chunk: int,
                init_state: jax.Array | None = None
                ) -> tuple[jax.Array, jax.Array]:
    """Chunked SSD.

    x ``[B, L, H, P]``, dt ``[B, L, H]`` (post-softplus), a_log ``[H]``,
    b / c ``[B, L, G, N]`` with ``H % G == 0``.  Sequences are padded to a
    chunk multiple with ``dt = 0`` steps (identity state updates).
    Returns (y ``[B, L, H, P]``, final_state ``[B, H, P, N]``).
    """
    l_orig = x.shape[1]
    pad = (-l_orig) % chunk
    if pad:
        padt = lambda a: jnp.pad(a, [(0, 0), (0, pad)] +
                                 [(0, 0)] * (a.ndim - 2))
        x, dt, b, c = padt(x), padt(dt), padt(b), padt(c)
    bs, l, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    nc = l // chunk
    rep = h // g

    # fold dt into the input; decay per step
    xdt = (x * dt[..., None]).reshape(bs, nc, chunk, h, p)
    da = (dt * (-jnp.exp(a_log.astype(jnp.float32)))).reshape(bs, nc, chunk, h)
    bq = jnp.repeat(b.reshape(bs, nc, chunk, g, n), rep, axis=3)
    cq = jnp.repeat(c.reshape(bs, nc, chunk, g, n), rep, axis=3)

    seg = _segsum(jnp.moveaxis(da, -1, -2))          # [B,nc,H,cs,cs]
    L = jnp.exp(seg)
    # intra-chunk (quadratic, attention-like)
    y_diag = jnp.einsum("bzihn,bzjhn,bzhij,bzjhp->bzihp",
                        cq, bq, L.astype(cq.dtype), xdt)

    # chunk output states
    cum = jnp.cumsum(da, axis=2)                      # [B,nc,cs,H]
    decay_states = jnp.exp(cum[:, :, -1:, :] - cum)   # [B,nc,cs,H]
    states = jnp.einsum("bzjhn,bzjh,bzjhp->bzhpn",
                        bq, decay_states.astype(bq.dtype), xdt)

    # inter-chunk recurrence over chunk states
    chunk_decay = jnp.exp(cum[:, :, -1, :])           # [B,nc,H]

    def scan_fn(carry, inp):
        s, d = inp                                    # [B,H,P,N], [B,H]
        new = carry * d[..., None, None].astype(carry.dtype) + s
        return new, carry                             # emit state *before*

    init = (jnp.zeros_like(states[:, 0]) if init_state is None
            else init_state.astype(states.dtype))
    final, prev_states = jax.lax.scan(
        scan_fn, init,
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    prev_states = jnp.moveaxis(prev_states, 0, 1)     # [B,nc,H,P,N]

    # inter-chunk contribution
    state_decay = jnp.exp(cum)                        # [B,nc,cs,H]
    y_off = jnp.einsum("bzihn,bzhpn,bzih->bzihp",
                       cq, prev_states, state_decay.astype(cq.dtype))

    y = (y_diag + y_off).reshape(bs, l, h, p)
    return y[:, :l_orig], final


def ssd_decode_step(x: jax.Array, dt: jax.Array, a_log: jax.Array,
                    b: jax.Array, c: jax.Array, state: jax.Array
                    ) -> tuple[jax.Array, jax.Array]:
    """O(1) recurrent step.  x ``[B,H,P]``, dt ``[B,H]``, b/c ``[B,G,N]``,
    state ``[B,H,P,N]``."""
    h, g = x.shape[1], b.shape[1]
    rep = h // g
    bq = jnp.repeat(b, rep, axis=1)                   # [B,H,N]
    cq = jnp.repeat(c, rep, axis=1)
    da = jnp.exp(dt * (-jnp.exp(a_log.astype(jnp.float32))))
    xdt = x * dt[..., None]
    new_state = state * da[..., None, None].astype(state.dtype) \
        + jnp.einsum("bhp,bhn->bhpn", xdt, bq)
    y = jnp.einsum("bhpn,bhn->bhp", new_state, cq)
    return y, new_state


# ---------------------------------------------------------------------------
# The mixer (shared by Mamba2LM and the hybrid DecoderLM)
# ---------------------------------------------------------------------------

def mamba2_mixer_init(init: Init, mc: MixerConfig, dtype):
    """One mixer's parameters and their logical-axis spec.  ``a_log``,
    ``dt_bias`` and the skip ``d_skip`` are float32 whatever ``dtype``."""
    p = {
        "in_proj": {"w": init.normal((mc.d_model, mc.d_in_proj),
                                     mc.d_model ** -0.5, dtype)},
        "conv": init.normal((mc.conv_width, mc.conv_dim),
                            mc.conv_width ** -0.5, dtype),
        "conv_bias": jnp.zeros((mc.conv_dim,), dtype),
        "a_log": jnp.log(jnp.linspace(1.0, 16.0, mc.n_heads,
                                      dtype=jnp.float32)),
        "dt_bias": jnp.zeros((mc.n_heads,), jnp.float32),
        "d_skip": jnp.ones((mc.n_heads,), jnp.float32),
        "out_norm": norm_init(mc.d_inner, dtype=dtype)[0],
        "out_proj": {"w": init.normal((mc.d_inner, mc.d_model),
                                      mc.d_inner ** -0.5, dtype)},
    }
    spec = {
        "in_proj": {"w": (None, "heads")},
        "conv": (None, "heads"),
        "conv_bias": ("heads",),
        "a_log": ("heads",),
        "dt_bias": ("heads",),
        "d_skip": ("heads",),
        "out_norm": {"scale": ("heads",)},
        "out_proj": {"w": ("heads", None)},
    }
    return p, spec


def _causal_conv(w: jax.Array, bias: jax.Array, u: jax.Array) -> jax.Array:
    """Causal depthwise conv over time, then SiLU.  u ``[B, L, C]``,
    w ``[W, C]``; sums in float32, result in ``u``'s dtype."""
    width = w.shape[0]
    pad = jnp.pad(u, ((0, 0), (width - 1, 0), (0, 0)))
    out = sum(pad[:, i:i + u.shape[1]].astype(jnp.float32)
              * w[i].astype(jnp.float32) for i in range(width))
    return jax.nn.silu(out + bias.astype(jnp.float32)).astype(u.dtype)


def _gated_norm(scale: jax.Array, y: jax.Array, z: jax.Array, groups: int,
                eps: float, dtype) -> jax.Array:
    """``rmsnorm(y * silu(z))`` over each of ``groups`` equal slices of
    the last axis, in float32; the normed tensor in ``dtype`` times the
    scale."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    if groups > 1:
        g = g.reshape(g.shape[:-1] + (groups, -1))
    ms = jnp.mean(jnp.square(g), axis=-1, keepdims=True)
    g = (g * jax.lax.rsqrt(ms + eps)).reshape(y.shape)
    return g.astype(dtype) * scale


def mamba2_mixer_apply(p, mc: MixerConfig, x: jax.Array, conv_state=None,
                       ssm_state=None):
    """One Mamba-2 mixer over ``x [B, L, d]``.

    Returns ``(y [B, L, d], conv_state, ssm_state)`` in ``x``'s dtype.
    Without states it runs the whole sequence (training, prefill) and
    returns the states the sequence leaves; with them it is the O(1)
    decode step (``L == 1``).  Runs under the named scope
    ``mamba_mixer``, its SSD core under ``ssd``."""
    b, l, _ = x.shape
    with jax.named_scope("mamba_mixer"):
        z, xc, bmat, cmat, dt = jnp.split(
            dense(p["in_proj"], x),
            [mc.d_inner, 2 * mc.d_inner,
             2 * mc.d_inner + mc.n_groups * mc.d_state,
             2 * mc.d_inner + 2 * mc.n_groups * mc.d_state], axis=-1)
        conv_in = jnp.concatenate([xc, bmat, cmat], -1)
        if conv_state is None:
            conv_out = _causal_conv(p["conv"], p["conv_bias"], conv_in)
            new_conv_state = conv_in[:, -(mc.conv_width - 1):]
        else:
            # roll the conv window: state [B, W-1, C]
            hist = jnp.concatenate([conv_state, conv_in], 1)
            new_conv_state = hist[:, 1:]
            conv_out = jax.nn.silu(
                jnp.einsum("bwc,wc->bc", hist, p["conv"])
                + p["conv_bias"])[:, None]

        xq, bq, cq = jnp.split(
            conv_out, [mc.d_inner, mc.d_inner + mc.n_groups * mc.d_state],
            axis=-1)
        xq = xq.reshape(b, l, mc.n_heads, mc.head_dim)
        bq = bq.reshape(b, l, mc.n_groups, mc.d_state)
        cq = cq.reshape(b, l, mc.n_groups, mc.d_state)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])

        with jax.named_scope("ssd"):
            if ssm_state is None:
                y, final = ssd_chunked(xq, dt, p["a_log"], bq, cq, mc.chunk)
            else:
                y1, final = ssd_decode_step(xq[:, 0], dt[:, 0], p["a_log"],
                                            bq[:, 0], cq[:, 0], ssm_state)
                y = y1[:, None]
        y = y + xq * p["d_skip"][:, None].astype(y.dtype)
        y = _gated_norm(p["out_norm"]["scale"], y.reshape(b, l, mc.d_inner),
                        z, mc.n_groups, mc.norm_eps, x.dtype)
        return dense(p["out_proj"], y), new_conv_state, final


def mamba2_mixer_param_count(mc: MixerConfig) -> int:
    return (mc.d_model * mc.d_in_proj                       # in_proj
            + mc.conv_width * mc.conv_dim + mc.conv_dim     # conv
            + 3 * mc.n_heads                                # a/dt/D
            + mc.d_inner                                    # out_norm
            + mc.d_inner * mc.d_model)                      # out_proj


def mamba2_mixer_fwd_flops(mc: MixerConfig, tokens: int, *,
                           decode: bool = False) -> float:
    """Forward FLOPs of one mixer over ``tokens`` tokens: projections and
    the chunked SSD (or the recurrent step when ``decode``)."""
    proj = 2.0 * tokens * mc.d_model * (mc.d_in_proj + mc.d_inner)
    state = 4.0 * tokens * mc.n_heads * mc.head_dim * mc.d_state
    if decode:
        return proj + state
    return proj + state + 2.0 * tokens * mc.chunk * mc.n_heads * (
        mc.d_state + mc.head_dim)


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------

class Mamba2LM:
    # recurrent state folds every prefill step in (pad steps included), so
    # right-padded (chunked) prefill would corrupt it — exact prefill only
    kv_position_indexed = False

    def __init__(self, cfg: Mamba2Config):
        self.cfg = cfg
        self.mix = cfg.mixer

    # ------------------------------------------------------------------ init
    def _block_init(self, key: jax.Array):
        cfg = self.cfg
        mixer, mixer_spec = mamba2_mixer_init(Init(key), self.mix, cfg.dtype)
        p = {"ln": norm_init(cfg.d_model, dtype=cfg.dtype)[0], **mixer}
        spec = {"ln": {"scale": (None,)}, **mixer_spec}
        return p, spec

    def init(self, key: jax.Array) -> PyTree:
        cfg = self.cfg
        k1, k2, k3 = jax.random.split(key, 3)
        init = Init(k1)
        params: dict = {
            "embed": {"table": init.normal((cfg.vocab, cfg.d_model), 1.0,
                                           cfg.dtype)},
        }
        lkeys = jax.random.split(k2, cfg.n_layers)
        params["blocks"] = jax.vmap(lambda k: self._block_init(k)[0])(lkeys)
        head: dict = {"norm": norm_init(cfg.d_model, dtype=cfg.dtype)[0]}
        if not cfg.tie_embeddings:
            head["out"] = {"w": Init(k3).normal(
                (cfg.d_model, cfg.vocab), cfg.d_model ** -0.5, cfg.dtype)}
        params["head"] = head
        return params

    def param_specs(self) -> PyTree:
        box: dict = {}

        def fn(k):
            p, s = self._block_init(k)
            box["spec"] = s
            return p

        jax.eval_shape(fn, jax.random.PRNGKey(0))
        blk = jax.tree.map(lambda sp: ("layers",) + tuple(sp), box["spec"],
                           is_leaf=lambda x: isinstance(x, tuple))
        specs = {"embed": {"table": ("vocab", None)}, "blocks": blk,
                 "head": {"norm": {"scale": (None,)}}}
        if not self.cfg.tie_embeddings:
            specs["head"]["out"] = {"w": (None, "vocab")}
        return specs

    # ----------------------------------------------------------------- apply
    def _block_apply(self, p, x, conv_state=None, ssm_state=None):
        y, ncs, nss = mamba2_mixer_apply(p, self.mix, rms_norm(p["ln"], x),
                                         conv_state, ssm_state)
        return x + y.astype(x.dtype), ncs, nss

    def _backbone(self, params, tokens, cache=None):
        cfg = self.cfg
        x = params["embed"]["table"][tokens]

        if cache is None:
            def body(carry, lp):
                fn = self._block_apply
                if cfg.remat:
                    fn = jax.checkpoint(fn)
                y, _, _ = fn(lp, carry)
                return y, None
            x, _ = jax.lax.scan(body, x, params["blocks"])
            return x, None

        def body(carry, xs):
            lp, (cs, ss) = xs
            y, ncs, nss = self._block_apply(lp, carry, cs, ss)
            return y, (ncs, nss)
        x, new_cache = jax.lax.scan(body, x, (params["blocks"], cache))
        return x, new_cache

    def _head(self, params, x):
        x = rms_norm(params["head"]["norm"], x)
        if self.cfg.tie_embeddings:
            return x @ params["embed"]["table"].T
        return dense(params["head"]["out"], x)

    def apply(self, params, tokens) -> jax.Array:
        x, _ = self._backbone(params, tokens)
        return self._head(params, x)

    def loss(self, params, batch, *, segment_cuts=()) -> jax.Array:
        logits = self.apply(params, batch["tokens"])
        return softmax_xent(logits[:, :-1], batch["labels"][:, 1:])

    # --------------------------------------------------------------- serving
    def init_cache(self, batch: int, max_seq: int) -> PyTree:
        cfg, mc = self.cfg, self.mix
        one = (
            jnp.zeros((batch, mc.conv_width - 1, mc.conv_dim), cfg.dtype),
            jnp.zeros((batch, mc.n_heads, mc.head_dim, mc.d_state),
                      jnp.float32),
        )
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (cfg.n_layers,) + a.shape),
            one)

    def prefill(self, params, tokens, cache) -> tuple[jax.Array, PyTree]:
        """Run the full sequence, emitting per-layer final states."""
        cfg = self.cfg
        x = params["embed"]["table"][tokens]

        def body(carry, xs):
            lp, _ = xs
            y, new_conv, final = self._block_apply(lp, carry)
            return y, (new_conv.astype(cfg.dtype), final)

        x, new_cache = jax.lax.scan(body, x, (params["blocks"], cache))
        return self._head(params, x[:, -1:]), new_cache

    def decode_step(self, params, cache, token, pos
                    ) -> tuple[jax.Array, PyTree]:
        x, new_cache = self._backbone(params, token, cache)
        return self._head(params, x), new_cache

    # ------------------------------------------------------------- structure
    def unit_layout(self) -> UnitLayout:
        entries = [UnitEntry("embed", "embed", None)]
        entries += [UnitEntry(f"layer_{i}", "blocks", i)
                    for i in range(self.cfg.n_layers)]
        entries.append(UnitEntry("head", "head", None))
        return UnitLayout(tuple(entries))

    def _block_param_count(self) -> int:
        return self.cfg.d_model + mamba2_mixer_param_count(self.mix)

    def param_count(self) -> int:
        cfg = self.cfg
        n = cfg.vocab * cfg.d_model + cfg.n_layers * self._block_param_count()
        n += cfg.d_model
        if not cfg.tie_embeddings:
            n += cfg.d_model * cfg.vocab
        return n

    def active_param_count(self) -> int:
        return self.param_count()

    def layer_costs(self, batch: int, seq: int, *, mode: str = "train"):
        cfg = self.cfg
        tokens = batch * (seq if mode == "train" else 1)
        out = [("embed", float(cfg.vocab * cfg.d_model),
                2.0 * tokens * cfg.d_model)]
        per_p = float(self._block_param_count())
        per_f = mamba2_mixer_fwd_flops(self.mix, tokens,
                                       decode=mode != "train")
        for i in range(cfg.n_layers):
            out.append((f"layer_{i}", per_p, per_f))
        head_p = float(cfg.d_model + (0 if cfg.tie_embeddings
                                      else cfg.d_model * cfg.vocab))
        out.append(("head", head_p, 2.0 * tokens * cfg.d_model * cfg.vocab))
        return out
