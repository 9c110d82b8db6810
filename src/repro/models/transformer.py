"""Generic decoder-only LM: dense GQA, MoE, MLA and hybrid Mamba-2 variants.

One class serves eight of the eleven assigned architectures (granite,
granite-4.0-h, phi4, qwen2.5, qwen3, llava backbone, qwen3-moe,
deepseek-v3).  Blocks of the same kind are **stacked** (leading layer axis)
and executed with ``jax.lax.scan`` — constant HLO size in depth, the
standard TPU idiom — and the stack can be split at arbitrary unit
boundaries (``segment_cuts``) so a DreamDDP phase's parameter all-reduce
becomes data-independent of the remaining backward segments (the overlap
window XLA's latency-hiding scheduler exploits; DESIGN.md §2).  A hybrid
(``LMConfig.layer_types``) keeps one stack per kind and runs slices of the
stacks in the published layer order.

Parameter tree = dict of *groups* (the partial-sync unit space):
``embed`` / [``dense_blocks`` | ``mamba_blocks``] / ``blocks`` / [``mtp``]
/ ``head``.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp

from ..core.partial_sync import UnitEntry, UnitLayout
from ..kernels.paged_attention import paged_attention, write_token_to_pages
from . import mla as mla_mod
from . import moe as moe_mod
from .layers import (Init, apply_rope, causal_self_attention, dense,
                     dense_init, embed_init, gqa_attention, layer_norm,
                     mlp_apply, mlp_init, norm_init, rms_norm, rope_freqs,
                     softmax_xent)
from .mamba2 import (MixerConfig, mamba2_mixer_apply, mamba2_mixer_fwd_flops,
                     mamba2_mixer_init, mamba2_mixer_param_count)

__all__ = ["LMConfig", "DecoderLM"]

PyTree = Any

# a hybrid's layer type -> (stacked group, block kind)
_HYBRID = {"mamba": ("mamba_blocks", "mamba"), "attention": ("blocks", "dense")}


@dataclass(frozen=True)
class LMConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int | None = None
    qkv_bias: bool = False
    qk_norm: bool = False
    mlp_kind: str = "swiglu"
    norm_kind: str = "rmsnorm"
    rope_theta: float = 1e4
    tie_embeddings: bool = False
    window: int | None = None             # local attention window
    param_dtype: str = "bfloat16"
    remat: bool = True
    # MoE
    moe: moe_mod.MoEConfig | None = None
    n_dense_layers: int = 0               # leading dense layers (dsv3: 3)
    dense_d_ff: int | None = None
    # MLA
    mla: mla_mod.MLAConfig | None = None
    # Multi-token prediction (dsv3)
    mtp: bool = False
    mtp_weight: float = 0.3
    norm_eps: float = 1e-6                # RMSNorm epsilon
    rope: bool = True                     # False: no positional encoding
    # Granite's scales: token embeddings times, each residual branch
    # times, logits divided by; attention scores times (None: hd ** -0.5)
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    attention_multiplier: float | None = None
    # hybrid: each layer's mixer in network order, "attention" (GQA) or
    # "mamba" (a Mamba-2 mixer of widths ``mamba``); MLPs are dense
    layer_types: tuple[str, ...] | None = None
    mamba: MixerConfig | None = None

    def __post_init__(self):
        if self.layer_types is None:
            return
        if len(self.layer_types) != self.n_layers \
                or not set(self.layer_types) <= set(_HYBRID):
            raise ValueError(f"layer_types must give {self.n_layers} of "
                             f"{sorted(_HYBRID)}: {self.layer_types}")
        if "mamba" in self.layer_types and self.mamba is None:
            raise ValueError("mamba layers need LMConfig.mamba")
        if self.moe is not None or self.mla is not None or self.mtp:
            raise ValueError("a hybrid's layers are GQA or Mamba-2 mixers "
                             "with dense MLPs")

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def dtype(self):
        return jnp.dtype(self.param_dtype)

    def runs(self) -> list[tuple[str, str, int]]:
        """(group_name, block_kind, n_layers), one per stacked group, in
        the order of each group's first layer."""
        if self.layer_types is not None:
            n = {(g, k): hi for g, k, _, hi in self.pieces()}
            return [(g, k, hi) for (g, k), hi in n.items()]
        if self.moe is None:
            return [("blocks", "dense", self.n_layers)]
        out = []
        if self.n_dense_layers:
            out.append(("dense_blocks", "dense", self.n_dense_layers))
        out.append(("blocks", "moe", self.n_layers - self.n_dense_layers))
        return out

    def pieces(self) -> list[tuple[str, str, int, int]]:
        """(group_name, block_kind, lo, hi) in network order: the layers
        ``lo .. hi-1`` of a group's stack run next."""
        if self.layer_types is None:
            return [(g, k, 0, n) for g, k, n in self.runs()]
        out: list[tuple[str, str, int, int]] = []
        seen: dict[str, int] = {}
        for t in self.layer_types:
            g, k = _HYBRID[t]
            i = seen[g] = seen.get(g, -1) + 1
            if out and out[-1][0] == g:
                out[-1] = (g, k, out[-1][2], i + 1)
            else:
                out.append((g, k, i, i + 1))
        return out


class DecoderLM:
    """Functional decoder LM (init / apply / loss / prefill / decode)."""

    # cache entries are addressed by position and masked by valid length,
    # so right-padded (chunked) prefill cannot leak into decode
    kv_position_indexed = True
    # every attention variant (GQA / MoE blocks / MLA latents) stores
    # position-addressed KV, so the cache can live in a paged pool
    supports_paged_kv = True

    def __init__(self, cfg: LMConfig):
        self.cfg = cfg

    # ------------------------------------------------------------------ init
    def _attn_init(self, init: Init):
        cfg = self.cfg
        if cfg.mla is not None:
            return mla_mod.mla_init(init, cfg.mla, cfg.d_model,
                                    dtype=cfg.dtype)
        d, hd = cfg.d_model, cfg.hd
        p, s = {}, {}
        p["wq"], s["wq"] = dense_init(init, d, cfg.n_heads * hd,
                                      bias=cfg.qkv_bias, dtype=cfg.dtype,
                                      out_axis="heads")
        p["wk"], s["wk"] = dense_init(init, d, cfg.n_kv_heads * hd,
                                      bias=cfg.qkv_bias, dtype=cfg.dtype,
                                      out_axis="heads")
        p["wv"], s["wv"] = dense_init(init, d, cfg.n_kv_heads * hd,
                                      bias=cfg.qkv_bias, dtype=cfg.dtype,
                                      out_axis="heads")
        p["wo"], s["wo"] = dense_init(init, cfg.n_heads * hd, d,
                                      dtype=cfg.dtype,
                                      scale=(cfg.n_heads * hd) ** -0.5,
                                      in_axis="heads")
        if cfg.qk_norm:
            p["q_norm"], s["q_norm"] = norm_init(hd, dtype=cfg.dtype)
            p["k_norm"], s["k_norm"] = norm_init(hd, dtype=cfg.dtype)
        return p, s

    def _block_init(self, key: jax.Array, kind: str):
        cfg = self.cfg
        init = Init(key)
        p, s = {}, {}
        p["ln1"], s["ln1"] = norm_init(cfg.d_model, dtype=cfg.dtype,
                                       bias=cfg.norm_kind == "layernorm")
        if kind == "mamba":
            p["mixer"], s["mixer"] = mamba2_mixer_init(init, cfg.mamba,
                                                       cfg.dtype)
        else:
            p["attn"], s["attn"] = self._attn_init(init)
        p["ln2"], s["ln2"] = norm_init(cfg.d_model, dtype=cfg.dtype,
                                       bias=cfg.norm_kind == "layernorm")
        if kind == "moe":
            p["mlp"], s["mlp"] = moe_mod.moe_init(init, cfg.moe, cfg.d_model,
                                                  dtype=cfg.dtype)
        else:
            d_ff = cfg.dense_d_ff or cfg.d_ff
            p["mlp"], s["mlp"] = mlp_init(init, cfg.d_model, d_ff,
                                          kind=cfg.mlp_kind, dtype=cfg.dtype)
        return p, s

    def init(self, key: jax.Array) -> PyTree:
        cfg = self.cfg
        keys = iter(jax.random.split(key, 8))
        params: dict = {}
        params["embed"], self._embed_spec = embed_init(
            Init(next(keys)), cfg.vocab, cfg.d_model, dtype=cfg.dtype)
        for group, kind, n in cfg.runs():
            lkeys = jax.random.split(next(keys), n)
            params[group] = jax.vmap(
                lambda k, kd=kind: self._block_init(k, kd)[0])(lkeys)
        if cfg.mtp:
            init = Init(next(keys))
            blk, _ = self._block_init(init.next(),
                                      cfg.runs()[-1][1])
            proj, _ = dense_init(init, 2 * cfg.d_model, cfg.d_model,
                                 dtype=cfg.dtype)
            nrm, _ = norm_init(cfg.d_model, dtype=cfg.dtype)
            params["mtp"] = {"block": blk, "proj": proj, "norm": nrm}
        head: dict = {"norm": norm_init(cfg.d_model, dtype=cfg.dtype,
                                        bias=cfg.norm_kind == "layernorm")[0]}
        if not cfg.tie_embeddings:
            head["out"], _ = dense_init(Init(next(keys)), cfg.d_model,
                                        cfg.vocab, dtype=cfg.dtype,
                                        out_axis="vocab")
        params["head"] = head
        return params

    def param_specs(self) -> PyTree:
        """Logical-axis spec tree mirroring ``init``'s output (stacked
        groups get a leading ``layers`` axis)."""
        cfg = self.cfg
        specs: dict = {"embed": {"table": ("vocab", None)}}
        for group, kind, _ in cfg.runs():
            blk_spec = self._block_init_spec(kind)
            specs[group] = jax.tree.map(
                lambda sp: ("layers",) + tuple(sp), blk_spec,
                is_leaf=lambda x: isinstance(x, tuple))
        if cfg.mtp:
            specs["mtp"] = {
                "block": self._block_init_spec(cfg.runs()[-1][1]),
                "proj": {"w": (None, None)},
                "norm": {"scale": (None,)},
            }
        head: dict = {"norm": {"scale": (None,)}}
        if cfg.norm_kind == "layernorm":
            head["norm"]["bias"] = (None,)
        if not cfg.tie_embeddings:
            head["out"] = {"w": (None, "vocab")}
        specs["head"] = head
        return specs

    def _block_init_spec(self, kind: str) -> PyTree:
        """Spec of one (unstacked) block — computed without materializing
        any arrays (the spec is side-channeled out of an eval_shape trace)."""
        box: dict = {}

        def fn(k):
            p, s = self._block_init(k, kind)
            box["spec"] = s
            return p

        jax.eval_shape(fn, jax.random.PRNGKey(0))
        return box["spec"]

    # ----------------------------------------------------------------- apply
    def _project_qkv(self, p, x, positions):
        """Shared GQA preamble: projections, optional qk-norm, RoPE.
        Both cache layouts (contiguous lanes and the paged pool) go
        through here, so the paged-vs-contiguous bitwise equivalence
        cannot drift."""
        cfg = self.cfg
        b, s, _ = x.shape
        hd = cfg.hd
        q = dense(p["wq"], x).reshape(b, s, cfg.n_heads, hd)
        k = dense(p["wk"], x).reshape(b, s, cfg.n_kv_heads, hd)
        v = dense(p["wv"], x).reshape(b, s, cfg.n_kv_heads, hd)
        if cfg.qk_norm:
            q = rms_norm(p["q_norm"], q)
            k = rms_norm(p["k_norm"], k)
        if cfg.rope:
            inv_freq = rope_freqs(hd, cfg.rope_theta)
            q = apply_rope(q, positions, inv_freq)
            k = apply_rope(k, positions, inv_freq)
        return q, k, v

    def _attend(self, p, x, positions, cache, write_pos,
                own_positions=False):
        """Attention sub-layer; returns (out, new_cache_entry).

        ``own_positions``: ``positions`` are the sequence's own ``0 ..
        s-1``, built by :meth:`_backbone`.  Uncached, unwindowed
        attention over them is
        :func:`~repro.models.layers.causal_self_attention` (the flash
        kernel on TPU); explicit positions, a cache or a window take
        :func:`~repro.models.layers.gqa_attention`."""
        cfg = self.cfg
        if cfg.mla is not None:
            if cache is None:
                out, _ = mla_mod.mla_apply_full(p, cfg.mla, x, positions)
                return out, None
            if x.shape[1] > 1:           # prefill: full pass, then fill cache
                out, fresh = mla_mod.mla_apply_full(p, cfg.mla, x, positions)
                pos0 = write_pos[0]
                new_cache = {
                    k: jax.lax.dynamic_update_slice_in_dim(
                        cache[k], fresh[k].astype(cache[k].dtype), pos0,
                        axis=1)
                    for k in ("c_kv", "k_rope")
                }
                return out, new_cache
            return mla_mod.mla_decode(p, cfg.mla, x, cache, write_pos)

        b, s, _ = x.shape
        q, k, v = self._project_qkv(p, x, positions)

        scale = cfg.attention_multiplier
        if cache is None and own_positions and cfg.window is None:
            out = causal_self_attention(q, k, v, positions, scale=scale)
            new_cache = None
        elif cache is None:
            out = gqa_attention(q, k, v, q_positions=positions,
                                kv_positions=positions, causal=True,
                                window=cfg.window, scale=scale)
            new_cache = None
        else:
            pos0 = write_pos[0]
            ck = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cache["k"].dtype), pos0, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cache["v"].dtype), pos0, axis=1)
            sk = ck.shape[1]
            kv_pos = jnp.broadcast_to(jnp.arange(sk), (b, sk))
            out = gqa_attention(q, ck, cv, q_positions=positions,
                                kv_positions=kv_pos, causal=True,
                                window=cfg.window, scale=scale,
                                kv_valid_len=write_pos + s)
            new_cache = {"k": ck, "v": cv}
        return out.reshape(b, s, -1) @ p["wo"]["w"], new_cache

    def _norm(self, p, x):
        return (rms_norm(p, x, eps=self.cfg.norm_eps)
                if self.cfg.norm_kind == "rmsnorm" else layer_norm(p, x))

    def _add(self, x, branch):
        """The residual stream plus a branch times the residual
        multiplier."""
        rm = self.cfg.residual_multiplier
        return x + (branch if rm == 1.0 else branch * rm)

    def _mlp(self, kind: str, p, x):
        h = self._norm(p["ln2"], x)
        if kind == "moe":
            return self._add(x, moe_mod.moe_apply(p["mlp"], self.cfg.moe, h))
        return self._add(x, mlp_apply(p["mlp"], h, kind=self.cfg.mlp_kind))

    def _block_apply(self, kind: str, p, x, positions, cache=None,
                     write_pos=None, own_positions=False):
        h = self._norm(p["ln1"], x)
        if kind == "mamba":
            a, new_cache = mamba2_mixer_apply(p["mixer"], self.cfg.mamba,
                                              h)[0], None
        else:
            a, new_cache = self._attend(p["attn"], h, positions, cache,
                                        write_pos, own_positions)
        return self._mlp(kind, p, self._add(x, a)), new_cache

    def _run_stack(self, kind, stacked, x, positions, cache=None,
                   write_pos=None, cuts=(), own_positions=False,
                   span=None):
        """Scan a block stack's layers ``span`` (``(lo, hi)``, all of
        them by default) over its layer axis, split at ``cuts``."""
        lo0, hi0 = span or (0, jax.tree_util.tree_leaves(stacked)[0]
                            .shape[0])
        bounds = sorted({lo0, hi0, *[c for c in cuts if lo0 < c < hi0]})
        caches = []
        for lo, hi in zip(bounds[:-1], bounds[1:], strict=True):
            seg = jax.tree.map(lambda a, lo=lo, hi=hi: a[lo:hi], stacked)
            seg_cache = (None if cache is None else
                         jax.tree.map(lambda a, lo=lo, hi=hi: a[lo:hi],
                                      cache))

            def body(carry, xs):
                lp, lc = xs
                fn = self._block_apply
                if self.cfg.remat and cache is None:
                    fn = jax.checkpoint(fn, static_argnums=(0, 6))
                y, nc = fn(kind, lp, carry, positions, lc, write_pos,
                           own_positions)
                return y, nc

            x, new_c = jax.lax.scan(body, x, (seg, seg_cache))
            if cache is not None:
                caches.append(new_c)
        if cache is None:
            return x, None
        new_cache = jax.tree.map(lambda *xs: jnp.concatenate(xs, 0), *caches)
        return x, new_cache

    def _embed(self, params, tokens, embeds):
        cfg = self.cfg
        parts = []
        if embeds is not None:
            parts.append(embeds.astype(cfg.dtype))
        if tokens is not None:
            e = params["embed"]["table"][tokens]
            m = cfg.embedding_multiplier
            parts.append(e if m == 1.0 else e * m)
        return jnp.concatenate(parts, axis=1) if len(parts) > 1 \
            else parts[0]

    def _head(self, params, x):
        x = self._norm(params["head"]["norm"], x)
        if self.cfg.tie_embeddings:
            logits = x @ params["embed"]["table"].T
        else:
            logits = dense(params["head"]["out"], x)
        ls = self.cfg.logits_scaling
        return logits if ls == 1.0 else logits / ls

    def _backbone(self, params, tokens=None, *, embeds=None, positions=None,
                  segment_cuts: tuple[int, ...] = ()) -> jax.Array:
        """Embed + block stacks -> final hidden states ``[b, s_total, d]``.

        ``segment_cuts`` are *global unit ids* (layout order) at which block
        stacks are split into separate scans (DreamDDP overlap windows).
        Without ``positions`` each token sits at its index (see
        :meth:`_attend`).
        """
        cfg = self.cfg
        x = self._embed(params, tokens, embeds)
        b, s, _ = x.shape
        own_positions = positions is None
        if own_positions:
            positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        unit0 = 1                        # unit 0 is the embedding
        for group, kind, lo, hi in cfg.pieces():
            n = hi - lo
            local_cuts = tuple(lo + c - unit0 for c in segment_cuts
                               if unit0 < c < unit0 + n)
            x, _ = self._run_stack(kind, params[group], x, positions,
                                   cuts=local_cuts,
                                   own_positions=own_positions,
                                   span=(lo, hi))
            unit0 += n
        return x

    def apply(self, params, tokens=None, *, embeds=None, positions=None,
              segment_cuts: tuple[int, ...] = ()) -> jax.Array:
        """Full-sequence forward -> logits ``[b, s_total, vocab]``."""
        x = self._backbone(params, tokens, embeds=embeds,
                           positions=positions, segment_cuts=segment_cuts)
        return self._head(params, x)

    # ----------------------------------------------------------------- loss
    def loss(self, params, batch, *,
             segment_cuts: tuple[int, ...] = ()) -> jax.Array:
        cfg = self.cfg
        embeds = batch.get("embeds")
        tokens = batch.get("tokens")
        x = self._backbone(params, tokens, embeds=embeds,
                           segment_cuts=segment_cuts)
        if embeds is not None:           # VLM: loss on the text tail only
            x = x[:, embeds.shape[1]:]
        logits = self._head(params, x)
        labels = batch["labels"]
        loss = softmax_xent(logits[:, :-1], labels[:, 1:])
        if cfg.mtp:
            loss = loss + cfg.mtp_weight * self._mtp_loss(params, x, batch)
        return loss

    def _mtp_loss(self, params, trunk_h, batch) -> jax.Array:
        """DeepSeek-V3 multi-token prediction: one extra block predicts
        token ``t+2`` from ``[h_t ; E(tok_{t+1})]`` (trunk is shared)."""
        cfg = self.cfg
        tokens, labels = batch["tokens"], batch["labels"]
        b, s, _ = trunk_h.shape
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        mtp = params["mtp"]
        nxt = params["embed"]["table"][tokens[:, 1:]]
        h = jnp.concatenate([self._norm(mtp["norm"], trunk_h[:, :-1]), nxt],
                            -1)
        h = dense(mtp["proj"], h)
        h, _ = self._block_apply(cfg.runs()[-1][1], mtp["block"], h,
                                 positions[:, :-1])
        logits = self._head(params, h)
        return softmax_xent(logits[:, :-1], labels[:, 2:])

    # --------------------------------------------------------------- serving
    def _kv_only(self) -> None:
        if self.cfg.mamba is not None:
            raise NotImplementedError(
                f"{self.cfg.name}: serving keeps attention KV only; a "
                "hybrid's Mamba-2 state has no cache here")

    def init_cache(self, batch: int, max_seq: int) -> PyTree:
        self._kv_only()
        cfg = self.cfg
        cache: dict = {}
        for group, _kind, n in cfg.runs():
            if cfg.mla is not None:
                one = mla_mod.mla_init_cache(cfg.mla, batch, max_seq,
                                             cfg.dtype)
            else:
                one = {
                    "k": jnp.zeros((batch, max_seq, cfg.n_kv_heads, cfg.hd),
                                   cfg.dtype),
                    "v": jnp.zeros((batch, max_seq, cfg.n_kv_heads, cfg.hd),
                                   cfg.dtype),
                }
            cache[group] = jax.tree.map(
                lambda a, n=n: jnp.broadcast_to(a[None], (n,) + a.shape),
                one)
        return cache

    def prefill(self, params, tokens, cache, *,
                embeds=None) -> tuple[jax.Array, PyTree]:
        """Fill the cache with ``tokens`` (``embeds`` optionally prepended —
        VLM prefix); returns (last-token logits, cache)."""
        cfg = self.cfg
        x = self._embed(params, tokens, embeds)
        b, s, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        write_pos = jnp.zeros((b,), jnp.int32)
        new_cache = {}
        for group, kind, _n in cfg.runs():
            x, new_cache[group] = self._run_stack(
                kind, params[group], x, positions,
                cache=cache[group], write_pos=write_pos)
        logits = self._head(params, x[:, -1:])
        return logits, new_cache

    def decode_step(self, params, cache, token, pos
                    ) -> tuple[jax.Array, PyTree]:
        """One-token decode.  ``token [b, 1]``, ``pos [b]`` (write index)."""
        cfg = self.cfg
        x = self._embed(params, token, None)
        b = x.shape[0]
        positions = pos[:, None]
        new_cache = {}
        for group, kind, _n in cfg.runs():
            x, new_cache[group] = self._run_stack(
                kind, params[group], x, positions,
                cache=cache[group], write_pos=pos)
        return self._head(params, x), new_cache

    # -------------------------------------------------------- paged serving
    def init_paged_cache(self, n_pages: int, page_size: int) -> PyTree:
        """Global KV page pool: per group, leaves are ``[layers, n_pages,
        page_size, ...]`` (GQA: k/v heads; MLA: latent + key-rope).  Page
        0 is reserved by the pool as a trash page (see
        :class:`repro.serve.cache.PagedCachePool`)."""
        self._kv_only()
        cfg = self.cfg
        cache: dict = {}
        for group, _kind, n in cfg.runs():
            if cfg.mla is not None:
                one = mla_mod.mla_init_paged_cache(cfg.mla, n_pages,
                                                   page_size, cfg.dtype)
            else:
                one = {
                    "k": jnp.zeros((n_pages, page_size, cfg.n_kv_heads,
                                    cfg.hd), cfg.dtype),
                    "v": jnp.zeros((n_pages, page_size, cfg.n_kv_heads,
                                    cfg.hd), cfg.dtype),
                }
            cache[group] = jax.tree.map(
                lambda a, n=n: jnp.broadcast_to(a[None], (n,) + a.shape),
                one)
        return cache

    def _attend_paged(self, p, x, positions, pages, block_tables, pos,
                      active):
        """Paged-pool counterpart of the decode branch of ``_attend``:
        write this token's KV into its slot's current page (inactive
        lanes write the trash page), then attend through the block
        table.  ``x [slots, 1, d]``."""
        cfg = self.cfg
        if cfg.mla is not None:
            return mla_mod.mla_decode_paged(p, cfg.mla, x, pages,
                                            block_tables, pos, active)
        b, s, _ = x.shape
        q, k, v = self._project_qkv(p, x, positions)
        ck = write_token_to_pages(pages["k"], block_tables, pos, active,
                                  k[:, 0])
        cv = write_token_to_pages(pages["v"], block_tables, pos, active,
                                  v[:, 0])
        out = paged_attention(q[:, 0], ck, cv, block_tables, pos + 1,
                              window=cfg.window,
                              scale=cfg.attention_multiplier)
        return out.reshape(b, s, -1) @ p["wo"]["w"], {"k": ck, "v": cv}

    def _block_apply_paged(self, kind, p, x, positions, pages,
                           block_tables, pos, active):
        a, new_pages = self._attend_paged(p["attn"],
                                          self._norm(p["ln1"], x),
                                          positions, pages, block_tables,
                                          pos, active)
        return self._mlp(kind, p, self._add(x, a)), new_pages

    def decode_step_paged(self, params, pages, token, pos, block_tables,
                          active) -> tuple[jax.Array, PyTree]:
        """Slot-batched one-token decode against the page pool.

        ``token [slots, 1]``, ``pos [slots]`` (per-slot write index),
        ``block_tables [slots, max_blocks]`` int32, ``active [slots]``
        bool (gates page writes).  Returns (logits ``[slots, 1, vocab]``,
        updated page pool).
        """
        cfg = self.cfg
        x = self._embed(params, token, None)
        positions = pos[:, None]
        new_pages = {}
        for group, kind, _n in cfg.runs():
            def body(carry, xs, kd=kind):
                lp, lpg = xs
                return self._block_apply_paged(kd, lp, carry, positions,
                                               lpg, block_tables, pos,
                                               active)

            x, new_pages[group] = jax.lax.scan(
                body, x, (params[group], pages[group]))
        return self._head(params, x), new_pages

    # ------------------------------------------------------------- structure
    def unit_layout(self) -> UnitLayout:
        cfg = self.cfg
        entries = [UnitEntry("embed", "embed", None)]
        for group, _kind, lo, hi in cfg.pieces():
            for i in range(lo, hi):
                entries.append(UnitEntry(f"layer_{len(entries) - 1}", group,
                                         i))
        if cfg.mtp:
            entries.append(UnitEntry("mtp", "mtp", None))
        entries.append(UnitEntry("head", "head", None))
        return UnitLayout(tuple(entries))

    # ---------------------------------------------------- analytic accounting
    def _block_param_count(self, kind: str) -> int:
        cfg = self.cfg
        d, hd = cfg.d_model, cfg.hd
        if kind == "mamba":
            attn = mamba2_mixer_param_count(cfg.mamba)
        elif cfg.mla is not None:
            attn = mla_mod.mla_param_count(cfg.mla, d)
        else:
            attn = d * hd * (cfg.n_heads + 2 * cfg.n_kv_heads) \
                + cfg.n_heads * hd * d
            if cfg.qkv_bias:
                attn += hd * (cfg.n_heads + 2 * cfg.n_kv_heads)
            if cfg.qk_norm:
                attn += 2 * hd
        norms = 2 * d * (2 if cfg.norm_kind == "layernorm" else 1)
        if kind == "moe":
            mlp = moe_mod.moe_param_count(cfg.moe, d)
        else:
            d_ff = cfg.dense_d_ff or cfg.d_ff
            mlp = d * d_ff * (3 if cfg.mlp_kind == "swiglu" else 2)
        return attn + mlp + norms

    def param_count(self) -> int:
        cfg = self.cfg
        n = cfg.vocab * cfg.d_model                       # embed
        for _group, kind, cnt in cfg.runs():
            n += cnt * self._block_param_count(kind)
        if cfg.mtp:
            n += self._block_param_count(cfg.runs()[-1][1]) \
                + 2 * cfg.d_model * cfg.d_model + cfg.d_model
        n += cfg.d_model                                  # final norm
        if not cfg.tie_embeddings:
            n += cfg.d_model * cfg.vocab
        return n

    def active_param_count(self) -> int:
        """Active params per token (MoE: top-k + shared only)."""
        cfg = self.cfg
        if cfg.moe is None:
            return self.param_count()
        n = cfg.vocab * cfg.d_model + cfg.d_model
        if not cfg.tie_embeddings:
            n += cfg.d_model * cfg.vocab
        for _group, kind, cnt in cfg.runs():
            per = self._block_param_count(kind)
            if kind == "moe":
                per = (per - moe_mod.moe_param_count(cfg.moe, cfg.d_model)
                       + moe_mod.moe_active_param_count(cfg.moe, cfg.d_model))
            n += cnt * per
        return n

    def _block_fwd_flops(self, kind: str, tokens: int, seq: int,
                         kv_len: int, decode: bool = False) -> float:
        cfg = self.cfg
        d, hd = cfg.d_model, cfg.hd
        if kind == "mamba":
            attn = mamba2_mixer_fwd_flops(cfg.mamba, tokens, decode=decode)
        elif cfg.mla is not None:
            attn = mla_mod.mla_fwd_flops(cfg.mla, d, tokens, kv_len)
        else:
            proj = d * hd * (cfg.n_heads + 2 * cfg.n_kv_heads) \
                + cfg.n_heads * hd * d
            att_len = kv_len if cfg.window is None else min(kv_len,
                                                            cfg.window)
            attn = 2.0 * tokens * proj \
                + 2.0 * tokens * att_len * cfg.n_heads * hd * 2
        if kind == "moe":
            mlp = moe_mod.moe_fwd_flops(cfg.moe, d, tokens, seq)
        else:
            d_ff = cfg.dense_d_ff or cfg.d_ff
            mlp = 2.0 * tokens * d * d_ff * (3 if cfg.mlp_kind == "swiglu"
                                             else 2)
        return attn + mlp

    def layer_costs(self, batch: int, seq: int, *,
                    mode: str = "train") -> list[tuple[str, float, float]]:
        """(unit_name, n_params, fwd_flops) per unit — profiler input.

        ``mode="decode"`` charges one-token steps against a ``seq``-deep KV
        cache (serving shapes)."""
        cfg = self.cfg
        if mode == "train":
            tokens, kv_len, s = batch * seq, seq, seq
        else:
            tokens, kv_len, s = batch * 1, seq, seq
        out = [("embed", float(cfg.vocab * cfg.d_model), 2.0 * tokens
                * cfg.d_model)]
        for _group, kind, lo, hi in cfg.pieces():
            per_p = float(self._block_param_count(kind))
            per_f = self._block_fwd_flops(kind, tokens, s, kv_len,
                                          decode=mode != "train")
            for _ in range(lo, hi):
                out.append((f"layer_{len(out) - 1}", per_p, per_f))
        if cfg.mtp:
            p = float(self._block_param_count(cfg.runs()[-1][1])
                      + 2 * cfg.d_model * cfg.d_model)
            f = self._block_fwd_flops(cfg.runs()[-1][1], tokens, s, kv_len) \
                + 2.0 * tokens * 2 * cfg.d_model * cfg.d_model
            out.append(("mtp", p, f))
        head_p = float(cfg.d_model + (0 if cfg.tie_embeddings
                                      else cfg.d_model * cfg.vocab))
        head_f = 2.0 * tokens * cfg.d_model * cfg.vocab
        out.append(("head", head_p, head_f))
        return out
