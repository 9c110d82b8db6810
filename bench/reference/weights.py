"""Seeded weights in the layout the benchmark hands to the program.

The benchmark makes the weights, not the program: the same function
feeds the system under test and the reference, so the reference takes
nothing the program made.  Matrices are drawn N(0, initializer_range)
and norm scales are 1, made on the device in one jitted call, in the
configuration's stored dtype.

Layout (stacked layers on a leading axis)::

    embed/table [V, d]
    blocks/ln1/scale [L, d]      blocks/ln2/scale [L, d]
    blocks/attn/{wq,wk,wv,wo}/w  blocks/mlp/{gate,up,down}/w
    head/norm/scale [d]
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["shapes", "make", "seed_key"]


def seed_key(seed: int, stream: int = 0) -> jax.Array:
    """A threefry key from any non-negative seed (wider than 32 bits
    too) and a stream number, so weights and data never share a key."""
    words = np.random.SeedSequence([int(seed), int(stream)]).generate_state(
        2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def shapes(cfg: dict) -> dict:
    """The tree of ``(shape, kind)``: kind is ``w`` (drawn) or ``1``."""
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    ff, v = cfg["intermediate_size"], cfg["vocab_size"]
    if not cfg["tie_word_embeddings"]:
        raise NotImplementedError("untied heads are not in this layout")
    return {
        "embed": {"table": ((v, d), "w")},
        "blocks": {
            "ln1": {"scale": ((L, d), "1")},
            "attn": {"wq": {"w": ((L, d, h * hd), "w")},
                     "wk": {"w": ((L, d, kv * hd), "w")},
                     "wv": {"w": ((L, d, kv * hd), "w")},
                     "wo": {"w": ((L, h * hd, d), "w")}},
            "ln2": {"scale": ((L, d), "1")},
            "mlp": {"gate": {"w": ((L, d, ff), "w")},
                    "up": {"w": ((L, d, ff), "w")},
                    "down": {"w": ((L, ff, d), "w")}},
        },
        "head": {"norm": {"scale": ((d,), "1")}},
    }


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], str)


def make(cfg: dict, key: jax.Array):
    """The weights, drawn in one jitted call."""
    dtype = jnp.dtype(cfg["torch_dtype"])
    std = float(cfg["initializer_range"])
    spec = shapes(cfg)
    leaves, treedef = jax.tree_util.tree_flatten(spec, is_leaf=_is_spec)

    def build(key):
        out = []
        for i, (shape, kind) in enumerate(leaves):
            if kind == "1":
                x = jnp.ones(shape, dtype)
            else:
                x = (jax.random.normal(jax.random.fold_in(key, i), shape,
                                       jnp.float32) * std).astype(dtype)
            out.append(x)
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(key)
