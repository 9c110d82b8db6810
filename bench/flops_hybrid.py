"""Operations a hybrid Mamba-2 / attention decoder needs, from shapes.

Arithmetic on a configuration file's sizes (``layer_types``, the
``mamba_*`` widths) as :mod:`bench.flops` is for the dense decoder:
2 FLOPs per multiply-add, the backward twice the forward, nothing
recomputed counted.  Attention and the SSD's intra-chunk products count
their causal half, the part the kernels compute.
"""

from __future__ import annotations

__all__ = ["train_flops_per_token"]


def _mixer_params(cfg: dict) -> int:
    d = cfg["hidden_size"]
    d_inner = cfg["mamba_expand"] * d
    gn = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    return d * (2 * d_inner + 2 * gn + cfg["mamba_n_heads"]) + d_inner * d


def _ssd_fwd_per_token(cfg: dict) -> float:
    """The chunked SSD's products for one token of one layer at chunk
    ``cs``: C.B over the causal half of its chunk, per group; the
    weighted sum of that half's inputs, per head; its share of the chunk
    state; and its output from the previous chunks' state."""
    cs = cfg["mamba_chunk_size"]
    h, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    g, n = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    return 2.0 * (cs / 2) * (g * n + h * p) + 2 * (2.0 * h * p * n)


def train_flops_per_token(cfg: dict, seq: int) -> float | None:
    """Forward + backward FLOPs per trained token of a hybrid
    configuration (``None`` for a file without ``layer_types``)."""
    if "layer_types" not in cfg:
        return None
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    n_m, n_a = kinds.count("mamba"), kinds.count("attention")
    attn = d * hd * (h + 2 * kv) + h * hd * d
    params = (n_m * _mixer_params(cfg) + n_a * attn
              + len(kinds) * 3 * d * ff + cfg["vocab_size"] * d)
    # q.k and p.v over the causal half of the sequence
    attn_products = n_a * 2.0 * 2 * (seq / 2) * h * hd
    return 6.0 * params + 3.0 * (attn_products
                                 + n_m * _ssd_fwd_per_token(cfg))
