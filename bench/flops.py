"""Operations and bytes that the algorithm needs, computed from shapes.

Everything here is arithmetic on a configuration file's sizes
(``bench/configs/<name>.json``); nothing is read from the program.
Matmul FLOPs count 2 per multiply-add.  Recomputed work (remat) is not
counted: these are the operations the model requires, so a share of the
peak built on them cannot pass 100% unless the time is wrong.
"""

from __future__ import annotations

from dataclasses import dataclass

__all__ = ["Dims", "dims_of", "matmul_params", "weight_bytes",
           "train_flops_per_token", "decode_flops", "kv_bytes",
           "paged_attention_cost"]


@dataclass(frozen=True)
class Dims:
    d: int          # hidden size
    layers: int
    heads: int
    kv_heads: int
    hd: int         # head size
    ff: int         # feed-forward width (SwiGLU: gate, up, down)
    vocab: int
    tied: bool
    param_bytes: int = 2    # bf16


def dims_of(cfg: dict) -> Dims:
    return Dims(d=cfg["hidden_size"], layers=cfg["num_hidden_layers"],
                heads=cfg["num_attention_heads"],
                kv_heads=cfg["num_key_value_heads"], hd=cfg["head_dim"],
                ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
                tied=bool(cfg["tie_word_embeddings"]))


def _layer_matmul_params(m: Dims) -> int:
    attn = m.d * m.hd * (m.heads + 2 * m.kv_heads) + m.heads * m.hd * m.d
    return attn + 3 * m.d * m.ff


def matmul_params(m: Dims) -> int:
    """Parameters that enter a matmul per token: every layer's
    projections plus the LM head once (tied or not; the embedding lookup
    is a gather, not a matmul)."""
    return m.layers * _layer_matmul_params(m) + m.vocab * m.d


def weight_bytes(m: Dims) -> int:
    """Bytes of every weight a decode iteration reads: the layers'
    matrices and norms, the final norm, and the LM head (the tied table
    once)."""
    n = m.layers * (_layer_matmul_params(m) + 2 * m.d) + m.d + m.vocab * m.d
    return n * m.param_bytes


def train_flops_per_token(m: Dims, seq: int) -> float:
    """Forward + backward FLOPs per trained token: 6 per matmul parameter
    plus the attention score and value products over the full ``seq``
    (the program computes the masked square, so causality saves
    nothing): 4*seq*heads*hd forward per layer, x3 with the backward."""
    return 6.0 * matmul_params(m) + 12.0 * m.layers * m.heads * m.hd * seq


def decode_flops(m: Dims, kv_len: int) -> float:
    """One decoded token attending over ``kv_len`` cached positions."""
    return 2.0 * matmul_params(m) + 4.0 * m.layers * m.heads * m.hd * kv_len


def kv_bytes(m: Dims, kv_len: int, cache_bytes: int = 2) -> int:
    """K and V of ``kv_len`` positions over every layer."""
    return 2 * m.layers * kv_len * m.kv_heads * m.hd * cache_bytes


def paged_attention_cost(m: Dims, kv_len: int,
                         cache_bytes: int = 2) -> tuple[float, int]:
    """(FLOPs, bytes) that one lane's paged-attention call needs in ONE
    layer: q.K^T and p.V over ``kv_len`` positions for every query head;
    the lane's K and V pages that hold those positions, its query and its
    output."""
    flops = 4.0 * m.heads * m.hd * kv_len
    kv = 2 * kv_len * m.kv_heads * m.hd * cache_bytes
    q_out = 2 * m.heads * m.hd * cache_bytes
    return flops, kv + q_out
