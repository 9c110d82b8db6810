"""granite-4.0-h-micro — IBM Granite 4.0-H Micro (hybrid Mamba-2 + GQA).

[hf:ibm-granite/granite-4.0-h-micro]: 40 layers, attention at layers 5, 15,
25 and 35 and Mamba-2 mixers everywhere else; d_model 2048, SwiGLU 8192 in
every layer; GQA 32/8 heads of 64 with no positional encoding (NoPE);
Mamba-2 with 64 heads of 64, d_state 128, one group, conv width 4, chunk
256; vocab 100352, tied; RMSNorm eps 1e-5; embeddings x12, residual
branches x0.22, attention scores x1/64, logits /8.
"""

from ..models.mamba2 import MixerConfig
from ..models.transformer import DecoderLM, LMConfig
from .common import ArchSpec

LAYER_TYPES = tuple("attention" if i % 10 == 5 else "mamba"
                    for i in range(40))

CONFIG = LMConfig(
    name="granite-4.0-h-micro",
    n_layers=40,
    d_model=2048,
    n_heads=32,
    n_kv_heads=8,
    d_ff=8192,
    vocab=100_352,
    head_dim=64,
    mlp_kind="swiglu",
    norm_kind="rmsnorm",
    tie_embeddings=True,
    norm_eps=1e-5,
    rope=False,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=8.0,
    attention_multiplier=1 / 64,
    layer_types=LAYER_TYPES,
    mamba=MixerConfig(d_model=2048, d_state=128, head_dim=64, expand=2,
                      n_groups=1, conv_width=4, chunk=256, norm_eps=1e-5),
)

SMOKE = LMConfig(
    name="granite-h-smoke",
    n_layers=4,
    d_model=64,
    n_heads=8,
    n_kv_heads=2,
    d_ff=128,
    vocab=512,
    head_dim=8,
    tie_embeddings=True,
    param_dtype="float32",
    norm_eps=1e-5,
    rope=False,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=8.0,
    attention_multiplier=1 / 8,
    layer_types=("mamba", "mamba", "attention", "mamba"),
    mamba=MixerConfig(d_model=64, d_state=16, head_dim=8, chunk=8,
                      norm_eps=1e-5),
)

ARCH = ArchSpec(
    arch_id="granite-4.0-h-micro",
    family="hybrid",
    make_model=lambda: DecoderLM(CONFIG),
    make_smoke=lambda: DecoderLM(SMOKE),
    large=False,
    optimizer="adamw",
    sub_quadratic=False,
    notes="36 Mamba-2 + 4 NoPE GQA layers; serving not supported (the "
          "cache holds attention KV only); full attention => long_500k "
          "skipped",
)
