"""The hybrid DecoderLM (Mamba-2 and attention layers in a published
order) against the plain reference ``bench/reference/hybrid.py``, whose
state-space layer is the step-by-step recurrence; the Granite scales of
the dense block against ``bench/reference/decoder.py``.  Seeded float32
weights at a small size on the CPU."""

import copy
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.drivers.train_hybrid import lm_config
from bench.reference import decoder as dense_ref
from bench.reference import hybrid as ref
from bench.reference import weights
from repro.api import JobConfig, Session
from repro.models import mamba2
from repro.models.mamba2 import ssd_chunked
from repro.models.transformer import DecoderLM, LMConfig

ROOT = Path(__file__).resolve().parent.parent

# granite-4.0-h-micro's file with small widths: d 32, 4/2 heads of 8,
# Mamba-2 8 heads of 8 (d_inner 64), state 8, chunk 8; layers M M A M.
# At width 32, N(0, 0.16) gives a matmul the gain N(0, 0.02) gives at
# the published 2048 (0.02 * sqrt(2048) ~ 0.16 * sqrt(32)).
TINY = {
    "hidden_size": 32, "intermediate_size": 64, "num_hidden_layers": 4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "vocab_size": 128, "mamba_n_heads": 8, "mamba_d_head": 8,
    "mamba_d_state": 8, "mamba_chunk_size": 8, "attention_multiplier": 0.1,
    "layer_types": ["mamba", "mamba", "attention", "mamba"],
    "initializer_range": 0.16, "torch_dtype": "float32",
}
SEQ = 44                  # five chunks and a half: the SSD has a tail
SEED = 2718281828459

# Tolerances.  Both sides compute in float32 (the reference under
# "highest" matmul precision); what remains is summation order: the
# chunked SSD against the recurrence, the program's einsum attention
# against the reference's blocks, the blocked cross-entropy.  Measured
# worst gaps are ~1e-6 relative for the loss and ~1e-5 of a leaf's
# largest gradient entry; the limits leave ~10x.  Leaving out the conv
# or the D skip moves the loss by ~1e-2 relative and every mixer leaf's
# gradient by tens of percent.
LOSS_RTOL = 2e-5
GRAD_TOL = 2e-4


def _tiny(**over) -> dict:
    with open(ROOT / "bench" / "configs" / "granite-4.0-h-micro.json") as f:
        cfg = json.load(f)
    cfg.update(TINY)
    cfg.update(over)
    return cfg


def _tokens(cfg, rows=2, seq=SEQ):
    return jax.random.randint(jax.random.PRNGKey(7), (rows, seq), 0,
                              cfg["vocab_size"], jnp.int32)


def _program(cfg, params, tokens, model=None, **kw):
    model = model or DecoderLM(lm_config(cfg))
    batch = {"tokens": tokens, "labels": tokens}
    return jax.jit(jax.value_and_grad(
        lambda p: model.loss(p, batch, **kw)))(params)


def _reference(cfg, params, tokens, loss=ref.loss):
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            lambda p: loss(cfg, p, tokens)))(params)


def _gaps(got, want):
    """(loss gap relative to the loss, worst leaf's gradient gap relative
    to that leaf's largest entry)."""
    (lg, gg), (lw, gw) = got, want
    leaf = max(float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
               for a, b in zip(jax.tree.leaves(gg), jax.tree.leaves(gw),
                               strict=True))
    return abs(float(lg) - float(lw)) / abs(float(lw)), leaf


def _assert_close(got, want):
    (lg, gg), (lw, gw) = got, want
    np.testing.assert_allclose(float(lg), float(lw), rtol=LOSS_RTOL)
    flat = jax.tree_util.tree_leaves_with_path(gw)
    for (path, w), g in zip(flat, jax.tree.leaves(gg), strict=True):
        scale = float(jnp.max(jnp.abs(w)))
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=0,
                                   atol=GRAD_TOL * scale,
                                   err_msg=jax.tree_util.keystr(path))


def _weights(cfg, seed=SEED):
    return ref.make(cfg, weights.seed_key(seed, 0))


@pytest.mark.parametrize("seed", [SEED, 11, 12345678901])
def test_loss_and_grads_match_reference(seed):
    """The program's loss and the gradient of every leaf agree with the
    reference's on the same seeded weights and rows."""
    cfg = _tiny()
    params = _weights(cfg, seed)
    tokens = _tokens(cfg)
    _assert_close(_program(cfg, params, tokens),
                  _reference(cfg, params, tokens))


def test_weight_layout_is_the_programs():
    """The reference's seeded weights have the program's tree, shapes and
    dtypes (bf16 stored, the Mamba-2 scalars float32)."""
    cfg = _tiny(torch_dtype="bfloat16")
    model = DecoderLM(lm_config(cfg))
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    have = jax.eval_shape(lambda: _weights(cfg))
    assert jax.tree.structure(want) == jax.tree.structure(have)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(have),
                    strict=True):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    assert have["mamba_blocks"]["mixer"]["a_log"].dtype == jnp.float32


@pytest.mark.parametrize("seq,chunk,groups", [
    (40, 8, 1),           # five whole chunks
    (37, 8, 1),           # a tail that is not a whole chunk
    (64, 16, 2),          # two groups of B and C
    (5, 8, 1),            # shorter than a chunk
])
def test_ssd_chunked_matches_recurrence(seq, chunk, groups):
    """The program's chunked SSD against the step-by-step recurrence."""
    heads, p, n = 4, 8, 8
    ks = jax.random.split(jax.random.PRNGKey(seq * chunk + groups), 5)
    x = jax.random.normal(ks[0], (2, seq, heads, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (2, seq, heads)) - 1.0)
    a_log = jnp.log(jax.random.uniform(ks[2], (heads,), minval=1,
                                       maxval=16))
    b = jax.random.normal(ks[3], (2, seq, groups, n))
    c = jax.random.normal(ks[4], (2, seq, groups, n))
    got, _ = ssd_chunked(x, dt, a_log, b, c, chunk)
    with jax.default_matmul_precision("highest"):
        want = jax.vmap(ref.ssd_recurrence, in_axes=(0, 0, None, 0, 0))(
            x, dt, -jnp.exp(a_log), b, c)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4 * float(
                                   jnp.max(jnp.abs(want))))


@pytest.mark.parametrize("cuts", [
    (2,), (3,), (4,),     # the stack's kind boundaries (units 3|4, 4|5)
    (3, 4), (4, 5), (1, 2, 3, 4, 5)])
def test_segment_cuts_at_kind_boundaries(cuts):
    """Splitting the stacks into separate scans at DreamDDP's cuts, at
    and between the kinds' boundaries, leaves the loss and gradients as
    they are without cuts."""
    cfg = _tiny()
    model = DecoderLM(lm_config(cfg))
    params = _weights(cfg)
    tokens = _tokens(cfg)
    whole = _program(cfg, params, tokens, model)
    split = _program(cfg, params, tokens, model, segment_cuts=cuts)
    np.testing.assert_allclose(float(split[0]), float(whole[0]), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(split[1]), jax.tree.leaves(whole[1]),
                    strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6 * float(jnp.max(jnp.abs(b))))


def test_units_follow_network_order():
    """The unit layout lists the layers in the published order, each
    with its own stack's index, and the profile costs the kinds
    differently."""
    model = DecoderLM(lm_config(_tiny()))
    entries = model.unit_layout().entries
    assert [(e.name, e.group, e.index) for e in entries] == [
        ("embed", "embed", None), ("layer_0", "mamba_blocks", 0),
        ("layer_1", "mamba_blocks", 1), ("layer_2", "blocks", 0),
        ("layer_3", "mamba_blocks", 2), ("head", "head", None)]
    costs = model.layer_costs(1, SEQ)
    assert [c[0] for c in costs] == [e.name for e in entries]
    mamba, attention = costs[1][1:], costs[3][1:]
    assert costs[2][1:] == costs[4][1:] == mamba != attention
    assert attention[0] < mamba[0]


_DENSE = {
    "hidden_size": 32, "intermediate_size": 64, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "vocab_size": 128, "initializer_range": 0.16, "torch_dtype": "float32",
    "rope_theta": 10000.0, "tie_word_embeddings": True,
}
_SCALES = {"embedding_multiplier": 12.0, "residual_multiplier": 0.22,
           "logits_scaling": 8.0, "attention_multiplier": 0.1,
           "rms_norm_eps": 1e-5}


@pytest.mark.parametrize("scales", [_SCALES, {"rms_norm_eps": 1e-6}],
                         ids=["granite-scales", "defaults"])
def test_dense_scales_match_decoder_reference(scales):
    """A dense DecoderLM with Granite's four scales and epsilon (or with
    the defaults) against the dense reference, which reads those keys."""
    cfg = {**_DENSE, **scales}
    lm = LMConfig(
        name="dense-tiny", n_layers=3, d_model=32, n_heads=4, n_kv_heads=2,
        d_ff=64, vocab=128, head_dim=8, tie_embeddings=True,
        param_dtype="float32", rope_theta=10000.0,
        norm_eps=cfg["rms_norm_eps"],
        **{k: v for k, v in scales.items() if k != "rms_norm_eps"})
    params = weights.make(cfg, weights.seed_key(SEED, 0))
    tokens = _tokens(cfg, seq=24)
    _assert_close(_program(cfg, params, tokens, DecoderLM(lm)),
                  _reference(cfg, params, tokens, loss=dense_ref.loss))


def _no_conv(monkeypatch, params):
    monkeypatch.setattr(mamba2, "_causal_conv",
                        lambda w, bias, u: jax.nn.silu(u + bias))
    return params


def _no_skip(monkeypatch, params):
    params = copy.copy(params)
    blocks = dict(params["mamba_blocks"])
    blocks["mixer"] = {**blocks["mixer"], "d_skip": jnp.zeros_like(
        blocks["mixer"]["d_skip"])}
    params["mamba_blocks"] = blocks
    return params


@pytest.mark.parametrize("fault", [_no_conv, _no_skip])
def test_planted_fault_fails(fault, monkeypatch):
    """The program with the conv or the D skip left out is out of
    tolerance of the reference."""
    cfg = _tiny()
    params = _weights(cfg)
    tokens = _tokens(cfg)
    want = _reference(cfg, params, tokens)
    got = _program(cfg, fault(monkeypatch, params), tokens)
    loss_gap, grad_gap = _gaps(got, want)
    assert loss_gap > LOSS_RTOL or grad_gap > GRAD_TOL, (loss_gap, grad_gap)
    with pytest.raises(AssertionError):
        _assert_close(got, want)


def test_arch_trains_through_session():
    """granite-4.0-h-micro's registered smoke model trains by DreamDDP
    through ``Session.fit``: two workers, partial syncs over both
    kinds of layer, a finite loss that falls."""
    sess = Session(JobConfig(arch="granite-4.0-h-micro", workers=2,
                             period=2, seq=24, batch_per_worker=2, lr=1e-2,
                             warmup_steps=1))
    sess.fit(8)
    losses = [row["loss"] for row in sess.history]
    assert all(np.isfinite(losses)) and losses[-1] < losses[0], losses
    groups = {sess.model.unit_layout().entries[u].group
              for h in range(sess.plan.H)
              for u in sess.plan.units_for_phase(h)}
    assert {"mamba_blocks", "blocks"} <= groups
