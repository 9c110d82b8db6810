"""Per-arch smoke tests: reduced config, one forward/train step on CPU,
shape + finiteness asserts; decode-vs-full-forward equivalence per family.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_arch
from repro.models.layers import count_params

ALL_ARCHS = sorted(ARCHS)


def _smoke_batch(arch, model, key, b=2, s=16):
    cfg = model.cfg
    toks = jax.random.randint(key, (b, s), 0, cfg.vocab)
    batch = {"tokens": toks, "labels": toks}
    if arch.frontend == "audio":
        batch["frames"] = jax.random.normal(key, (b, cfg.n_frames,
                                                  cfg.d_model))
    if arch.frontend == "vision":
        batch["embeds"] = jax.random.normal(key, (b, 4, cfg.d_model))
    return batch


@pytest.mark.parametrize("arch_id", ALL_ARCHS)
def test_smoke_forward_and_train_step(arch_id):
    arch = get_arch(arch_id)
    model = arch.make_smoke()
    key = jax.random.PRNGKey(0)
    params = model.init(key)
    assert count_params(params) == model.param_count()

    batch = _smoke_batch(arch, model, jax.random.PRNGKey(1))
    loss, grads = jax.value_and_grad(model.loss)(params, batch)
    assert jnp.isfinite(loss), arch_id
    gleaves = jax.tree_util.tree_leaves(grads)
    assert all(bool(jnp.isfinite(g).all()) for g in gleaves), arch_id
    # one SGD step moves the loss
    params2 = jax.tree.map(lambda p, g: p - 0.5 * g, params, grads)
    loss2 = model.loss(params2, batch)
    assert jnp.isfinite(loss2)


@pytest.mark.parametrize("arch_id", ALL_ARCHS)
def test_smoke_unit_layout_matches_costs(arch_id):
    arch = get_arch(arch_id)
    for model in (arch.make_smoke(), arch.make_model()):
        layout = model.unit_layout()
        costs = model.layer_costs(2, 64)
        assert len(layout) == len(costs)
        assert [c[0] for c in costs] == list(layout.names)
        layout.validate_against(
            jax.eval_shape(model.init, jax.random.PRNGKey(0)),
            worker_stacked=False)


@pytest.mark.parametrize("arch_id", ["granite-3-2b", "qwen3-moe-30b-a3b",
                                     "deepseek-v3-671b", "mamba2-780m",
                                     "recurrentgemma-9b", "whisper-medium"])
def test_smoke_decode_matches_full_forward(arch_id):
    arch = get_arch(arch_id)
    model = arch.make_smoke()
    if getattr(model.cfg, "moe", None) is not None:
        # capacity dropping is order-dependent (full-seq prefill may drop
        # what one-token decode never does); compare with dropless capacity
        import dataclasses
        moe = dataclasses.replace(model.cfg.moe,
                                  capacity_factor=float(
                                      model.cfg.moe.n_experts))
        model = type(model)(dataclasses.replace(model.cfg, moe=moe))
    key = jax.random.PRNGKey(0)
    params = model.init(key)
    b, s = 2, 12
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0,
                              model.cfg.vocab)
    cache = model.init_cache(b, s + 4)
    if arch.frontend == "audio":
        frames = jax.random.normal(key, (b, model.cfg.n_frames,
                                         model.cfg.d_model))
        lg, cache = model.prefill(params, toks, cache, frames)
        full = model.apply(params, toks, frames)
    else:
        lg, cache = model.prefill(params, toks, cache)
        full = model.apply(params, toks)
    np.testing.assert_allclose(np.asarray(lg[:, 0]),
                               np.asarray(full[:, -1]), rtol=2e-3,
                               atol=2e-3)
    nxt = jnp.argmax(lg, -1).astype(jnp.int32)
    lg2, cache = model.decode_step(params, cache, nxt,
                                   jnp.full((b,), s, jnp.int32))
    toks2 = jnp.concatenate([toks, nxt], 1)
    if arch.frontend == "audio":
        full2 = model.apply(params, toks2, frames)
    else:
        full2 = model.apply(params, toks2)
    np.testing.assert_allclose(np.asarray(lg2[:, 0]),
                               np.asarray(full2[:, -1]), rtol=5e-3,
                               atol=5e-3)


def test_full_config_param_counts():
    """Published sizes (the config-fidelity check)."""
    expect = {
        "granite-3-2b": (2.3e9, 2.8e9),
        "phi4-mini-3.8b": (3.5e9, 4.2e9),
        "qwen2.5-32b": (31e9, 34e9),
        "qwen3-1.7b": (1.6e9, 2.1e9),
        "llava-next-34b": (33e9, 36e9),
        "mamba2-780m": (0.7e9, 0.85e9),
        "recurrentgemma-9b": (8.0e9, 9.5e9),
        "qwen3-moe-30b-a3b": (29e9, 32e9),
        "deepseek-v3-671b": (650e9, 700e9),
        "whisper-medium": (0.7e9, 0.85e9),
        "granite-4.0-h-micro": (3.1e9, 3.3e9),
    }
    for aid, (lo, hi) in expect.items():
        n = get_arch(aid).make_model().param_count()
        assert lo <= n <= hi, (aid, n)
    # MoE active counts
    assert 3.0e9 <= get_arch("qwen3-moe-30b-a3b").make_model() \
        .active_param_count() <= 3.7e9
    assert 34e9 <= get_arch("deepseek-v3-671b").make_model() \
        .active_param_count() <= 40e9


def test_segment_cuts_preserve_function():
    arch = get_arch("granite-3-2b")
    model = arch.make_smoke()
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                              model.cfg.vocab)
    a = model.apply(params, toks)
    b = model.apply(params, toks, segment_cuts=(2, 3))
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


# ---------------------------------------------------------------------------
# Causal self-attention: the flash kernel's path and what stays on einsum
# ---------------------------------------------------------------------------

def _granite_f32(**over):
    """granite-3-2b's smoke model in float32 (so two attention paths
    can be compared tightly), with ``over`` applied to its config."""
    from dataclasses import replace

    from repro.models.transformer import DecoderLM
    cfg = get_arch("granite-3-2b").make_smoke().cfg
    return DecoderLM(replace(cfg, param_dtype="float32", **over))


def test_flash_path_loss_and_grads_match_einsum(monkeypatch):
    """The smoke model's loss and every gradient on the flash kernels'
    path (interpret mode here, steered onto it as a TPU lowering would
    be) agree with the einsum path the CPU takes."""
    from repro.kernels.flash_attention import flash_attention
    from repro.models import transformer

    def loss_and_grads():
        model = _granite_f32()          # a fresh model: no cached trace
        params = model.init(jax.random.PRNGKey(0))
        batch = _smoke_batch(get_arch("granite-3-2b"), model,
                             jax.random.PRNGKey(1), b=2, s=40)
        return jax.jit(jax.value_and_grad(model.loss))(params, batch)

    loss, grads = loss_and_grads()
    calls = []

    def flash(q, k, v, positions, **kw):
        calls.append(q.shape)
        return flash_attention(q, k, v, **kw)

    monkeypatch.setattr(transformer, "causal_self_attention", flash)
    flash_loss, flash_grads = loss_and_grads()
    assert calls
    np.testing.assert_allclose(float(flash_loss), float(loss), rtol=1e-5)
    flat = jax.tree_util.tree_leaves_with_path(grads)
    for (path, want), got in zip(flat, jax.tree.leaves(flash_grads),
                                 strict=True):
        scale = max(1e-6, float(np.abs(want).max()))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-3, atol=1e-4 * scale,
                                   err_msg=jax.tree_util.keystr(path))


def _count_causal_calls(monkeypatch):
    """Count the model's calls of ``causal_self_attention``."""
    from repro.models import transformer
    calls = []
    real = transformer.causal_self_attention

    def spy(q, k, v, positions, **kw):
        calls.append(q.shape)
        return real(q, k, v, positions, **kw)

    monkeypatch.setattr(transformer, "causal_self_attention", spy)
    return calls


def test_attention_routing(monkeypatch):
    """Training's loss and ``apply`` without positions take causal
    self-attention, which carries the flash kernels for a TPU lowering;
    explicit positions, a cache (prefill, decode) and a local window
    keep the einsum path.  Each call gets a fresh model, so no cached
    trace of a layer hides a call."""
    calls = _count_causal_calls(monkeypatch)
    params = _granite_f32().init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                              _granite_f32().cfg.vocab)
    batch = {"tokens": toks, "labels": toks}

    jaxpr = str(jax.make_jaxpr(jax.grad(_granite_f32().loss))(params,
                                                              batch))
    assert calls and "splash_mqa_fwd" in jaxpr and "splash_mqa_dkv" in jaxpr
    calls.clear()
    jax.make_jaxpr(_granite_f32().apply)(params, toks)
    assert calls
    calls.clear()

    positions = jnp.broadcast_to(jnp.arange(16), (2, 16))
    jax.make_jaxpr(lambda p, t: _granite_f32().apply(
        p, t, positions=positions))(params, toks)
    model = _granite_f32()
    _, cache = jax.jit(model.prefill)(params, toks, model.init_cache(2, 32))
    jax.jit(model.decode_step)(params, cache, toks[:, :1],
                               jnp.full((2,), 16, jnp.int32))
    jaxpr = str(jax.make_jaxpr(jax.grad(_granite_f32(window=8).loss))(
        params, batch))
    assert not calls and "pallas_call" not in jaxpr
