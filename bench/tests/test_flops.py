"""The FLOP and byte counts of bench/flops.py against the executed dots
of the program's own step at a small size (``analysis/hlo_costs.py``)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import flops, harness
from bench.tests import tiny

harness.program_on_path()

from repro.analysis.hlo_costs import parse_module_costs  # noqa: E402
from repro.models.transformer import DecoderLM  # noqa: E402


@pytest.fixture(scope="module")
def small():
    cfg = dict(tiny.config("granite-3-2b"), **tiny.GRANITE_TINY,
               torch_dtype="float32")
    lm = dataclasses.replace(harness.lm_config(cfg), remat=False)
    model = DecoderLM(lm)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def executed_flops(fn, *args) -> float:
    return parse_module_costs(jax.jit(fn).lower(*args).compile()
                              .as_text()).flops


@pytest.mark.parametrize("seq", [32, 64])
def test_train_flops_match_executed_dots(small, seq):
    cfg, model, params = small
    toks = jnp.zeros((1, seq), jnp.int32)
    batch = {"tokens": toks, "labels": toks}
    got = executed_flops(jax.value_and_grad(model.loss), params, batch)
    want = flops.train_flops_per_token(flops.dims_of(cfg), seq) * seq
    assert got == pytest.approx(want, rel=1e-9)


@pytest.mark.parametrize("kv_len", [16, 48])
def test_decode_flops_match_executed_dots(small, kv_len):
    cfg, model, params = small
    cache = model.init_cache(1, kv_len)
    got = executed_flops(model.decode_step, params, cache,
                         jnp.zeros((1, 1), jnp.int32),
                         jnp.full((1,), kv_len - 1, jnp.int32))
    want = flops.decode_flops(flops.dims_of(cfg), kv_len)
    assert got == pytest.approx(want, rel=1e-9)


def test_paged_attention_bytes_match_shapes():
    cfg = tiny.config("granite-3-2b")
    m = flops.dims_of(cfg)
    kv_len = 1000
    q = np.zeros((m.heads, m.hd), np.float16)
    kv = np.zeros((kv_len, m.kv_heads, m.hd), np.float16)
    f, b = flops.paged_attention_cost(m, kv_len)
    assert b == 2 * q.nbytes + 2 * kv.nbytes       # q, out, K, V
    assert f == 2 * (2 * kv_len * m.heads * m.hd)  # q.K^T and p.V


def test_weight_bytes_count_every_parameter_once():
    cfg = tiny.config("granite-3-2b")
    m = flops.dims_of(cfg)
    shapes = jax.eval_shape(DecoderLM(harness.lm_config(cfg)).init,
                            jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree.leaves(shapes))
    assert flops.weight_bytes(m) == 2 * n
