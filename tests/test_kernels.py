"""Pallas kernel sweeps (interpret mode) vs pure-jnp oracles."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import attention_ref, flash_attention
from repro.kernels.fused_adam_sync import adamw_ref, fused_adamw_step
from repro.kernels.int8_quant import (dequantize, quantize,
                                      quantize_rows_ref)
from repro.kernels.paged_attention import (gather_pages, paged_attention,
                                           paged_attention_ref)
from repro.kernels.ssd_scan import ssd_chunk, ssd_chunk_ref


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FLASH_CASES = [
    (1, 128, 4, 2, 32),
    (2, 192, 8, 8, 16),     # MHA
    (1, 256, 4, 1, 64),     # MQA
    (2, 100, 6, 2, 8),      # ragged seq (padding path)
    (1, 256, 32, 8, 64),    # granite-3-2b's head layout
    (1, 200, 32, 8, 64),    # the same, ragged
]


def _qkv(b, sq, nq, nkv, hd, dtype, lead=()):
    k0, k1, k2 = jax.random.split(jax.random.PRNGKey(sq + nq), 3)
    return (jax.random.normal(k0, (*lead, b, sq, nq, hd), dtype),
            jax.random.normal(k1, (*lead, b, sq, nkv, hd), dtype),
            jax.random.normal(k2, (*lead, b, sq, nkv, hd), dtype))


def _grads(attn, q, k, v):
    """dq, dk, dv of a fixed random projection of ``attn``'s output."""
    ct = jax.random.normal(jax.random.PRNGKey(7), q.shape, jnp.float32)

    def f(q, k, v):
        return jnp.sum(attn(q, k, v).astype(jnp.float32) * ct)

    return jax.jit(jax.grad(f, argnums=(0, 1, 2)))(q, k, v)


def _close(got, want, tol):
    """Agreement to ``tol`` of the reference's largest magnitude."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    scale = max(1.0, float(np.abs(want).max()))
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol * scale)


@pytest.mark.parametrize("b,sq,nq,nkv,hd", FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(b, sq, nq, nkv, hd, dtype):
    q, k, v = _qkv(b, sq, nq, nkv, hd, dtype)
    out = flash_attention(q, k, v)
    ref = attention_ref(q, k, v)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("b,sq,nq,nkv,hd", FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_grad_sweep(b, sq, nq, nkv, hd, dtype):
    """dq, dk and dv of the kernels' backward against the oracle's."""
    q, k, v = _qkv(b, sq, nq, nkv, hd, dtype)
    got = _grads(flash_attention, q, k, v)
    want = _grads(attention_ref, q, k, v)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    for name, g, w in zip("qkv", got, want, strict=True):
        assert g.dtype == dtype, name
        _close(g, w, tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_scale(dtype):
    """``scale=None`` is the op as it was (scores by ``hd ** -0.5``, to
    the bit); ``scale=1/64`` at head 64 (granite-4.0-h's attention
    multiplier) agrees with the einsum path and the oracle at that scale,
    forward and backward."""
    from repro.models.layers import gqa_attention
    q, k, v = _qkv(1, 256, 32, 8, 64, dtype)
    assert bool((flash_attention(q, k, v, scale=None)
                 == flash_attention(q, k, v, scale=64 ** -0.5)).all())
    scaled = functools.partial(flash_attention, scale=1 / 64)
    oracle = functools.partial(attention_ref, scale=1 / 64)
    einsum = functools.partial(gqa_attention, scale=1 / 64)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    _close(scaled(q, k, v), oracle(q, k, v), tol)
    _close(einsum(q, k, v), oracle(q, k, v), tol)
    for g, w in zip(_grads(scaled, q, k, v), _grads(oracle, q, k, v),
                    strict=True):
        _close(g, w, tol)
    assert not np.allclose(np.asarray(scaled(q, k, v), np.float32),
                           np.asarray(flash_attention(q, k, v), np.float32),
                           atol=tol)


def test_flash_attention_under_worker_vmap():
    """Forward and gradients vmapped over a leading worker axis, as the
    DreamDDP step runs them, agree with the oracle worker by worker."""
    q, k, v = _qkv(1, 128, 8, 2, 64, jnp.float32, lead=(3,))
    out = jax.vmap(flash_attention)(q, k, v)
    _close(out, jax.vmap(attention_ref)(q, k, v), 2e-5)
    got = _grads(jax.vmap(flash_attention), q, k, v)
    want = _grads(jax.vmap(attention_ref), q, k, v)
    for g, w in zip(got, want, strict=True):
        _close(g, w, 2e-5)


def test_flash_attention_non_causal():
    k0 = jax.random.PRNGKey(0)
    q = jax.random.normal(k0, (1, 128, 2, 16))
    out = flash_attention(q, q, q, causal=False)
    ref = attention_ref(q, q, q, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_non_causal_ragged_hides_padding():
    """A ragged sequence without the causal mask: the padded keys stay
    out of every real query's softmax, forward and backward."""
    q, k, v = _qkv(2, 100, 4, 2, 32, jnp.float32)
    full = functools.partial(flash_attention, causal=False)
    full_ref = functools.partial(attention_ref, causal=False)
    _close(full(q, k, v), full_ref(q, k, v), 2e-5)
    for g, w in zip(_grads(full, q, k, v), _grads(full_ref, q, k, v),
                    strict=True):
        _close(g, w, 2e-5)


# ---------------------------------------------------------------------------
# paged attention
# ---------------------------------------------------------------------------

def _paged_case(seed, slots, nq, nkv, hd, ps, mb, dtype):
    """Random page pool + disjoint per-slot block tables + ragged
    lengths; page 0 is the (never-referenced-validly) trash page."""
    n_pages = 1 + slots * mb
    k0, k1, k2 = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(k0, (slots, nq, hd), dtype)
    kp = jax.random.normal(k1, (n_pages, ps, nkv, hd), dtype)
    vp = jax.random.normal(k2, (n_pages, ps, nkv, hd), dtype)
    rng = np.random.RandomState(seed)
    bt = rng.permutation(np.arange(1, n_pages)).reshape(slots, mb)
    # ragged valid lengths, incl. a page-boundary and a full-stream slot
    kv_len = rng.randint(1, mb * ps + 1, size=slots)
    kv_len[0] = ps
    kv_len[-1] = mb * ps
    # entries past the allocated blocks point at the trash page, like a
    # real block table (contents there must be masked out by kv_len)
    for s in range(slots):
        bt[s, -(-int(kv_len[s]) // ps):] = 0
    return (q, kp, vp, jnp.asarray(bt, jnp.int32),
            jnp.asarray(kv_len, jnp.int32))


@pytest.mark.parametrize("slots,nq,nkv,hd,ps,mb", [
    (3, 4, 2, 32, 8, 4),      # GQA
    (2, 4, 4, 16, 16, 2),     # MHA
    (4, 8, 1, 8, 8, 8),       # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_sweep(slots, nq, nkv, hd, ps, mb, dtype):
    q, kp, vp, bt, kv_len = _paged_case(slots * nq, slots, nq, nkv, hd,
                                        ps, mb, dtype)
    out = paged_attention(q, kp, vp, bt, kv_len, impl="interpret")
    ref = paged_attention_ref(q, kp, vp, bt, kv_len)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_paged_attention_windowed():
    q, kp, vp, bt, kv_len = _paged_case(7, 3, 4, 2, 16, 8, 4,
                                        jnp.float32)
    out = paged_attention(q, kp, vp, bt, kv_len, window=5,
                          impl="interpret")
    ref = paged_attention_ref(q, kp, vp, bt, kv_len, window=5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_paged_ref_matches_contiguous_oracle():
    """Gathering the pages back to a contiguous stream and running the
    flash oracle on the valid prefix must agree with the paged ref —
    the block-table indirection is pure storage layout."""
    slots, nq, nkv, hd, ps, mb = 2, 4, 2, 16, 8, 4
    q, kp, vp, bt, kv_len = _paged_case(11, slots, nq, nkv, hd, ps, mb,
                                        jnp.float32)
    out = paged_attention_ref(q, kp, vp, bt, kv_len)
    k = gather_pages(kp, bt)
    v = gather_pages(vp, bt)
    for s in range(slots):
        n = int(kv_len[s])
        ref = attention_ref(q[s:s + 1, None], k[s:s + 1, :n],
                            v[s:s + 1, :n], causal=False)
        np.testing.assert_allclose(np.asarray(out[s]),
                                   np.asarray(ref[0, 0]),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("slots,nq,nkv,hd,ps,mb", [
    (3, 4, 2, 32, 8, 4),
    (2, 4, 4, 16, 16, 2),
    (4, 8, 1, 8, 8, 8),
])
def test_paged_attention_page_skip_bitwise(slots, nq, nkv, hd, ps, mb):
    """Stopping the innermost page loop at ``ceil(kv_len / page_size)``
    must be BITWISE identical to scanning all ``max_blocks``: a fully
    masked page contributes alpha=1 / p=0 to the online softmax, so
    skipping it (compute + clamped-index DMA) changes nothing.  The
    ``_paged_case`` lengths are ragged and include single-page,
    page-boundary and full-stream slots."""
    from repro.kernels.paged_attention.kernel import paged_attention_fwd
    q, kp, vp, bt, kv_len = _paged_case(29 + slots, slots, nq, nkv, hd,
                                        ps, mb, jnp.float32)
    # sharpen the ragged edge: a one-token slot next to a full stream
    kv_len = kv_len.at[0].set(1)
    skip = paged_attention_fwd(q, kp, vp, bt, kv_len, skip_pages=True,
                               interpret=True)
    full = paged_attention_fwd(q, kp, vp, bt, kv_len, skip_pages=False,
                               interpret=True)
    np.testing.assert_array_equal(np.asarray(skip), np.asarray(full))
    # and the skipping kernel still matches the gather oracle
    ref = paged_attention_ref(q, kp, vp, bt, kv_len)
    np.testing.assert_allclose(np.asarray(skip), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_paged_attention_page_skip_windowed_bitwise():
    """Skip + sliding window compose: trailing pages are skipped, the
    window mask still clips the leading ones."""
    from repro.kernels.paged_attention.kernel import paged_attention_fwd
    q, kp, vp, bt, kv_len = _paged_case(7, 3, 4, 2, 16, 8, 4,
                                        jnp.float32)
    kw = dict(window=5, interpret=True)
    skip = paged_attention_fwd(q, kp, vp, bt, kv_len, skip_pages=True,
                               **kw)
    full = paged_attention_fwd(q, kp, vp, bt, kv_len, skip_pages=False,
                               **kw)
    np.testing.assert_array_equal(np.asarray(skip), np.asarray(full))


def test_paged_trash_page_contents_never_leak():
    """Poisoning the trash page (and every unreferenced page) with huge
    values must not change the output — masking happens before the
    softmax, not after."""
    q, kp, vp, bt, kv_len = _paged_case(13, 3, 4, 2, 16, 8, 4,
                                        jnp.float32)
    base = paged_attention(q, kp, vp, bt, kv_len, impl="ref")
    poisoned_k = kp.at[0].set(1e4)
    poisoned_v = vp.at[0].set(1e4)
    out = paged_attention(q, poisoned_k, poisoned_v, bt, kv_len,
                          impl="ref")
    np.testing.assert_array_equal(np.asarray(base), np.asarray(out))
    out_i = paged_attention(q, poisoned_k, poisoned_v, bt, kv_len,
                            impl="interpret")
    np.testing.assert_allclose(np.asarray(out_i), np.asarray(base),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# fused adamw
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(64,), (300, 17), (5, 33, 9)])
@pytest.mark.parametrize("pdtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("step", [0, 100])
def test_fused_adamw_sweep(shape, pdtype, step):
    k = jax.random.PRNGKey(42)
    p = jax.random.normal(k, shape, pdtype)
    g = jax.random.normal(jax.random.PRNGKey(1), shape, jnp.float32)
    m = jax.random.normal(jax.random.PRNGKey(2), shape, jnp.float32) * 0.1
    v = jnp.abs(jax.random.normal(jax.random.PRNGKey(3), shape,
                                  jnp.float32)) * 0.01
    got = fused_adamw_step(p, g, m, v, 1e-3, step, weight_decay=0.1)
    want = adamw_ref(p, g, m, v, lr=1e-3, step=step, weight_decay=0.1)
    for a, b in zip(got, want, strict=True):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# ssd chunk
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,NC,H,cs,p,n", [
    (1, 2, 2, 8, 8, 8),
    (2, 3, 4, 16, 8, 16),
    (1, 1, 8, 32, 16, 8),
])
def test_ssd_chunk_sweep(B, NC, H, cs, p, n):
    k = jax.random.PRNGKey(B * NC * H)
    x = jax.random.normal(k, (B, NC, H, cs, p))
    bb = jax.random.normal(jax.random.PRNGKey(1), (B, NC, H, cs, n))
    cc = jax.random.normal(jax.random.PRNGKey(2), (B, NC, H, cs, n))
    da = -jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(3),
                                            (B, NC, H, cs)))
    y, s = ssd_chunk(x, bb, cc, da)
    yr, sr = ssd_chunk_ref(x, bb, cc, da)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=2e-5,
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=2e-5,
                               atol=2e-5)


def test_ssd_chunk_matches_model_oracle():
    """Kernel intra-chunk part == models.mamba2.ssd_chunked with a single
    chunk and zero initial state."""
    from repro.models.mamba2 import ssd_chunked
    B, H, cs, p, n = 2, 4, 16, 8, 16
    k = jax.random.PRNGKey(7)
    x = jax.random.normal(k, (B, cs, H, p))
    dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(1),
                                           (B, cs, H)))
    a_log = jnp.log(jnp.linspace(1, 4, H))
    bmat = jax.random.normal(jax.random.PRNGKey(2), (B, cs, 1, n))
    cmat = jax.random.normal(jax.random.PRNGKey(3), (B, cs, 1, n))
    y_full, state = ssd_chunked(x, dt, a_log, bmat, cmat, chunk=cs)

    xdt = (x * dt[..., None]).reshape(B, 1, cs, H, p).swapaxes(2, 3)
    da = (dt * -jnp.exp(a_log)).reshape(B, 1, cs, H).swapaxes(2, 3)
    bq = jnp.repeat(bmat, H, 2).reshape(B, 1, cs, H, n).swapaxes(2, 3)
    cq = jnp.repeat(cmat, H, 2).reshape(B, 1, cs, H, n).swapaxes(2, 3)
    y_k, s_k = ssd_chunk(xdt, bq, cq, da)
    np.testing.assert_allclose(
        np.asarray(y_k[:, 0].swapaxes(1, 2)), np.asarray(y_full),
        rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        np.asarray(s_k[:, 0]), np.asarray(state), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# int8 quant
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("r,c", [(8, 16), (77, 33), (256, 128)])
@pytest.mark.parametrize("scale", [1e-3, 1.0, 100.0])
def test_int8_quant_sweep(r, c, scale):
    x = jax.random.normal(jax.random.PRNGKey(r * c), (r, c)) * scale
    q, s = quantize(x)
    qr, sr = quantize_rows_ref(x)
    # rounding ties may differ by 1 quantum on <0.1% of elements
    # (float associativity between the padded-kernel and ref paths)
    diff = np.abs(np.asarray(q, np.int32) - np.asarray(qr, np.int32))
    assert diff.max() <= 1 and (diff > 0).mean() < 1e-3
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)
    err = jnp.abs(dequantize(q, s) - x)
    assert float((err <= s * 0.5 + 1e-9).mean()) > 0.999
    assert float((err <= s * 0.51 + 1e-9).mean()) == 1.0


def test_int8_quant_zero_rows():
    x = jnp.zeros((4, 8))
    q, s = quantize(x)
    assert int(jnp.abs(q).max()) == 0
    np.testing.assert_allclose(np.asarray(dequantize(q, s)), 0.0)
