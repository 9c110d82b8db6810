"""Jitted public wrapper: Pallas on TPU, vectorized-XLA gather elsewhere.

Unlike the training-side kernels, paged attention sits on the serving hot
path, so the non-TPU fallback is the **ref** implementation (one fused
gather + einsum program), not interpret mode: Pallas interpret executes
the ``slots x max_blocks`` grid as a Python-level loop, which
is fine for parity sweeps but orders of magnitude too slow for a decode
tick.  The kernel-vs-ref parity tests pass ``impl="interpret"``
explicitly.
"""

from __future__ import annotations

import functools

import jax

from .kernel import paged_attention_fwd
from .ref import paged_attention_ref

__all__ = ["paged_attention"]


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("window", "impl",
                                             "skip_pages", "scale"))
def paged_attention(q, k_pages, v_pages, block_tables, kv_len, *,
                    window: int | None = None, impl: str | None = None,
                    skip_pages: bool = True, scale: float | None = None):
    """Paged-KV single-token decode attention.

    q ``[slots, n_q, hd]``, k/v pages ``[n_pages, page_size, n_kv, hd]``,
    ``block_tables [slots, max_blocks]``, ``kv_len [slots]``.  ``impl``:
    ``None`` (auto: Mosaic kernel on TPU, ref elsewhere), ``"pallas"``,
    ``"interpret"`` (kernel body under the Pallas interpreter, for parity
    tests), or ``"ref"``.  ``skip_pages`` (kernel impls only) stops each
    slot's page loop at ``ceil(kv_len / page_size)`` pages — bitwise-
    equal output, less page traffic; the ref path always gathers exactly
    the table's pages.  Scores are scaled by ``scale`` (``hd ** -0.5``
    when None).
    """
    if impl is None:
        impl = "pallas" if _on_tpu() else "ref"
    if impl == "ref":
        return paged_attention_ref(q, k_pages, v_pages, block_tables,
                                   kv_len, window=window, scale=scale)
    if impl not in ("pallas", "interpret"):
        raise ValueError(f"unknown paged_attention impl {impl!r}")
    return paged_attention_fwd(q, k_pages, v_pages, block_tables, kv_len,
                               window=window, skip_pages=skip_pages,
                               interpret=impl == "interpret", scale=scale)
