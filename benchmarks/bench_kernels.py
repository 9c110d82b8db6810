"""Kernel benchmarks: correctness deltas + v5e roofline projections.

Interpret-mode wall time on CPU is NOT kernel performance; what we report
per kernel is (a) max abs error vs the jnp oracle, (b) the HBM bytes each
implementation moves, and (c) the projected v5e time at 819 GB/s — the
quantity the fusion actually improves.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_attention import attention_ref, flash_attention
from repro.kernels.fused_adam_sync import adamw_ref, fused_adamw_step
from repro.kernels.int8_quant import dequantize, quantize
from repro.kernels.ssd_scan import ssd_chunk, ssd_chunk_ref

_HBM = 819e9


def _err(a, b):
    return float(jnp.max(jnp.abs(jnp.asarray(a, jnp.float32)
                                 - jnp.asarray(b, jnp.float32))))


def run(csv: bool = True) -> list[dict]:
    rows = []
    # flash attention: bytes ~ q+k+v+o (flash) vs + score map (naive)
    b, s, nq, nkv, hd = 1, 512, 8, 2, 64
    q = jax.random.normal(jax.random.PRNGKey(0), (b, s, nq, hd),
                          jnp.bfloat16)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, nkv, hd),
                          jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, nkv, hd),
                          jnp.bfloat16)
    out = flash_attention(q, k, v)
    ref = attention_ref(q, k, v)
    io = (q.size + 2 * k.size + out.size) * 2
    naive = io + b * nq * s * s * 4 * 2          # fp32 scores r+w
    rows.append({"kernel": "flash_attention", "max_err": _err(out, ref),
                 "hbm_bytes": io, "naive_bytes": naive,
                 "v5e_us": io / _HBM * 1e6,
                 "v5e_us_naive": naive / _HBM * 1e6})

    # fused adamw: 7 passes vs ~13 unfused (p,g,m,v r/w + casts)
    n = 1 << 20
    p = jax.random.normal(jax.random.PRNGKey(3), (n,), jnp.bfloat16)
    g = jax.random.normal(jax.random.PRNGKey(4), (n,), jnp.float32)
    m = jnp.zeros((n,), jnp.float32)
    vv = jnp.zeros((n,), jnp.float32)
    got = fused_adamw_step(p, g, m, vv, 1e-3, 0)
    want = adamw_ref(p, g, m, vv, lr=1e-3, step=0)
    fused_bytes = n * (2 + 4 * 3) + n * (2 + 4 * 2)
    unfused_bytes = fused_bytes + n * 4 * 6      # extra temps materialized
    rows.append({"kernel": "fused_adam_sync",
                 "max_err": max(_err(a, b) for a, b in zip(got, want, strict=True)),
                 "hbm_bytes": fused_bytes, "naive_bytes": unfused_bytes,
                 "v5e_us": fused_bytes / _HBM * 1e6,
                 "v5e_us_naive": unfused_bytes / _HBM * 1e6})

    # ssd chunk
    B, NC, Hh, cs, pp, nn = 1, 4, 8, 64, 64, 128
    x = jax.random.normal(jax.random.PRNGKey(5), (B, NC, Hh, cs, pp))
    bb = jax.random.normal(jax.random.PRNGKey(6), (B, NC, Hh, cs, nn))
    cc = jax.random.normal(jax.random.PRNGKey(7), (B, NC, Hh, cs, nn))
    da = -jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(8),
                                            (B, NC, Hh, cs)))
    y, st = ssd_chunk(x, bb, cc, da)
    yr, sr = ssd_chunk_ref(x, bb, cc, da)
    io = (x.size + bb.size + cc.size + y.size) * 4 + st.size * 4
    naive = io + B * NC * Hh * cs * cs * 4 * 2   # L matrix materialized
    rows.append({"kernel": "ssd_scan",
                 "max_err": max(_err(y, yr), _err(st, sr)),
                 "hbm_bytes": io, "naive_bytes": naive,
                 "v5e_us": io / _HBM * 1e6,
                 "v5e_us_naive": naive / _HBM * 1e6})

    # int8 quant: wire bytes halve vs bf16
    r, c = 4096, 1024
    xq = jax.random.normal(jax.random.PRNGKey(9), (r, c))
    qq, ss = quantize(xq)
    deq = dequantize(qq, ss)
    rows.append({"kernel": "int8_quant",
                 "max_err": float(jnp.abs(deq - xq).max()),
                 "hbm_bytes": r * c * (4 + 1) + r * 4,
                 "naive_bytes": r * c * 8,
                 "v5e_us": r * c * 5 / _HBM * 1e6,
                 "v5e_us_naive": r * c * 8 / _HBM * 1e6})

    if csv:
        keys = list(rows[0])
        print(",".join(keys))
        for rr in rows:
            print(",".join(f"{rr[k]:.4g}" if isinstance(rr[k], float)
                           else str(rr[k]) for k in keys))
    return rows


if __name__ == "__main__":
    run()
