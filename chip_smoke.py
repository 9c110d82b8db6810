#!/usr/bin/env python3
"""Smoke run of the system's main paths on a TPU, at published widths.

    python chip_smoke.py              # one chip: train, then serve
    python chip_smoke.py --chips 4    # the worker axis over four chips,
                                      # against the same job on one chip

One chip (the default):

* **train** — DreamDDP partial-sync training of granite-3-2b at every
  published width (d_model 2048, 32/8 heads, head_dim 64, d_ff 8192,
  vocab 49155), cut to ``TRAIN_LAYERS`` layers, through
  ``Session(JobConfig(...)).fit()`` for whole periods, so the fused
  ``pipeline`` executor runs every phase program.  Checks: finite losses,
  a final loss below the first, and a plan that syncs in more than one
  phase.
* **serve** — the full 40-layer granite-3-2b (random bf16 weights) behind
  ``Session.serve()``: a ``ServeEngine`` with the paged KV backend answers
  8 seeded greedy requests (prompts of 128-1024 tokens, 32-64 new tokens)
  twice, cold then warm.  Checks: every request finishes, the warm run
  repeats the cold run's tokens, the decode program holds the Pallas
  kernel (``tpu_custom_call``), and on the live page pool the kernel
  agrees with the gather reference within ``ATTN_TOL``.

``--chips 4`` runs only the W=4 train job with its worker axis spread over
the four chips, then the same job pinned to one chip.  Checks: every
per-step loss agrees within ``LOSS_RTOL``, and each syncing phase program
holds an ``all-reduce``.

Every number printed is a smoke figure from one run, not a benchmark
result.  Any failed check raises, so the script exits nonzero; it also
exits nonzero, before printing any result, when JAX finds no TPU.  The
last line of standard output is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import re
import sys
import time
from dataclasses import replace
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

# Train-phase shape.  Depth is the largest whose phase programs keep 15%
# of the 16 GiB chip free by the compiler's own memory analysis for a
# described v5e (W=2, B=1, S=1024): L=4 11.56 GB, L=5 13.57 GB, L=6
# 14.71 GB (> 0.85 x 17.18 GB).
TRAIN_LAYERS = 5
WORKERS = 2
PERIOD = 2                 # H: two distinct partial-sync phase programs
SEQ = 1024
PERIODS = 4
# --chips 4: W=4 must also fit ONE chip for the comparison run (L=2:
# 13.24 GB by the same analysis; L=3 does not keep the margin)
MULTI_LAYERS = 2
MULTI_WORKERS = 4

# bf16 params carry 8 significant bits (relative step 2^-8 = 0.39%).
# Spreading the worker axis changes how XLA partitions and fuses the
# step, which may re-round a bf16 parameter by one step; a few such steps
# cannot move a mean loss by more than that relative amount.
LOSS_RTOL = 2.0 ** -8

# Kernel vs reference on bf16 pages: both outputs are rounded to bf16
# (2^-9 relative each) and the reference also rounds the softmax weights
# to bf16 before the value matmul (at most 2^-9 * max|v| on an output,
# a convex combination of v rows).  The bound is 3 * 2^-9 * max|v|;
# the tolerance is 2^-6 * max|v|, leaving room for summation order.
ATTN_TOL = 2.0 ** -6

# an all-reduce whose result has dimensions: a parameter sync, not the
# scalar loss mean
SYNC_ALL_REDUCE = re.compile(r"= \(?[a-z]\w*\[\d.*all-reduce")

SERVE_REQUESTS = 8
PAGE_SIZE = 16
MIN_PROMPT, MAX_PROMPT = 128, 1024
MIN_NEW, MAX_NEW = 32, 64
PREFILL_CHUNK = 512


def _fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def _check(ok: bool, msg: str) -> None:
    if not ok:
        _fail(msg)


class CompileClock:
    """Sums JAX's backend-compile durations (persistent-cache reads
    included) so each phase can report its own compile seconds."""

    def __init__(self, jax):
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.total += secs


def train_job(*, layers: int, workers: int, clock: CompileClock,
              programs: bool = False) -> tuple[list[float], dict, list]:
    """Fit granite-3-2b (published widths, ``layers`` deep) for PERIODS
    whole periods; returns (per-step losses, facts, and with
    ``programs`` the compiled text of each syncing phase's step)."""
    from repro.api import JobConfig, Session
    from repro.configs.granite_3_2b import CONFIG
    from repro.models.transformer import DecoderLM
    from repro.parallel.sharding import place_worker_axis

    model = DecoderLM(replace(CONFIG, n_layers=layers))
    sess = Session(JobConfig(
        arch="granite-3-2b", algo="dreamddp", smoke=False, workers=workers,
        period=PERIOD, seq=SEQ, batch_per_worker=1, warmup_steps=2,
        seed=0), model=model)
    plan = sess.plan
    counts = plan.meta["partition_counts"]
    _check(sum(1 for c in counts if c) > 1,
           f"DreamDDP plan syncs in one phase only: {counts}")

    c0 = clock.total
    sess.fit(PERIOD * PERIODS)
    compile_s = clock.total - c0
    losses = [row["loss"] for row in sess.history]
    period_s = list(sess.runner.period_times)

    texts = []
    if programs:
        # each syncing phase's step the runner dispatched, compiled for
        # the live state's placement
        runner = sess.runner
        batch = place_worker_axis(runner.data.batch(0))
        texts = [runner._steps[h].lower(sess.state, batch).compile()
                 .as_text() for h in range(PERIOD)
                 if plan.units_for_phase(h)]
        del runner, batch
    facts = {"L": layers, "W": workers, "H": PERIOD, "seq": SEQ,
             "batch_per_worker": 1, "partition_counts": counts,
             "compile_s": compile_s, "period_s_cold": period_s[0],
             "period_s_warm": period_s[1:]}
    del sess
    gc.collect()
    return losses, facts, texts


def check_losses(losses: list[float]) -> None:
    _check(all(math.isfinite(x) for x in losses),
           f"non-finite training loss: {losses}")
    _check(losses[-1] < losses[0],
           f"loss did not fall: first {losses[0]} last {losses[-1]}")


def serve_phase(jax, clock: CompileClock) -> dict:
    """Full-depth granite-3-2b behind the paged engine; cold then warm."""
    import jax.numpy as jnp
    import numpy as np

    from repro.api import JobConfig, Session
    from repro.kernels.paged_attention import paged_attention
    from repro.serve import EngineConfig, Request

    rng = np.random.default_rng(0)
    lens = rng.integers(MIN_PROMPT, MAX_PROMPT + 1, SERVE_REQUESTS)
    gens = rng.integers(MIN_NEW, MAX_NEW + 1, SERVE_REQUESTS)

    sess = Session(JobConfig(arch="granite-3-2b", smoke=False, seed=0))
    vocab = sess.model.cfg.vocab
    prompts = [rng.integers(0, vocab, n).tolist() for n in lens]
    # prompts pad to PREFILL_CHUNK multiples: prefill compiles per bucket
    cfg = EngineConfig(max_batch=SERVE_REQUESTS,
                       max_seq=MAX_PROMPT + MAX_NEW, kv_backend="paged",
                       page_size=PAGE_SIZE, prefill_chunk=PREFILL_CHUNK)
    c0 = clock.total
    t0 = time.perf_counter()
    engine = sess.serve(config=cfg)
    build_s = time.perf_counter() - t0

    def run():
        reqs = [Request(tokens=p, max_new_tokens=int(g))
                for p, g in zip(prompts, gens, strict=True)]
        t = time.perf_counter()
        comps = engine.generate(reqs)
        return comps, time.perf_counter() - t

    cold, cold_s = run()
    compile_s = clock.total - c0
    for c, g in zip(cold, gens, strict=True):
        _check(c.finish_reason in ("length", "stop"),
               f"request {c.request_id} finished {c.finish_reason!r}")
        _check(c.finish_reason == "stop" or len(c.tokens) == g,
               f"request {c.request_id}: {len(c.tokens)} of {g} tokens")
    engine.reset()
    warm, warm_s = run()
    stats = engine.stats
    _check([c.tokens for c in warm] == [c.tokens for c in cold],
           "warm run's greedy tokens differ from the cold run's")

    # the decode program the engine dispatched holds the Pallas kernel
    decode_text = engine._decode_block.lower(
        engine.params, engine.pool.arena, engine._state,
        engine.pool.device_block_tables()).as_text()
    _check("tpu_custom_call" in decode_text,
           "decode program has no tpu_custom_call: the paged-attention "
           "kernel did not run")

    # kernel vs reference on the live page pool: tables over the pages
    # the run filled (handed out from page 1 up), one slot per request
    # length, pages drawn at random (slots may share pages)
    pool = engine.pool
    k_pages = pool.arena["blocks"]["k"][-1]
    v_pages = pool.arena["blocks"]["v"][-1]
    used = np.arange(1, pool.peak_pages_in_use + 1)
    kv_len = lens + gens - 1               # positions the run wrote
    bt = np.zeros((SERVE_REQUESTS, pool.max_blocks), np.int32)
    for b, n in enumerate(kv_len):
        need = -(-int(n) // PAGE_SIZE)
        bt[b, :need] = rng.choice(used, need)
    cfg_m = sess.model.cfg
    q = jax.random.normal(jax.random.PRNGKey(1),
                          (SERVE_REQUESTS, cfg_m.n_heads, cfg_m.hd),
                          jnp.bfloat16)
    args = (q, k_pages, v_pages, jnp.asarray(bt), jnp.asarray(kv_len))
    out_k = paged_attention(*args, impl="pallas")
    out_r = paged_attention(*args, impl="ref")
    err = float(jnp.max(jnp.abs(out_k.astype(jnp.float32)
                                - out_r.astype(jnp.float32))))
    vmax = float(jnp.max(jnp.abs(v_pages[used].astype(jnp.float32))))
    _check(err <= ATTN_TOL * vmax,
           f"pallas vs ref max abs error {err} > {ATTN_TOL} * {vmax}")

    facts = {"layers": cfg_m.n_layers, "requests": SERVE_REQUESTS,
             "prompt_lens": lens.tolist(), "new_tokens": gens.tolist(),
             "peak_pages_in_use": pool.peak_pages_in_use,
             "build_s": build_s, "compile_s": compile_s, "cold_s": cold_s,
             "warm_s": warm_s, "warm_decode_tokens": stats.decode_tokens,
             "warm_decode_s": stats.decode_time_s,
             "warm_decode_tokens_per_s": stats.decode_tokens_per_s,
             "attn_max_abs_err": err, "attn_tol": ATTN_TOL * vmax}
    del engine, sess, pool, k_pages, v_pages, args
    gc.collect()
    return facts


def peak_bytes(jax) -> list[int]:
    return [d.memory_stats()["peak_bytes_in_use"] for d in jax.devices()]


def report(name: str, facts: dict) -> None:
    print(f"[smoke figure, not a benchmark] {name}: "
          + json.dumps(facts, sort_keys=True), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        _fail(f"no repro package under {SRC}: run from a checkout")
    sys.path.insert(0, str(SRC))

    import jax

    if jax.default_backend() != "tpu":
        _fail(f"JAX found no TPU (backend {jax.default_backend()!r})")
    devices = jax.devices()
    _check(len(devices) >= args.chips,
           f"--chips {args.chips} but {len(devices)} device(s)")

    from repro.launch.compile_cache import use_compile_cache
    print(f"compile cache: {use_compile_cache()}", flush=True)
    clock = CompileClock(jax)
    kind = devices[0].device_kind
    print(f"device_kind={kind} platform={devices[0].platform} "
          f"count={len(devices)}", flush=True)

    if args.chips == 1:
        losses, facts, _ = train_job(layers=TRAIN_LAYERS,
                                     workers=WORKERS, clock=clock)
        check_losses(losses)
        facts.update(losses=losses, peak_bytes_in_use=peak_bytes(jax)[0])
        report("train", facts)
        facts = serve_phase(jax, clock)
        facts["peak_bytes_in_use"] = peak_bytes(jax)[0]
        report("serve", facts)
    else:
        spread, facts4, texts = train_job(
            layers=MULTI_LAYERS, workers=MULTI_WORKERS, clock=clock,
            programs=True)
        check_losses(spread)
        for i, text in enumerate(texts):
            _check(any(SYNC_ALL_REDUCE.search(line)
                       for line in text.splitlines()),
                   f"syncing phase program {i} has no parameter-sized "
                   "all-reduce")
        facts4.update(losses=spread, peak_bytes_in_use=peak_bytes(jax),
                      all_reduce_phases=len(texts))
        report("train over 4 chips", facts4)
        with jax.default_device(devices[0]):
            single, facts1, _ = train_job(
                layers=MULTI_LAYERS, workers=MULTI_WORKERS,
                clock=clock)
        facts1.update(losses=single)
        report("same train job on 1 chip", facts1)
        worst = max(abs(a - b) / abs(b)
                    for a, b in zip(spread, single, strict=True))
        report("4 vs 1 chip", {"max_rel_loss_diff": worst,
                               "loss_rtol": LOSS_RTOL})
        _check(worst <= LOSS_RTOL,
               f"4-chip and 1-chip losses differ by {worst} > {LOSS_RTOL}")

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
