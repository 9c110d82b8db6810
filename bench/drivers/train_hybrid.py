"""Hybrid training traffic: one DreamDDP job through ``Session.fit`` on a
decoder whose layers are Mamba-2 or attention mixers in a published
order (``layer_types``).

The job, the window and the readings for ``correct`` are those of
:mod:`bench.drivers.train`; what differs is the model: the program's
``DecoderLM`` built with its hybrid layers, NoPE attention and Granite's
scales, and the plain reference :mod:`bench.reference.hybrid`, whose
state-space layer is the step-by-step recurrence.

A traced run also splits the traced period's device time by the phase
step's named scopes: each device op is joined with its phase program's
compiled HLO, and ``record["scope_ms"]`` holds device ms a step under
``ssd`` and ``mamba_mixer`` and in each of :data:`bench.scopes.BUCKETS`.
"""

from __future__ import annotations

import gc
import math
import re
import time

import jax

from bench import scopes
from bench import trace as tr
from bench.drivers.train import (TokenRows, _readings, check_optimizer,
                                 compare, phases_of, program_readings)
from bench.harness import NoResult, Outcome, SeededModel
from bench.reference import hybrid, weights

# a Mosaic custom call's metadata runs over several lines of HLO text
_CONTINUED = re.compile(r'\n(?=")|\n(?=\}\},)')
_MODULE = re.compile(r"HloModule ([\w.\-]+)")
NAMED = ("ssd", "mamba_mixer")


def lm_config(cfg: dict):
    """The program's hybrid ``LMConfig`` for a configuration file,
    refusing numbers the program or the reference cannot run as stated."""
    try:
        from repro.models.mamba2 import MixerConfig
    except ImportError as e:
        raise NoResult(f"the program has no Mamba-2 mixer for DecoderLM "
                       f"({e})") from None
    from repro.models.transformer import LMConfig
    for key, want in (("hidden_act", "silu"), ("attention_bias", False),
                      ("mamba_proj_bias", False), ("mamba_conv_bias", True),
                      ("position_embedding_type", "nope"),
                      ("normalization_function", "rmsnorm")):
        if cfg[key] != want:
            raise NoResult(f"{cfg['name']}: {key}={cfg[key]!r}; the "
                           f"program and the reference run {want!r}")
    return LMConfig(
        name=cfg["name"], n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        head_dim=cfg["head_dim"], mlp_kind="swiglu", norm_kind="rmsnorm",
        tie_embeddings=cfg["tie_word_embeddings"],
        param_dtype=cfg["torch_dtype"], norm_eps=cfg["rms_norm_eps"],
        rope=False,
        embedding_multiplier=float(cfg["embedding_multiplier"]),
        residual_multiplier=float(cfg["residual_multiplier"]),
        logits_scaling=float(cfg["logits_scaling"]),
        attention_multiplier=float(cfg["attention_multiplier"]),
        layer_types=tuple(hybrid.kinds(cfg)),
        mamba=MixerConfig(
            d_model=cfg["hidden_size"], d_state=cfg["mamba_d_state"],
            head_dim=cfg["mamba_d_head"], expand=cfg["mamba_expand"],
            n_groups=cfg["mamba_n_groups"], conv_width=cfg["mamba_d_conv"],
            chunk=cfg["mamba_chunk_size"], norm_eps=cfg["rms_norm_eps"]))


def program_model(cfg: dict, seed: int):
    """(program model with the seeded weights of
    :mod:`bench.reference.hybrid`, weight maker)."""
    from repro.models.transformer import DecoderLM

    inner = DecoderLM(lm_config(cfg))

    def make():
        return hybrid.make(cfg, weights.seed_key(seed, 0))

    want = jax.eval_shape(inner.init, jax.random.PRNGKey(0))
    have = jax.eval_shape(make)
    same = jax.tree.structure(want) == jax.tree.structure(have) and all(
        (a.shape, a.dtype) == (b.shape, b.dtype) for a, b in zip(
            jax.tree.leaves(want), jax.tree.leaves(have), strict=True))
    if not same:
        raise NoResult("the program's parameter tree differs from the "
                       "benchmark's hybrid weight layout")
    return SeededModel(inner, make), make


def build(cfg: dict, job: dict, seed: int):
    """The session, the program's model, the weight maker and the data."""
    from repro.api import JobConfig, Session

    model, make = program_model(cfg, seed)
    data = TokenRows(cfg["vocab_size"], job["seq"], job["batch_per_worker"],
                     job["workers"], seed)
    opt = job["optimizer"]
    sess = Session(JobConfig(
        arch=cfg["name"], algo=job["algo"], smoke=False,
        workers=job["workers"], period=job["period"], seq=job["seq"],
        batch_per_worker=job["batch_per_worker"],
        bandwidth=job["bandwidth"], period_exec=job["period_exec"],
        optimizer=opt["name"], lr=opt["lr"],
        warmup_steps=opt["warmup_steps"], decay_steps=opt["decay_steps"],
        seed=0), model=model, data=data)
    return sess, model, make, data


def reference_readings(cfg, job, seed, phases, *, chips=1, prec="f32",
                       keep=1.0, sync=True, raw_norms=None):
    """:func:`bench.drivers.train.reference_readings` of the hybrid
    reference."""
    data = TokenRows(cfg["vocab_size"], job["seq"], job["batch_per_worker"],
                     job["workers"], seed)
    batches = [data.batch(t)["tokens"] for t in range(len(phases))]
    losses, stack, m, v, raw = hybrid.adam_steps(
        cfg, job["optimizer"], hybrid.make(cfg, weights.seed_key(seed, 0)),
        batches, phases, chips=chips, prec=prec, keep=keep, sync=sync)
    out = _readings(losses, stack, m, v,
                    hybrid.make(cfg, weights.seed_key(seed, 0)))
    del stack, m, v
    if raw_norms is not None:
        raw_norms.extend(raw)
    return out


def readings(cell, seed: int) -> dict:
    """What ``bench/calibrate.py`` gives a ``train`` cell, for this
    kind: the program's numbers, the fp8 control's and half a batch's,
    each against the float32 reference."""
    cfg, job = cell.config, cell.traffic
    sess, model, make, _ = build(cfg, job, seed)
    prog = program_readings(sess, make, job)
    phases = phases_of(sess.plan, model, job["period"])
    del sess, model
    gc.collect()

    def reference(**kw):
        return reference_readings(cfg, job, seed, phases, chips=cell.chips,
                                  **kw)

    ref = reference()
    return {"program": compare(prog, ref),
            "control": compare(reference(prec="fp8"), ref),
            "half_batch": compare(reference(keep=0.5), ref)}


def phase_texts(sess, rows) -> dict[str, str]:
    """``{module name: compiled HLO text}`` of the session's phase
    programs (persistent-cache hits), each custom call's metadata joined
    onto one line."""
    out = {}
    for step in sess.runner._steps:
        text = _CONTINUED.sub(" ", step.lower(sess.state, rows).compile()
                              .as_text())
        out[_MODULE.search(text).group(1)] = text
    return out


def scope_ms(events: list[tr.Event], texts: dict[str, str],
             steps: int) -> dict[str, float]:
    """Device ms a step of the traced period (its ``bench.period``
    span) on the first device: under each of :data:`NAMED` scopes, and
    in each of :data:`bench.scopes.BUCKETS`."""
    ops = tr.device_ops(events)
    dev = min(ops)
    plane = ops[dev][0].plane
    modules = [e for e in events
               if e.plane == plane and e.line == scopes.MODULES_LINE]
    marks = [s for s in tr.host_spans(events, "bench.")
             if s.name == "bench.period"]
    lo, hi = marks[0].start_ns, marks[-1].end_ns
    scoped = scopes.op_scopes(ops[dev], modules, texts)
    out = {k: v / 1e6 / steps
           for k, v in scopes.scope_time(scoped, lo, hi).items()}
    for name in NAMED:
        rx = re.compile(rf"(^|/){name}(/|$)")
        mine = [o for o, path in scoped
                if rx.search(path.split(";")[0])
                and tr.op_name(o.name)[1] not in tr.CONTAINERS]
        out[name] = tr.busy_ns(mine, lo, hi) / 1e6 / steps
    return out


def run(ctx) -> Outcome:
    cfg, job, seed = ctx.cell.config, ctx.cell.traffic, ctx.seed
    sess, model, make, data = build(cfg, job, seed)
    plan = sess.plan
    check_optimizer(sess, job["optimizer"])
    prog = program_readings(sess, make, job)
    h = job["period"]
    t = time.perf_counter()
    sess.fit(h)                         # warm period, sizes the window
    periods = max(2, math.ceil(ctx.seconds / (time.perf_counter() - t)))
    phases = phases_of(plan, model, h)

    # the window, as bench/drivers/train.py runs it
    ctx.window_opens()
    traced = None
    t0 = time.perf_counter()
    if ctx.trace:
        with jax.profiler.TraceAnnotation("bench.period"):
            sess.fit(h)
        ctx.start_trace()
        with jax.profiler.TraceAnnotation("bench.period"):
            sess.fit(h)
        traced = ctx.stop_trace()
        if periods > 2:
            sess.fit((periods - 2) * h)
    else:
        sess.fit(periods * h)
    window = time.perf_counter() - t0
    compiles = ctx.window_closes()
    tokens = periods * h * job["workers"] * job["batch_per_worker"] \
        * job["seq"]
    peak = ctx.peak_memory()
    period_times = list(sess.runner.period_times[-periods:])
    record = {"window_s": window, "tokens": tokens, "periods": periods,
              "period": h, "workers": job["workers"], "seq": job["seq"],
              "batch_per_worker": job["batch_per_worker"],
              "chips": ctx.cell.chips, "period_times": period_times,
              "compiles_in_window": compiles}
    if traced is not None:
        record["scope_ms"] = scope_ms(tr.load(tr.find_xplane(traced)),
                                      phase_texts(sess, data.batch(0)), h)
    del sess, model
    gc.collect()

    raw: list = []
    t1 = time.perf_counter()
    ref = reference_readings(cfg, job, seed, phases, chips=ctx.cell.chips,
                             raw_norms=raw)
    ref_s = time.perf_counter() - t1
    compared = compare(prog, ref)
    notes = {"losses": prog[0], "ref_losses": ref[0], "reference_s": ref_s,
             "ref_grad_norms_before_clip": raw,
             "partition_counts": plan.meta.get("partition_counts"),
             "scope_ms": record.get("scope_ms")}
    return Outcome(
        attempted=tokens, failed=0,
        e2e={"train_tokens_per_s": tokens / window},
        record=record, compared={k: (v, ctx.limit(k))
                                 for k, v in compared.items()},
        memory_peak_bytes=peak, trace=traced, notes=notes)
