"""Training traffic: one DreamDDP job through ``Session.fit``.

Set-up builds ONE session (compiled phase steps and their state) from the
seeded weights and drives it through its first whole period with the
window's own call, ``fit`` over a whole period: the fused executor, its
prefetched rows and every phase program of the plan.  The readings for
``correct`` are taken from the state that period leaves.  One more
period, timed, sizes the window; the same session then trains whole
periods for the window.  Afterwards the session is freed and the plain
reference repeats the first period from the same seeded weights and
rows, its worker stack spread over the cell's chips as the program's is.
"""

from __future__ import annotations

import gc
import math
import time

import jax
import jax.numpy as jnp

from bench.harness import Outcome, program_model
from bench.reference import decoder, weights

F32 = jnp.float32


class TokenRows:
    """Seeded training rows ``{tokens, labels}: [W, B, S]``, a different
    draw for every step, worker and row (uniform over the vocabulary)."""

    def __init__(self, vocab: int, seq: int, batch: int, workers: int,
                 seed: int):
        self._key = weights.seed_key(seed, 1)
        shape = (workers, batch, seq)

        def build(step):
            toks = jax.random.randint(jax.random.fold_in(self._key, step),
                                      shape, 0, vocab, jnp.int32)
            return {"tokens": toks, "labels": toks}

        self._build = jax.jit(build)

    def batch(self, step: int) -> dict:
        return self._build(jnp.asarray(step, jnp.int32))


def _gap(prog: dict, ref: dict, keys) -> float:
    """Worst leaf's gap of norms, over the larger of that leaf's
    reference norm and the median leaf's."""
    med = sorted(ref.values())[len(ref) // 2]
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keys)


@jax.jit
def _state_norms(p, m, v, p0):
    """Per-leaf norms of Adam's moments and of the parameters' change."""
    return {"m": decoder.leaf_norms(m), "v": decoder.leaf_norms(v),
            "change": decoder.leaf_norms(jax.tree.map(
                lambda a, b: a.astype(F32) - b.astype(F32), p, p0))}


def _readings(losses, p, m, v, p0):
    """``(losses, {"m" | "v" | "change": {leaf: norm}})`` on the host."""
    norms = jax.device_get(_state_norms(p, m, v, p0))
    return list(losses), {k: {leaf: float(x) for leaf, x in d.items()}
                          for k, d in norms.items()}


def phases_of(plan, model, steps: int) -> list[list]:
    """Per step, the ``(group, layer)`` units its phase averages, by the
    unit layout's names (``embed``, ``layer_<i>``, ``head``)."""
    entries = model.unit_layout().entries
    out = []
    for t in range(steps):
        units = []
        for u in plan.units_for_phase(plan.phase_of_iteration(t)):
            e = entries[u]
            units.append((e.group, e.index))
        out.append(units)
    return out


def reference_readings(cfg, job, seed, phases, *, chips=1, prec="f32",
                       keep=1.0, sync=True, raw_norms=None):
    """The reference's readings after ``len(phases)`` steps, as
    :func:`program_readings` gives the program's; each step's per-worker
    gradient norms before the clip are appended to ``raw_norms`` when
    given."""
    data = TokenRows(cfg["vocab_size"], job["seq"], job["batch_per_worker"],
                     job["workers"], seed)
    batches = [data.batch(t)["tokens"] for t in range(len(phases))]
    losses, stack, m, v, raw = decoder.adam_steps(
        cfg, job["optimizer"], weights.make(cfg, weights.seed_key(seed, 0)),
        batches, phases, chips=chips, prec=prec, keep=keep, sync=sync)
    out = _readings(losses, stack, m, v,
                    weights.make(cfg, weights.seed_key(seed, 0)))
    del stack, m, v
    if raw_norms is not None:
        raw_norms.extend(raw)
    return out


def compare(prog, ref) -> dict[str, float]:
    """The numbers ``correct`` holds to a limit: the worst leaf's gap of
    the norms of Adam's moments and of the parameters' change.  Leaves
    whose reference first moment is under 1e-3 of the median leaf's are
    left out of the change (round-off alone moves them under Adam).
    The per-step losses are reported beside them, not compared: no
    control or fault separates their gap from sound runs' after a
    period."""
    (_, pn), (_, rn) = prog, ref
    rm = rn["m"]
    med = sorted(rm.values())[len(rm) // 2]
    moving = [k for k in rn["change"] if rm[k] >= 1e-3 * med]
    return {
        "m_gap": _gap(pn["m"], rm, rm.keys()),
        "v_gap": _gap(pn["v"], rn["v"], rn["v"].keys()),
        "update_gap": _gap(pn["change"], rn["change"], moving),
    }


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


def check_optimizer(sess, opt: dict) -> None:
    ocfg = sess.runner.optimizer.cfg
    for key in ("beta1", "beta2", "eps", "grad_clip", "min_lr_ratio",
                "weight_decay"):
        if abs(getattr(ocfg, key) - opt[key]) > 1e-15:
            raise SystemExit(f"bench: the program's {key}="
                             f"{getattr(ocfg, key)}, the job states "
                             f"{opt[key]}")


def program_readings(sess, make, job):
    """Drive the fresh session through its first whole period with the
    window's own call and read the period's losses and, from the state
    it leaves, the norms of Adam's moments and of the parameters'
    change."""
    h = job["period"]
    sess.fit(h)
    if len(sess.runner.period_times) != 1 or len(sess.history) != h:
        raise SystemExit("bench: the first period did not run as one "
                         "fused period")
    st = sess.state
    return _readings([row["loss"] for row in sess.history], st.params,
                     st.opt_state["m"], st.opt_state["v"], make())


def run(ctx) -> Outcome:
    cfg, job, seed = ctx.cell.config, ctx.cell.traffic, ctx.seed
    sess, model, make, _ = build(cfg, job, seed)
    plan = sess.plan
    check_optimizer(sess, job["optimizer"])
    prog = program_readings(sess, make, job)
    h = job["period"]
    t = time.perf_counter()
    sess.fit(h)                         # warm period, sizes the window
    periods = max(2, math.ceil(ctx.seconds / (time.perf_counter() - t)))
    phases = phases_of(plan, model, h)

    # the window: one fit over whole periods (traced: one period of it
    # under the profiler, cut out as a call of its own)
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
    del sess, model
    gc.collect()

    raw: list = []
    t1 = time.perf_counter()
    ref = reference_readings(cfg, job, seed, phases, chips=ctx.cell.chips,
                             raw_norms=raw)
    ref_s = time.perf_counter() - t1
    compared = compare(prog, ref)
    record = {"window_s": window, "tokens": tokens, "periods": periods,
              "period": h, "workers": job["workers"], "seq": job["seq"],
              "batch_per_worker": job["batch_per_worker"],
              "chips": ctx.cell.chips, "period_times": period_times,
              "compiles_in_window": compiles}
    notes = {"losses": prog[0], "ref_losses": ref[0], "reference_s": ref_s,
             "ref_grad_norms_before_clip": raw,
             "partition_counts": plan.meta.get("partition_counts")}
    return Outcome(
        attempted=tokens, failed=0,
        e2e={"train_tokens_per_s": tokens / window},
        record=record, compared={k: (v, ctx.limit(k))
                                 for k, v in compared.items()},
        memory_peak_bytes=peak, trace=traced, notes=notes)
