"""What a profiler trace of training can attribute: the phase step's
named scopes in the compiled HLO (classified as the benchmark's
``bench/scopes.py`` reads them), the executables' names, and the
runner's host spans (runtime/spans.py) read back from a real trace."""

import glob
import re

import jax
import pytest
from jax.profiler import ProfileData

from bench.scopes import bucket
from repro.api import JobConfig, Session
from repro.models.transformer import DecoderLM, LMConfig
from repro.parallel.sharding import place_worker_axis
from repro.runtime import make_period_step, spans

H = 3
_CFG = LMConfig(name="t", n_layers=4, d_model=48, n_heads=4, n_kv_heads=2,
                d_ff=96, vocab=64, param_dtype="float32", remat=True)
# an HLO instruction of the compiled text: (name, opcode, metadata op_name)
_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = [^\n]*?\b([a-z][\w\-]*)\("
                    r"[^\n]*?op_name=\"([^\"]+)\"", re.M)


@pytest.fixture(scope="module")
def session():
    sess = Session(JobConfig(algo="dreamddp", workers=2, period=H,
                             bandwidth=1e9, seq=32, batch_per_worker=2,
                             lr=3e-3, warmup_steps=2, decay_steps=200),
                   model=DecoderLM(_CFG))
    sess.fit(H)
    return sess


def _compiled(fn, sess):
    batch = place_worker_axis(sess.runner.data.batch(0))
    return fn.lower(sess.state, batch).compile().as_text()


def _module(text: str) -> str:
    return re.search(r"HloModule (\S+?),", text).group(1)


@pytest.mark.parametrize("which", [*range(H), "local"])
def test_phase_step_named_and_matmuls_in_step_buckets(session, which):
    r = session.runner
    fn, name = ((r._local, "local_step") if which == "local"
                else (r._steps[which], f"phase_{which}"))
    text = _compiled(fn, session)
    assert _module(text) == f"jit_{name}"
    instrs = _INSTR.findall(text)
    matmuls = [op for _, opcode, op in instrs
               if opcode in ("dot", "convolution")]
    assert matmuls
    assert all(op.startswith(f"jit({name})/") for op in matmuls)
    found = {bucket(op) for op in matmuls}
    assert found == {"fwd", "bwd", "remat"}
    every = {bucket(op) for _, _, op in instrs}
    assert "opt" in every
    # the local step syncs nothing; phases with units average them
    units = () if which == "local" else session.plan.units_for_phase(which)
    assert ("sync" in every) == bool(units)


def test_period_step_named(session):
    r = session.runner
    period = make_period_step(r.model, r.optimizer, r.plan, cfg=r.step_cfg)
    batch = jax.tree.map(lambda *xs: jax.numpy.stack(xs),
                         *[r.data.batch(t) for t in range(H)])
    text = period.lower(session.state,
                        place_worker_axis(batch, axis=1)).compile().as_text()
    assert _module(text) == "jit_period_step"
    assert {bucket(op) for _, opcode, op in _INSTR.findall(text)
            if opcode == "dot"} == {"fwd", "bwd", "remat"}


def _spans(trace_dir):
    path = sorted(glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def test_fit_spans_nest_per_period(tmp_path):
    sess = Session(JobConfig(algo="dreamddp", workers=2, period=H,
                             bandwidth=1e9, seq=32, batch_per_worker=2,
                             lr=3e-3, warmup_steps=2, decay_steps=200),
                   model=DecoderLM(_CFG))
    with jax.profiler.trace(str(tmp_path)):
        sess.fit(2 * H)
    got = _spans(tmp_path)
    fits = [s for s in got if s[0] == spans.FIT]
    periods = [s for s in got if s[0] == spans.PERIOD]
    assert len(fits) == 1 and fits[0][3]["step"] == 0
    assert [p[3]["step_num"] for p in periods] == [0, H]
    _, f0, f1, _ = fits[0]
    for name, s, e, stats in periods:
        assert f0 <= s and e <= f1
        inner = [g for g in got if g[0] != name and s <= g[1] and g[2] <= e]
        order = [g[0] for g in inner]
        assert set(order) >= {spans.STAGE, spans.DISPATCH, spans.WAIT}
        assert order.index(spans.STAGE) < order.index(spans.DISPATCH) \
            < order.index(spans.WAIT)
        assert {g[3]["step"] for g in inner} == {stats["step_num"]}
    drains = [s for s in got if s[0] == spans.DRAIN]
    assert drains and drains[0][1] >= periods[-1][2]
