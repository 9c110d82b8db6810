"""Scope buckets and the idle split on synthesised traces."""

import pytest

from bench import scopes
from bench import trace as tr

DEV0 = "/device:TPU:0"
HOST = "/host:CPU"


def op(name, s, e, line=tr.OPS_LINE, plane=DEV0):
    return tr.Event(plane, line, name, float(s), float(e))


def span(name, s, e):
    return tr.Event(HOST, "python", name, float(s), float(e))


FWD = "jit(phase_0)/vmap(jvp(fwd))/while/body/closed_call/dot_general"
BWD = "jit(phase_0)/vmap(transpose(jvp(fwd)))/while/body/closed_call/" \
    "checkpoint/dot_general"
REMAT = "jit(phase_0)/vmap(transpose(jvp(fwd)))/while/body/closed_call/" \
    "checkpoint/rematted_computation/dot_general"
OPT = "jit(phase_0)/optimizer/sqrt"
SYNC = "jit(phase_1)/sync/reduce_sum"


@pytest.mark.parametrize("path,want", [
    (FWD, "fwd"), (BWD, "bwd"), (REMAT, "remat"), (OPT, "opt"),
    (SYNC, "sync"), ("jit(phase_0)/add", "other"), ("", "other"),
    # a fusion's joined paths go by the first
    (f"{BWD};{FWD}", "bwd"), (f"{FWD};{BWD}", "fwd"), (f"{OPT};{SYNC}", "opt"),
])
def test_bucket(path, want):
    assert scopes.bucket(path) == want


HLO = """HloModule jit_phase_0, entry_computation_layout={()->()}

%fused_computation.1 (p: bf16[8]) -> bf16[8] {
  %p = bf16[8]{0} parameter(0)
  ROOT %exp.1 = bf16[8]{0} exponential(%p), metadata={op_name="@FWD@"}
}

ENTRY %main {
  %fusion.1 = bf16[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="@OPT@;@FWD@"}
  %convolution.2 = bf16[8]{0} convolution(%a, %b), metadata={op_name="@REMAT@" source_file="x.py"}
  ROOT %copy.3 = bf16[8]{0} copy(%c)
}
""".replace("@FWD@", FWD).replace("@OPT@", OPT).replace("@REMAT@", REMAT)


def test_hlo_scopes_reads_metadata():
    got = scopes.hlo_scopes(HLO)
    assert got == {"exp.1": FWD, "fusion.1": f"{OPT};{FWD}",
                   "convolution.2": REMAT}
    assert scopes.module_name("jit_phase_0(1449986496228299716)") \
        == "jit_phase_0"


def test_op_scopes_joins_by_module():
    """The same instruction name means different ops in two modules."""
    other = HLO.replace("jit_phase_0", "jit_phase_1").replace(
        REMAT, SYNC)
    mods = [op("jit_phase_0(11)", 0, 100, line=scopes.MODULES_LINE),
            op("jit_phase_1(22)", 100, 200, line=scopes.MODULES_LINE)]
    ops = [op("%convolution.2 = bf16[8]{0} convolution(%a, %b)", 10, 20),
           op("%convolution.2 = bf16[8]{0} convolution(%a, %b)", 110, 130),
           op("%copy.3 = bf16[8]{0} copy(%c)", 130, 140),
           op("%fusion.1 = bf16[8]{0} fusion(%a)", 250, 260)]
    got = scopes.op_scopes(ops, mods, {"jit_phase_0": HLO,
                                       "jit_phase_1": other})
    assert [p for _, p in got] == [REMAT, SYNC, "", ""]


def _scoped():
    """Ops of one device with their paths: a loop container around two
    leaves, an overlap, a joined fusion, an op outside the window."""
    return [
        (op("%while.1 = (s32[]) while(%t), body=%b", 0, 100), FWD),
        (op("%fusion.1 = bf16[8] fusion(%a)", 5, 30), FWD),
        (op("%convolution.2 = bf16[8] convolution(%a, %b)", 40, 70), BWD),
        (op("%fusion.3 = bf16[8] fusion(%a)", 60, 80), REMAT),  # overlaps
        (op("%fusion.4 = bf16[8] fusion(%a)", 110, 130), f"{OPT};{FWD}"),
        (op("%fusion.5 = bf16[8] fusion(%a)", 130, 140), ""),
        (op("%copy.6 = bf16[8] copy(%a)", 300, 310), SYNC),
    ]


@pytest.mark.parametrize("lo,hi", [(0, 200), (10, 125), (0, 400)])
def test_scope_time_sums_to_busy(lo, hi):
    scoped = _scoped()
    by = scopes.scope_time(scoped, lo, hi)
    assert set(by) == set(scopes.BUCKETS)
    assert sum(by.values()) == pytest.approx(
        tr.busy_ns([o for o, _ in scoped], lo, hi))


def test_scope_time_buckets():
    by = scopes.scope_time(_scoped(), 0, 200)
    # the loop's own time (0-5, 30-40, 80-100) goes to other, with the
    # op that has no path; the overlap 60-70 stays with the earlier op
    assert by == {"fwd": 25, "bwd": 30, "remat": 10, "opt": 20,
                  "sync": 0, "other": 5 + 10 + 20 + 10}


SPANS = [span("repro.fit", 0, 1000), span("repro.period", 0, 500),
         span("repro.stage", 0, 20), span("repro.dispatch", 20, 60),
         span("repro.wait", 60, 500), span("repro.period", 500, 1000),
         span("repro.stage", 500, 505), span("repro.dispatch", 510, 520),
         span("repro.wait", 520, 990), span("repro.drain", 990, 1000)]
OPS = [op("a.1", 30, 200), op("b.1", 210, 480), op("c.1", 540, 980)]


@pytest.mark.parametrize("lo,hi", [(0, 1000), (100, 600), (0, 400)])
def test_idle_split_sums_to_idle(lo, hi):
    launch, host = scopes.idle_split(OPS, SPANS, lo, hi)
    assert launch + host == pytest.approx(
        tr.idle_share(OPS, lo, hi) * (hi - lo))


def test_idle_split_by_wait():
    launch, host = scopes.idle_split(OPS, SPANS, 0, 1000)
    # idle: 0-30 (stage, dispatch), 200-210 (wait), 480-540 (wait to
    # 500, the next period's stage and dispatch, wait from 520),
    # 980-1000 (wait to 990, then drain)
    assert launch == 10 + 20 + 20 + 10
    assert host == 30 + 20 + 10


def test_idle_split_without_program_spans_is_all_host():
    assert scopes.idle_split(OPS, [], 0, 1000) == (0.0, 120.0)


def test_idle_gaps_keep_lengths_with_program_spans():
    bench = [span("bench.period", 0, 1000)]
    old = tr.idle_gaps(OPS, bench, 0, 1000)
    new = tr.idle_gaps(OPS, bench + SPANS, 0, 1000)
    assert [g[1] for g in new] == [g[1] for g in old] \
        == pytest.approx([60e-9, 30e-9, 20e-9, 10e-9])
    assert {g[0] for g in old} == {"bench.period"}
    assert [g[0] for g in new] == ["repro.dispatch", "repro.stage",
                                   "repro.drain", "repro.wait"]
