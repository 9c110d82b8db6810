"""The trace reduction on synthesised traces."""

import pytest

from bench import trace as tr

DEV0 = "/device:TPU:0"
DEV1 = "/device:TPU:1"


def op(name, s, e, plane=DEV0, line=tr.OPS_LINE):
    return tr.Event(plane, line, name, float(s), float(e))


def test_union_merges_overlaps_and_drops_empty():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 9), (4, 4)]) == [
        (0, 3), (5, 9)]


@pytest.mark.parametrize("lo,hi,busy", [(0, 100, 70), (10, 50, 20),
                                        (95, 100, 0)])
def test_busy_and_idle_share(lo, hi, busy):
    ops = [op("fusion.1", 0, 20), op("fusion.2", 10, 30),
           op("convolution.3", 50, 90)]
    assert tr.busy_ns(ops, lo, hi) == busy
    assert tr.idle_share(ops, lo, hi) == pytest.approx(1 - busy / (hi - lo))


def test_device_ops_keeps_only_device_op_lines():
    ev = [op("a", 0, 1), op("b", 0, 1, plane=DEV1),
          op("m", 0, 5, line="XLA Modules"),
          op("bench.step", 0, 9, plane="/host:CPU", line="python")]
    per = tr.device_ops(ev)
    assert sorted(per) == [0, 1]
    assert [e.name for e in per[0]] == ["a"]


def test_exposed_collective_not_overlapped():
    ops = [op("fusion.1", 0, 10), op("all-reduce.4", 10, 20),
           op("fusion.2", 20, 30)]
    assert tr.exposed_ns(ops) == (10, 10)


def test_exposed_collective_fully_hidden():
    ops = [op("all-reduce.1", 5, 15), op("fusion.1", 0, 20)]
    assert tr.exposed_ns(ops) == (10, 0)


def test_exposed_collective_partly_hidden_async_pair():
    # start at 10, done ends at 40: in flight 30 ns, compute covers 20-35
    ops = [op("all-reduce-start.3", 10, 11), op("fusion.5", 20, 35),
           op("all-reduce-done.3", 39, 40)]
    total, exposed = tr.exposed_ns(ops)
    assert total == 30
    assert exposed == 15


def test_exposed_clipped_to_window():
    ops = [op("all-reduce.1", 0, 100)]
    assert tr.exposed_ns(ops, 50, 80) == (30, 30)


def test_kernel_time_by_name():
    ops = [op("paged_attention.1", 0, 4), op("fusion.2", 4, 10),
           op("paged_attention.7", 10, 13)]
    assert tr.op_time(ops, r"paged_attention") == (7, 2)
    assert tr.op_time(ops, r"paged_attention", 2, 11) == (3, 2)


def test_top_ops_groups_instances():
    ops = [op("fusion.1", 0, 4e9), op("fusion.2", 4e9, 5e9),
           op("convolution.1", 5e9, 8e9)]
    assert tr.top_ops(ops, 0, 10e9, n=2) == [["fusion", 5.0],
                                              ["convolution", 3.0]]


def test_idle_gaps_named_by_host_span():
    ops = [op("fusion.1", 0, 10), op("fusion.2", 30, 40)]
    spans = [op("bench.step", 0, 50, plane="/host:CPU", line="t"),
             op("bench.client", 12, 28, plane="/host:CPU", line="t")]
    gaps = tr.idle_gaps(ops, spans, 0, 50)
    assert gaps == [["bench.client", 20e-9], ["bench.step", 10e-9]]


def test_ops_named_by_hlo_text():
    """TPU op events carry their HLO text; containers span their body."""
    ops = [op("%while.2 = (s32[], bf16[8]{0}) while(%t), body=%b", 0, 10),
           op("%fusion.34 = (u32[1,1]{1,0:T(1,128)}) fusion(%p)", 0, 6),
           op("%convolution.7 = bf16[8]{0} convolution(%a, %b)", 6, 10)]
    assert tr.op_name(ops[0].name) == ("while.2", "while")
    assert tr.top_ops(ops, 0, 10) == [["fusion", 6e-9],
                                      ["convolution", 4e-9]]
    assert tr.busy_ns(ops, 0, 20) == 10
    assert tr.is_collective("%all-reduce.1 = f32[8]{0} all-reduce(%x)")
    assert not tr.is_collective("%fusion.1 = f32[8]{0} fusion(%all-reduce.1)")
