"""Model FLOP/s utilization of training over the traced period: the
period's trained tokens over its span (the benchmark's ``bench.period``
host span, profiler start and stop outside it) times the FLOPs each
token needs (6 per matmul parameter plus the attention products, no
recompute; bench/flops.py), over chips times the bf16 peak, in %."""

from bench.flops import train_flops_per_token


def read(rec):
    r = rec["record"]
    lo, hi = rec["window"]
    tokens = r["period"] * r["workers"] * r["batch_per_worker"] * r["seq"]
    rate = tokens / ((hi - lo) / 1e9)
    flops = train_flops_per_token(rec["dims"], r["seq"])
    return 100.0 * rate * flops / (r["chips"] * rec["peaks"].bf16_flops)
