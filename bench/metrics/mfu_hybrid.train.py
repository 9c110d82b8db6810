"""Model FLOP/s utilization of hybrid training over the traced period,
as ``mfu.train`` reads it, over the FLOPs of bench/flops_hybrid.py: 6
per matmul parameter, attention's and the SSD's products over their
causal half, no recompute.  No reading for a configuration without
``layer_types``."""

from bench.flops_hybrid import train_flops_per_token


def read(rec):
    r = rec["record"]
    flops = train_flops_per_token(rec["config"], r["seq"])
    if flops is None:
        return None
    lo, hi = rec["window"]
    tokens = r["period"] * r["workers"] * r["batch_per_worker"] * r["seq"]
    rate = tokens / ((hi - lo) / 1e9)
    return 100.0 * rate * flops / (r["chips"] * rec["peaks"].bf16_flops)
