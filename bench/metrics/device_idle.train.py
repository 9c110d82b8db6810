"""Share of the traced training window (whole periods) in which no
operation ran on the device, averaged over the chips in use, in %."""

from bench import trace as tr


def read(rec):
    lo, hi = rec["window"]
    ops = rec["ops"]
    if not ops:
        return None
    return 100.0 * sum(tr.idle_share(v, lo, hi) for v in ops.values()) \
        / len(ops)
