#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python bench/calibrate.py --workload <cell> --seeds 1 2 3 ...

For each seed it prints one JSON line: the program's numbers against the
float32 reference (the lower readings), the control's (the reference in
float8, in the program's place: the upper readings) and the faults
planted in the reference (half of each row left out of the loss; with
several workers, the exchange left out).  The program's readings come
from a fresh session's first whole period, as in a run's set-up; no
window is needed.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

harness.keep_logs_inside()


def train_readings(cell, seed: int) -> dict:
    from bench.drivers import train
    cfg, job = cell.config, cell.traffic
    sess, model, make, _ = train.build(cfg, job, seed)
    prog = train.program_readings(sess, make, job)
    phases = train.phases_of(sess.plan, model, job["period"])
    del sess, model
    gc.collect()

    def reference(**kw):
        return train.reference_readings(cfg, job, seed, phases,
                                        chips=cell.chips, **kw)

    ref = reference()
    out = {"program": train.compare(prog, ref),
           "control": train.compare(reference(prec="fp8"), ref),
           "half_batch": train.compare(reference(keep=0.5), ref)}
    if job["workers"] > 1:
        out["no_exchange"] = train.compare(reference(sync=False), ref)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.program_on_path()
    harness.require_chips(cell.chips)
    harness.use_cache()
    if cell.kind != "train":
        raise harness.NoResult(f"no readings for traffic kind {cell.kind!r}")
    for seed in args.seeds:
        print(json.dumps({"seed": seed, **train_readings(cell, seed)}),
              flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
