#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once and print one JSON result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  Set-up (weights, state, compile or cache
loads, warm-up) counts as ``setup_s``; then the cell's traffic runs for
``--seconds``; then the plain reference checks what the timed path
produced.  With ``--trace 0`` the metrics are the cell's end-to-end
metrics; with ``--trace 1`` part of the window runs under the profiler
and the metrics are the cell's per-layer metrics.  Without an
accelerator, or with fewer chips than the cell asks for, the run exits
nonzero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

harness.keep_logs_inside()


class Ctx:
    """What a driver sees of the run: its cell, seed and length, the
    window's bookkeeping and the profiler."""

    def __init__(self, cell, seed: int, seconds: float, trace: bool,
                 devices, clock, t_start: float = T_START):
        self.cell, self.seed, self.seconds, self.trace = (cell, seed,
                                                          seconds, trace)
        self.devices, self.clock, self.t_start = devices, clock, t_start
        self.setup_s = None
        self._compiles0 = 0
        self._trace_dir = None

    def limit(self, name: str) -> float:
        return float(self.cell.limits[name]["limit"])

    def window_opens(self) -> None:
        self.setup_s = time.perf_counter() - self.t_start
        self._compiles0 = self.clock.count

    def window_closes(self) -> int:
        return self.clock.count - self._compiles0

    def peak_memory(self) -> int:
        return harness.peak_memory(self.devices[:self.cell.chips])

    def start_trace(self) -> None:
        import jax
        self._trace_dir = harness.OUT_DIR / f"trace-{self.cell.name}"
        shutil.rmtree(self._trace_dir, ignore_errors=True)
        self._trace_dir.mkdir(parents=True)
        jax.profiler.start_trace(str(self._trace_dir))

    def stop_trace(self) -> str:
        import jax
        jax.profiler.stop_trace()
        return str(self._trace_dir)


def traced_metrics(cell, outcome, devices):
    """Per-layer metrics, device busy time and the breakdown, from the
    trace and the driver's record."""
    from bench import trace as tr
    from bench.flops import dims_of
    from bench.peaks import peaks_for

    events = tr.load(tr.find_xplane(outcome.trace))
    shutil.rmtree(outcome.trace, ignore_errors=True)
    ops = {d: v for d, v in tr.device_ops(events).items()
           if d < cell.chips}
    spans = tr.host_spans(events, "bench.")
    marks = [s for s in spans if s.name in ("bench.period", "bench.step")]
    if not ops or not marks:
        raise harness.NoResult("the trace holds no device operations or "
                               "no benchmark spans")
    lo, hi = marks[0].start_ns, max(s.end_ns for s in marks)
    rec = {"record": outcome.record, "ops": ops, "spans": spans,
           "window": (lo, hi), "dims": dims_of(cell.config),
           "peaks": peaks_for(devices[0].device_kind),
           "config": cell.config, "traffic": cell.traffic}
    metrics = {}
    for m in cell.per_layer:
        mod = harness.load_module(harness.BENCH / "metrics"
                                  / f"{m['name']}.py",
                                  f"bench_metric_{m['name']}")
        value = mod.read(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    busy = sum(tr.busy_ns(v, lo, hi) for v in ops.values()) / len(ops)
    all_ops = [o for v in ops.values() for o in v]
    breakdown = {"device_ops": tr.top_ops(all_ops, lo, hi),
                 "idle_gaps": tr.idle_gaps(ops[min(ops)], spans, lo, hi)}
    return metrics, busy / 1e9, (hi - lo) / 1e9, breakdown


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.load_cell(args.workload)
    harness.program_on_path()
    devices = harness.require_chips(cell.chips)
    harness.use_cache()
    clock = harness.CompileClock()
    ctx = Ctx(cell, args.seed, args.seconds, bool(args.trace), devices,
              clock)
    driver = harness.load_module(harness.BENCH / "drivers"
                                 / f"{cell.kind}.py",
                                 f"bench_driver_{cell.kind}")
    out = driver.run(ctx)

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": out.memory_peak_bytes}
    result = {"correct": out.correct, "attempted": out.attempted,
              "failed": out.failed}
    if args.trace:
        metrics, busy_s, window_s, breakdown = traced_metrics(cell, out,
                                                              devices)
        device.update(busy_s=busy_s, window_s=window_s)
    else:
        values = dict(out.e2e, setup_s=ctx.setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
        breakdown = None
    result.update(metrics=metrics, device=device)
    if breakdown is not None:
        result["breakdown"] = breakdown
    print("notes " + json.dumps(out.notes, default=float), file=sys.stderr)
    print(f"compiles_in_window {out.record['compiles_in_window']}",
          file=sys.stderr)
    compared = {k: {"value": v, "limit": lim}
                for k, (v, lim) in out.compared.items()}
    result["compared"] = compared
    for k, (v, lim) in out.compared.items():
        print(f"{k} {v!r} limit {lim!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
