"""A training step's device time by the part of the step it belongs to,
and device idle time by what the host was doing.

The phase step (``src/repro/runtime/step.py``) runs under named scopes
that XLA keeps as each instruction's ``op_name`` metadata:

- ``fwd``: ``.../jvp(fwd)/...``, without ``transpose(``;
- ``remat``: ``.../transpose(jvp(fwd))/.../rematted_computation/...``;
- ``bwd``: the rest of ``transpose(jvp(fwd))``;
- ``opt``: ``.../optimizer/...``;
- ``sync``: ``.../sync/...``;
- ``other``: everything else (the loss mean, the step counter, control
  flow between the ops of a loop).

A fusion's ``;``-joined paths are classified by the first.  A v5e
trace's op events carry the instruction's text and no metadata, so an
op's path is found by joining its instruction name and its module (the
``XLA Modules`` event around it) with that module's compiled HLO text.

The runner's host spans (``repro.*``, ``src/repro/runtime/spans.py``)
split the device's idle time: idle under ``repro.wait`` is the device
waiting between executables the host had already queued (launch gaps);
every other idle time is the host holding the chip back.
"""

from __future__ import annotations

import bisect
import re
from typing import Iterable

from bench import trace as tr

__all__ = ["BUCKETS", "MODULES_LINE", "WAIT", "bucket", "hlo_scopes",
           "module_name", "op_scopes", "scope_time", "idle_split"]

BUCKETS = ("fwd", "bwd", "remat", "opt", "sync", "other")
MODULES_LINE = "XLA Modules"
WAIT = "repro.wait"
# an instruction of compiled HLO text with its metadata's op_name
_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = [^\n]*?"
                    r"op_name=\"([^\"]*)\"", re.M)


def bucket(op_name: str) -> str:
    """The part of the step an ``op_name`` belongs to (``BUCKETS``)."""
    path = op_name.split(";")[0]
    if "/optimizer/" in path:
        return "opt"
    if "/sync/" in path:
        return "sync"
    if "transpose(jvp(fwd))" in path:
        return "remat" if "rematted_computation" in path else "bwd"
    if "jvp(fwd)" in path:
        return "fwd"
    return "other"


def hlo_scopes(text: str) -> dict[str, str]:
    """``{instruction name: op_name}`` of one module's compiled HLO
    text; instructions without metadata are left out."""
    return dict(_INSTR.findall(text))


def module_name(event_name: str) -> str:
    """``jit_phase_3(1449986496228299716)`` -> ``jit_phase_3``."""
    return event_name.split("(", 1)[0]


def op_scopes(ops: list[tr.Event], modules: list[tr.Event],
              texts: dict[str, str]) -> list[tuple[tr.Event, str]]:
    """Each op of ONE device with its ``op_name`` ("" where its module's
    text is not given or has no metadata for it), its module being the
    ``modules`` event that covers its midpoint."""
    mods = sorted(modules, key=lambda m: m.start_ns)
    starts = [m.start_ns for m in mods]
    scopes = {name: hlo_scopes(text) for name, text in texts.items()}
    out = []
    for o in ops:
        mid = (o.start_ns + o.end_ns) / 2
        i = bisect.bisect_right(starts, mid) - 1
        path = ""
        if i >= 0 and mid <= mods[i].end_ns:
            table = scopes.get(module_name(mods[i].name), {})
            path = table.get(tr.op_name(o.name)[0], "")
        out.append((o, path))
    return out


def scope_time(scoped: Iterable[tuple[tr.Event, str]], lo: float,
               hi: float) -> dict[str, float]:
    """Device ns per bucket inside ``[lo, hi]`` for the ops of ONE
    device.  Control-flow ops (``while``, ``call``, ``conditional``) are
    left out, as in :func:`bench.trace.top_ops`; time when only they run
    counts as ``other``.  Where ops overlap, the one that started first
    keeps the time, so the buckets sum to ``busy_ns`` of the same ops."""
    acc = dict.fromkeys(BUCKETS, 0.0)
    scoped = list(scoped)
    leaves = sorted(((o, p) for o, p in scoped
                     if tr.op_name(o.name)[1] not in tr.CONTAINERS),
                    key=lambda op: op[0].start_ns)
    t = lo
    for o, path in leaves:
        s, e = max(o.start_ns, t), min(o.end_ns, hi)
        if e > s:
            acc[bucket(path)] += e - s
        t = max(t, min(o.end_ns, hi))
    acc["other"] += tr.busy_ns([o for o, _ in scoped], lo, hi) \
        - tr.busy_ns([o for o, _ in leaves], lo, hi)
    return acc


def idle_split(ops: list[tr.Event], spans: list[tr.Event], lo: float,
               hi: float) -> tuple[float, float]:
    """``(launch, host)`` device-idle ns inside ``[lo, hi]`` for the ops
    of ONE device: idle time under a ``repro.wait`` span, and the rest.
    They sum to ``idle_share(ops, lo, hi) * (hi - lo)``."""
    busy = tr.union(tr.clip(((o.start_ns, o.end_ns) for o in ops), lo, hi))
    idle, t = [], lo
    for s, e in busy:
        if s > t:
            idle.append((t, s))
        t = max(t, e)
    if hi > t:
        idle.append((t, hi))
    waits = tr.union((w.start_ns, w.end_ns) for w in spans
                     if w.name == WAIT)
    launch = 0.0
    for s, e in idle:
        launch += sum(b - a for a, b in tr.clip(waits, s, e))
    return launch, sum(e - s for s, e in idle) - launch

