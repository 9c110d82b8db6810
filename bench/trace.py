"""Reduction of a profiler trace to intervals and the numbers read off them.

``load(path)`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and
returns flat :class:`Event` records.  Everything else works on those
records alone, so the tests can feed synthesised traces.

Device planes are named ``/device:TPU:<n>``; the operations that ran on a
device are the events of its ``XLA Ops`` line.  Host spans that the
benchmark opens with ``jax.profiler.TraceAnnotation`` sit on the host
plane's threads and are used to name idle gaps.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass
from typing import Iterable

__all__ = ["Event", "load", "find_xplane", "device_ops", "host_spans",
           "union", "busy_ns", "idle_share", "exposed_ns", "clip",
           "merge_pairs", "op_time", "top_ops", "idle_gaps",
           "is_collective", "op_name"]

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(r"all-reduce", re.I)
# a TPU op event is named by its HLO text: "%fusion.34 = (...) fusion(...)"
HLO_TEXT = re.compile(r"^%?([\w.\-]+) = .*?\b([a-z][\w\-]*)\(")
# control flow spans the ops of its body on the same line
CONTAINERS = {"while", "call", "conditional"}


@dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    end_ns: float

    @property
    def dur_ns(self) -> float:
        return self.end_ns - self.start_ns


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> list[Event]:
    """Every event of every plane of one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                start = float(ev.start_ns)
                out.append(Event(plane.name, line.name, ev.name, start,
                                 start + float(ev.duration_ns)))
    return out


def device_ops(events: Iterable[Event]) -> dict[int, list[Event]]:
    """Operations per device id, sorted by start."""
    per: dict[int, list[Event]] = defaultdict(list)
    for e in events:
        m = DEVICE_PLANE.match(e.plane)
        if m and e.line == OPS_LINE:
            per[int(m.group(2))].append(e)
    return {d: sorted(v, key=lambda e: e.start_ns) for d, v in per.items()}


def host_spans(events: Iterable[Event], prefix: str) -> list[Event]:
    """Host-side spans whose name starts with ``prefix``."""
    return sorted((e for e in events if not DEVICE_PLANE.match(e.plane)
                   and e.name.startswith(prefix)),
                  key=lambda e: e.start_ns)


def union(intervals: Iterable[tuple[float, float]]
          ) -> list[tuple[float, float]]:
    """Merge overlapping ``(start, end)`` intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def busy_ns(ops: Iterable[Event], lo: float, hi: float) -> float:
    """Length of the union of op intervals inside ``[lo, hi]``."""
    return sum(e - s for s, e in union(clip(
        ((o.start_ns, o.end_ns) for o in ops), lo, hi)))


def idle_share(ops: Iterable[Event], lo: float, hi: float) -> float:
    """Share of ``[lo, hi]`` in which no operation ran, 0..1."""
    return 1.0 - busy_ns(ops, lo, hi) / (hi - lo)


def is_collective(text: str) -> bool:
    return bool(COLLECTIVE.search(op_name(text)[1]))


def merge_pairs(ops: list[Event]) -> list[Event]:
    """Replace each asynchronous ``X-start`` / ``X-done`` pair by one
    event spanning both (the collective is in flight in between)."""
    out, open_ = [], {}
    for o in ops:
        name = op_name(o.name)[0]
        base = re.sub(r"-(start|done)(?=\.|$)", "", name)
        if re.search(r"-start(\.|$)", name):
            open_[base] = o
        elif re.search(r"-done(\.|$)", name) and base in open_:
            s = open_.pop(base)
            out.append(Event(o.plane, o.line, base, s.start_ns, o.end_ns))
        else:
            out.append(o)
    out.extend(open_.values())
    return sorted(out, key=lambda e: e.start_ns)


def exposed_ns(ops: list[Event], lo: float = float("-inf"),
               hi: float = float("inf")) -> tuple[float, float]:
    """(collective time, the part of it during which no other op ran),
    both inside ``[lo, hi]``, for the ops of ONE device."""
    ops = merge_pairs(ops)
    coll = union(clip(((o.start_ns, o.end_ns) for o in ops
                       if is_collective(o.name)), lo, hi))
    other = union(clip(((o.start_ns, o.end_ns) for o in ops
                        if not is_collective(o.name)), lo, hi))
    total = sum(e - s for s, e in coll)
    covered, j = 0.0, 0
    for s, e in coll:
        while j < len(other) and other[j][1] <= s:
            j += 1
        k = j
        while k < len(other) and other[k][0] < e:
            covered += min(e, other[k][1]) - max(s, other[k][0])
            k += 1
    return total, total - covered


def op_time(ops: Iterable[Event], pattern: str, lo: float = float("-inf"),
            hi: float = float("inf")) -> tuple[float, int]:
    """(summed duration, count) of ops whose name matches ``pattern``."""
    rx = re.compile(pattern)
    t, n = 0.0, 0
    for o in ops:
        if rx.search(o.name):
            c = clip([(o.start_ns, o.end_ns)], lo, hi)
            if c:
                t += c[0][1] - c[0][0]
                n += 1
    return t, n


def op_name(text: str) -> tuple[str, str]:
    """(instruction name, opcode) of an op event; a plain name is its
    own instruction name, with its family as opcode."""
    m = HLO_TEXT.match(text)
    if m:
        return m.group(1), m.group(2)
    return text, _family(text)


def _family(name: str) -> str:
    """An op's name without its instance number: ``fusion.12`` and
    ``fusion.7`` are one family."""
    return re.sub(r"\.\d+$", "", name)


def top_ops(ops: Iterable[Event], lo: float, hi: float, n: int = 10
            ) -> list[list]:
    """The ``n`` op families with the most device time, in seconds.
    Control-flow ops (``while``, ``call``, ``conditional``) are left out:
    their time is their body's ops'."""
    acc: dict[str, float] = defaultdict(float)
    for o in ops:
        name, opcode = op_name(o.name)
        if opcode in CONTAINERS:
            continue
        c = clip([(o.start_ns, o.end_ns)], lo, hi)
        if c:
            acc[_family(name)] += c[0][1] - c[0][0]
    best = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in best]


def idle_gaps(ops: Iterable[Event], spans: list[Event], lo: float,
              hi: float, n: int = 10) -> list[list]:
    """The ``n`` longest device-idle gaps inside ``[lo, hi]``, each named
    by the innermost host span that covers its midpoint (``host`` when
    none does), in seconds."""
    busy = union(clip(((o.start_ns, o.end_ns) for o in ops), lo, hi))
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:n]:
        mid = (s + e) / 2
        cover = [sp for sp in spans if sp.start_ns <= mid <= sp.end_ns]
        name = min(cover, key=lambda sp: sp.dur_ns).name if cover \
            else "host"
        out.append([name, (e - s) / 1e9])
    return out
