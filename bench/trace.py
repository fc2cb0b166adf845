"""Reduction of a profiler trace to device busy time, program time, idle
gaps and the host's activity in them.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData``. On a TPU each chip is a plane
``/device:TPU:<n>``, with a line of the XLA programs that ran (``XLA
Modules``, named after the jitted function, e.g. ``jit_decode``) and a
line of their operations (``XLA Ops``). The harness's own spans
(``jax.profiler.TraceAnnotation``) are events of a host plane, on the same
clock. ``bench.traced`` brackets the traced window.

- busy: the union of the operations' intervals in the window, averaged
  over the chips;
- operation time: each operation's self time, its interval less the
  operations nested in it (a ``while`` holds the operations of its body),
  under its HLO name without its signature (``%fusion.58``);
- idle gaps: the rest of the window, each part attributed to the
  innermost harness span the host was in at that time (``none`` where it
  was in none).
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
WINDOW_SPAN = "bench.traced"
SPAN_PREFIXES = ("wait.", "engine.", "executor.")
TOP = 10


@dataclass
class Events:
    """The events of one trace, as (start_ns, end_ns, name)."""
    ops: dict = field(default_factory=dict)      # device plane -> events
    modules: list = field(default_factory=list)  # every chip's programs
    spans: list = field(default_factory=list)    # harness host spans
    window: tuple = None                         # the bench.traced span


def load(trace_dir) -> Events:
    """Read the one ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(str(trace_dir), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, "
                                f"found {len(paths)}")
    return events(ProfileData.from_file(paths[0]))


def events(pd) -> Events:
    ev = Events()
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ev.ops[plane.name] = [
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events]
                elif line.name == MODULES_LINE:
                    ev.modules.extend(
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_SPAN:
                        ev.window = (e.start_ns, e.start_ns + e.duration_ns)
                    elif e.name.startswith(SPAN_PREFIXES):
                        ev.spans.append((e.start_ns,
                                         e.start_ns + e.duration_ns, e.name))
    ev.spans.sort()
    return ev


def union(intervals) -> list:
    """Merge (start, end, ...) intervals into sorted disjoint (start, end)."""
    out = []
    for s, e, *_ in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def complement(intervals, lo, hi) -> list:
    """The parts of [lo, hi) that sorted disjoint ``intervals`` leave."""
    out, t = [], lo
    for s, e in intervals:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def overlap(a, b) -> float:
    """Total length of the intersection of two sorted disjoint lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def overlap_by_name(a, pieces, into: dict):
    """Add to ``into[name]`` the length of the intersection of sorted
    disjoint intervals ``a`` with each sorted disjoint (start, end, name)
    piece."""
    i = j = 0
    while i < len(a) and j < len(pieces):
        s, e = max(a[i][0], pieces[j][0]), min(a[i][1], pieces[j][1])
        if e > s:
            into[pieces[j][2]] += e - s
        if a[i][1] < pieces[j][1]:
            i += 1
        else:
            j += 1


def innermost(spans, lo, hi) -> list:
    """Split [lo, hi) into (start, end, name) pieces, each named after
    the innermost span open over it (spans nest), or ``none``."""
    bounds = sorted({lo, hi, *(t for s, e, _ in spans for t in (s, e)
                               if lo < t < hi)})
    starts = sorted(spans)
    out, stack, k = [], [], 0
    for a, b in zip(bounds, bounds[1:]):
        while k < len(starts) and starts[k][0] <= a:
            stack.append(starts[k])
            k += 1
        stack = [s for s in stack if s[1] > a]
        name = max(stack)[2] if stack else "none"
        if out and out[-1][2] == name and out[-1][1] == a:
            out[-1] = (out[-1][0], b, name)
        else:
            out.append((a, b, name))
    return out


@dataclass
class Reduced:
    """A traced window, reduced. Times in ns on the trace's clock."""
    lo: int
    hi: int
    busy: dict            # device plane -> sorted disjoint busy intervals
    modules: list         # (start, end, name) of programs in the window
    op_time: dict         # op name -> self ns in the window, over chips
    idle_by_span: dict    # host span -> idle ns under it, over all chips
    on_ms: float          # the harness's clock at lo
    off_ms: float

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    @property
    def busy_s(self) -> float:
        return sum(sum(e - s for s, e in b) for b in self.busy.values()) \
            / max(len(self.busy), 1) / 1e9

    def module_s(self, name: str) -> tuple:
        """(seconds, count) of the programs called ``name``."""
        hits = [e - s for s, e, n in self.modules
                if n == name or n.startswith(name + "(")]
        return sum(hits) / 1e9, len(hits)

    def ns(self, ms: float) -> float:
        """A time of the harness's clock, on the trace's clock."""
        return self.lo + (ms - self.on_ms) * 1e6

    def idle_share(self, intervals_ms) -> float:
        """Share of the window in which no operation ran on a chip while
        one of ``intervals_ms`` (harness clock) was open, over chips."""
        want = union([(self.ns(a), self.ns(b)) for a, b in intervals_ms])
        want = clip(want, self.lo, self.hi)
        idle = [overlap(complement(b, self.lo, self.hi), want)
                for b in self.busy.values()]
        return sum(idle) / max(len(idle), 1) / (self.hi - self.lo)

    def breakdown(self) -> dict:
        top = sorted(self.op_time.items(), key=lambda kv: -kv[1])[:TOP]
        gaps = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[n, t / 1e9] for n, t in top],
                "idle_gaps": [[n, t / 1e9] for n, t in gaps]}


def reduce(ev: Events, on_ms: float, off_ms: float) -> Reduced:
    """Reduce the window that ``bench.traced`` brackets. ``on_ms`` and
    ``off_ms`` are the harness's clock at its ends."""
    if ev.window is None:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    if not ev.ops:
        raise ValueError("no device operations in the trace")
    lo, hi = ev.window
    busy, op_time, idle_by_span = {}, defaultdict(float), defaultdict(float)
    pieces = innermost(clip_spans(ev.spans, lo, hi), lo, hi)
    for plane, ops in ev.ops.items():
        busy[plane] = clip(union(ops), lo, hi)
        self_time(clip_spans(ops, lo, hi), op_time)
        overlap_by_name(complement(busy[plane], lo, hi), pieces,
                        idle_by_span)
    modules = [m for m in ev.modules if m[0] >= lo and m[1] <= hi]
    return Reduced(lo, hi, busy, modules, dict(op_time), dict(idle_by_span),
                   on_ms, off_ms)


def self_time(ops, into: dict):
    """Add to ``into[name]`` each operation's self time: its length less
    that of the operations directly nested in it. ``name`` is the HLO
    instruction's name, the text before `` = ``."""
    stack = []   # open operations: [end, name, self time]
    for s, e, name in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][0] <= s:
            _, n, t = stack.pop()
            into[n] += t
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name.split(" = ", 1)[0], e - s])
    for _, n, t in stack:
        into[n] += t


def clip_spans(spans, lo, hi) -> list:
    return [(max(s, lo), min(e, hi), n) for s, e, n in spans
            if e > lo and s < hi]
