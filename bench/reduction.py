"""From a profiler trace to the numbers the per-layer metrics read.

The harness wraps each timed call in two host spans of its own,
``bench.call`` around the pipeline call and ``bench.wait`` around
``block_until_ready``.  The device plane of each of the cell's chips
(``/device:TPU:0`` to ``/device:TPU:<chips - 1>``: a mesh is built on the
first devices; one chip reads ``/device:TPU:0`` alone) gives the
intervals in which an operation ran there.  ``reduce`` works on plain
tuples, so it can be checked on a recorded trace and on hand-made events
alike:

- the traced window runs from the first ``bench.call`` start to the last
  ``bench.wait`` end;
- busy is the union of the device-op intervals of every chip inside the
  window (the mesh is busy while any of its chips is), and idle is the
  rest of the window;
- each idle gap is named by the span open at its midpoint (``bench.call``,
  ``bench.wait``, or ``loop`` when neither is);
- op totals add each op's self time inside the window (an op that runs
  others, such as a ``while`` loop, is charged only what its children do
  not cover), by ``<module>/<op>``, where the module is the XLA program
  the op ran in.  Ops nest only within one chip's plane, so self times and
  labels are taken per plane; the totals are per chip (the sum over the
  planes divided by their number).
"""

from __future__ import annotations

import bisect
import dataclasses

CALL, WAIT, LOOP = "bench.call", "bench.wait", "loop"
SPANS = (CALL, WAIT)
#: a device plane's lines of operations and of programs.
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


@dataclasses.dataclass
class Events:
    ops: list        # (name, start_ns, end_ns) on the first chip's device
    spans: list      # (name, start_ns, end_ns) on the host, names in SPANS
    modules: list = dataclasses.field(default_factory=list)  # as ops
    #: ``(ops, modules)`` of each further chip of a mesh, in device order.
    others: list = dataclasses.field(default_factory=list)

    def planes(self) -> list:
        """``(ops, modules)`` of every chip read, the first chip first."""
        return [(self.ops, self.modules), *self.others]


@dataclasses.dataclass
class Reading:
    """What one traced window shows.  Seconds throughout."""
    window_s: float
    busy_s: float
    calls: int                  # bench.call spans in the window
    idle_in_calls_s: float      # device idle while a bench.call span was open
    op_totals: list             # [(op name, seconds)], longest first
    gaps: list                  # [(span name, seconds)], longest first
    flops: float = 0.0          # compulsory work of the traced calls
    bytes: float = 0.0
    peak_flops_per_s: float = 0.0
    peak_bytes_per_s: float = 0.0
    call_median_s: float = 0.0  # of the timed (untraced) window, host clock
    chips: int = 1              # device planes read; the peaks are per chip
    chip_busy_s: tuple = ()     # each chip's own busy time, first chip first

    def min_time_s(self) -> float:
        """Least time the cell's chips could take for the traced calls'
        work, the work spread evenly over them."""
        return max(self.flops / (self.chips * self.peak_flops_per_s),
                   self.bytes / (self.chips * self.peak_bytes_per_s))


def events_from_profile(profile, chips: int = 1) -> Events:
    """Device ops of chips ``0 .. chips - 1`` and bench spans of a
    ``jax.profiler.ProfileData``."""
    planes = {f"/device:TPU:{i}": ([], []) for i in range(chips)}
    spans = []
    for plane in profile.planes:
        if plane.name in planes:
            ops, modules = planes[plane.name]
            for line in plane.lines:
                into = {OPS_LINE: ops, MODULES_LINE: modules}.get(line.name)
                if into is not None:
                    into += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events if e.name in SPANS]
    (ops, modules), *others = planes.values()
    return Events(ops=ops, spans=spans, modules=modules, others=others)


def load(path: str, chips: int = 1) -> Events:
    from jax.profiler import ProfileData
    return events_from_profile(ProfileData.from_file(path), chips)


def merge(intervals) -> list:
    """Union of ``(start, end)`` intervals, sorted and disjoint."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def _complement(busy, lo, hi) -> list:
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def _overlap(a, b) -> float:
    """Total length of the intersection of two merged interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _covering(intervals, t) -> bool:
    """Whether ``t`` lies in one of the merged, sorted ``intervals``."""
    i = bisect.bisect_right(intervals, (t, float("inf"))) - 1
    return i >= 0 and intervals[i][0] <= t < intervals[i][1]


def short_name(name: str) -> str:
    """``%fusion.1 = f32[...] fusion(...)`` -> ``fusion.1``;
    ``jit_driver(1234)`` -> ``jit_driver``."""
    return name.split(" = ", 1)[0].lstrip("%").split("(", 1)[0]


def self_times(ops) -> list:
    """``(name, start, end)`` -> ``(name, self time)``: an op's duration less
    what the ops nested inside it cover.  Ops of one line nest properly."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    own = [e - s for _, s, e in ops]
    stack: list = []
    for i in order:
        _, s, e = ops[i]
        while stack and ops[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][2]:
            own[stack[-1]] -= e - s
        stack.append(i)
    return [(ops[i][0], own[i]) for i in range(len(ops))]


def label_ops(ops, modules) -> list:
    """Name each op ``<module>/<op>`` by the program that was running."""
    mods = sorted((s, e, short_name(n)) for n, s, e in modules)
    starts = [m[0] for m in mods]
    out = []
    for n, s, e in ops:
        i = bisect.bisect_right(starts, s) - 1
        mod = mods[i][2] + "/" if i >= 0 and s < mods[i][1] else ""
        out.append((mod + short_name(n), s, e))
    return out


def window(ev: Events) -> tuple | None:
    """``(start, end)`` of the traced window in ns: the first ``bench.call``
    start to the last ``bench.wait`` end; None where no call was traced."""
    calls = sorted((s, e) for n, s, e in ev.spans if n == CALL)
    if not calls:
        return None
    waits = [e for n, _, e in ev.spans if n == WAIT]
    return calls[0][0], max(waits + [calls[-1][1]])


def busy(ops, lo, hi) -> list:
    """Union of the intervals of ``ops`` inside ``[lo, hi]``, merged."""
    return merge(_clip([(s, e) for _, s, e in ops if e > lo and s < hi],
                       lo, hi))


def reduce(ev: Events, top: int = 10) -> Reading | None:
    """The window's reading, or None where the trace holds no bench.call."""
    if (w := window(ev)) is None:
        return None
    lo, hi = w
    calls = sorted((s, e) for n, s, e in ev.spans if n == CALL)
    waits = sorted((s, e) for n, s, e in ev.spans if n == WAIT)
    planes = ev.planes()
    each = [busy(ops, lo, hi) for ops, _ in planes]
    union = merge([iv for b in each for iv in b])
    idle = _complement(union, lo, hi)
    call_iv, wait_iv = merge(calls), merge(waits)
    totals: dict = {}
    for ops, modules in planes:
        ops = [(n, s, e) for n, s, e in ops if e > lo and s < hi]
        clipped = [(n, max(s, lo), min(e, hi)) for n, s, e in
                   label_ops(ops, modules)]
        for n, t in self_times(clipped):
            totals[n] = totals.get(n, 0) + t

    def name_gap(s, e):
        mid = (s + e) / 2
        return CALL if _covering(call_iv, mid) else (
            WAIT if _covering(wait_iv, mid) else LOOP)

    gaps = sorted(((name_gap(s, e), (e - s) * 1e-9) for s, e in idle),
                  key=lambda g: -g[1])
    return Reading(
        window_s=(hi - lo) * 1e-9,
        busy_s=sum(e - s for s, e in union) * 1e-9,
        calls=len(calls),
        idle_in_calls_s=_overlap(idle, call_iv) * 1e-9,
        op_totals=sorted(((n, t * 1e-9 / len(planes))
                          for n, t in totals.items()),
                         key=lambda o: -o[1])[:top],
        gaps=gaps[:top],
        chips=len(planes),
        chip_busy_s=tuple(sum(e - s for s, e in b) * 1e-9 for b in each))
