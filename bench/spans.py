"""The program's own spans in a traced window, and what they show.

The pipeline opens ``mozart.*`` spans on the profiler's clock
(``repro/core/trace.py``): the entry spans of each call (``mozart.call``,
``mozart.capture``, ``mozart.plan`` with ``mozart.rewrite`` and
``mozart.fingerprint``, ``mozart.evaluate``, ``mozart.force``) and the
spans of each stage (``mozart.stage`` with ``mozart.inputs``,
``mozart.layout``, ``mozart.drive`` and ``mozart.merge``).  Spans are
``(name, start_ns, end_ns, attrs)`` and nest properly, as they are opened
by one thread.  From those inside the traced window (``reduction.py``):

- each span's self time: its duration less what its child spans cover;
- the sums of each span's numeric attributes (``mozart.stage`` carries
  its ``layout_bytes``);
- the device-idle time inside ``bench.call``, split by the innermost
  program span open at each idle instant, and the rest, where none is
  open ("unattributed").

A metric reader is handed only the window's ``reduction.Reading``;
``of_reading`` finds the trace it was reduced from among those ``run.py``
writes (``out/trace/<cell>/``), by reducing each again until one reads the
same, and prints the spans' per-call summary once.  A trace with no
program span (a program without them) gives ``None``.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import sys
from pathlib import Path

from bench import reduction

PREFIX = "mozart."
ENTRY = ("mozart.call", "mozart.capture", "mozart.plan", "mozart.rewrite",
         "mozart.fingerprint", "mozart.evaluate", "mozart.force")
STAGE = ("mozart.stage", "mozart.inputs", "mozart.layout", "mozart.drive",
         "mozart.merge")
#: where ``run.py`` writes each cell's traced window.
TRACES = Path(__file__).resolve().parent / "out" / "trace"


@dataclasses.dataclass
class SpanTotals:
    """One span name's sums over the window.  Seconds throughout."""
    count: int = 0
    total_s: float = 0.0     # durations
    self_s: float = 0.0      # durations less what child spans cover
    idle_s: float = 0.0      # device idle in bench.call while innermost
    attrs: collections.Counter = dataclasses.field(
        default_factory=collections.Counter)   # numeric attributes, summed


@dataclasses.dataclass
class Totals:
    by_name: dict                  # span name -> SpanTotals
    unattributed_idle_s: float     # idle in bench.call, no program span open
    parents: collections.Counter   # (span, enclosing span or None) -> count

    def idle_s(self, names) -> float:
        return sum(self.by_name[n].idle_s for n in names if n in self.by_name)


def from_profile(profile) -> list:
    """The ``mozart.*`` host spans of a ``jax.profiler.ProfileData``."""
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for plane in profile.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(PREFIX)]


def parents(spans) -> list:
    """For each span, the index of the span directly enclosing it, or
    ``None``."""
    order = sorted(range(len(spans)), key=lambda i: (spans[i][1], -spans[i][2]))
    out: list = [None] * len(spans)
    stack: list = []
    for i in order:
        _, s, e = spans[i][:3]
        while stack and spans[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= spans[stack[-1]][2]:
            out[i] = stack[-1]
        stack.append(i)
    return out


def innermost(spans) -> list:
    """``[(start, end, name)]``, sorted and disjoint, covering the union of
    ``spans``: in each piece ``name`` is the innermost span open."""
    order = sorted(spans, key=lambda sp: (sp[1], -sp[2]))
    pieces: list = []
    stack: list = []
    t = float("-inf")

    def close_until(x):
        nonlocal t
        while stack and stack[-1][2] <= x:
            name, _, end = stack.pop()
            if end > t:
                pieces.append((t, end, name))
                t = end

    for name, s, e, *_ in order:
        close_until(s)
        if stack and s > t:
            pieces.append((t, s, stack[-1][0]))
        stack.append((name, s, e))
        t = max(t, s)
    close_until(float("inf"))
    return pieces


def name_at(pieces, t) -> str | None:
    """The innermost span open at ``t``, or ``None``."""
    i = bisect.bisect_right(pieces, (t, float("inf"), "")) - 1
    return pieces[i][2] if i >= 0 and pieces[i][0] <= t < pieces[i][1] else None


def _overlaps(pieces, intervals) -> list:
    """For each piece, its overlap with the merged, sorted ``intervals``."""
    out = []
    j = 0
    for s, e, _ in pieces:
        while j < len(intervals) and intervals[j][1] <= s:
            j += 1
        total, k = 0.0, j
        while k < len(intervals) and intervals[k][0] < e:
            total += min(e, intervals[k][1]) - max(s, intervals[k][0])
            k += 1
        out.append(total)
    return out


def totals(spans, idle_in_calls) -> Totals | None:
    """Sums over ``spans``; ``idle_in_calls`` is the merged, sorted device
    idle inside ``bench.call`` spans, in ns."""
    if not spans:
        return None
    by_name: dict = collections.defaultdict(SpanTotals)
    up = parents(spans)
    for (name, s, e, attrs), p in zip(spans, up):
        t = by_name[name]
        t.count += 1
        t.attrs.update({k: v for k, v in attrs.items()
                        if isinstance(v, (int, float))})
        t.total_s += (e - s) * 1e-9
        t.self_s += (e - s) * 1e-9
        if p is not None:
            by_name[spans[p][0]].self_s -= (e - s) * 1e-9
    pieces = innermost(spans)
    for (_, _, name), idle in zip(pieces, _overlaps(pieces, idle_in_calls)):
        by_name[name].idle_s += idle * 1e-9
    # What no span covers, from the outermost spans (not from the pieces).
    outermost = sorted(((s, e, None) for (_, s, e, _), p in zip(spans, up)
                        if p is None), key=lambda piece: piece[0])
    open_idle = sum(_overlaps(outermost, idle_in_calls))
    all_idle = sum(e - s for s, e in idle_in_calls)
    return Totals(
        by_name=dict(by_name),
        unattributed_idle_s=(all_idle - open_idle) * 1e-9,
        parents=collections.Counter(
            (sp[0], None if p is None else spans[p][0])
            for sp, p in zip(spans, up)))


def _intersect(a, b) -> list:
    """Intersection of two merged interval lists, merged."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def in_window(ev, program) -> Totals | None:
    """``totals`` of the program spans inside the window ``reduction.reduce``
    reads from ``ev`` (first ``bench.call`` start to last ``bench.wait``
    end), with the device idle inside ``bench.call`` as it computes it."""
    calls = sorted((s, e) for n, s, e in ev.spans if n == reduction.CALL)
    waits = sorted((s, e) for n, s, e in ev.spans if n == reduction.WAIT)
    if not calls:
        return None
    lo = calls[0][0]
    hi = max([e for _, e in waits] + [calls[-1][1]])
    busy = reduction.merge(reduction._clip(
        [(s, e) for _, s, e in ev.ops if e > lo and s < hi], lo, hi))
    idle = reduction._complement(busy, lo, hi)
    return totals([sp for sp in program if sp[1] >= lo and sp[2] <= hi],
                  _intersect(idle, reduction.merge(calls)))


def _same(a, b) -> bool:
    return (a.window_s, a.busy_s, a.calls, a.idle_in_calls_s) == (
        b.window_s, b.busy_s, b.calls, b.idle_in_calls_s)


def report(t: Totals, calls: int) -> str:
    """Per call: each span's count, self time and the device idle while it
    was the innermost open span; the idle no span covers; the summed
    attributes; and how the spans nest (span < enclosing span: count)."""
    per_call = 1e3 / calls
    parts = [f"{name} n {v.count / calls:.2f} self {v.self_s * per_call:.6f} "
             f"ms idle {v.idle_s * per_call:.6f} ms"
             for name, v in sorted(t.by_name.items(), key=lambda kv: -kv[1].self_s)]
    attrs = ", ".join(f"{name}.{k} {n / calls:g}"
                      for name, v in sorted(t.by_name.items())
                      for k, n in sorted(v.attrs.items()))
    nesting = ", ".join(f"{child} < {parent}: {n}" for (child, parent), n in
                        sorted(t.parents.items(), key=str))
    return (f"[bench] spans per call: {'; '.join(parts)}; unattributed idle "
            f"{t.unattributed_idle_s * per_call:.6f} ms; attributes per call "
            f"{attrs}; nesting {nesting}")


def _median_call_s(ev) -> float:
    """The median traced call, ``bench.call`` start to its ``bench.wait``
    end (host clock, profiler on)."""
    calls = sorted(s for n, s, _ in ev.spans if n == reduction.CALL)
    waits = sorted(e for n, _, e in ev.spans if n == reduction.WAIT)
    lat = sorted(e - s for s, e in zip(calls, waits))
    return lat[len(lat) // 2] * 1e-9 if lat else 0.0


#: the last reading ``of_reading`` was asked for, and its answer.
_last: tuple = (None, None)


def of_reading(r) -> Totals | None:
    """The program spans of the trace ``r`` was reduced from, or ``None``
    where no trace under ``TRACES`` reads as ``r`` or it holds none."""
    global _last
    if _last[0] is r:
        return _last[1]
    from jax.profiler import ProfileData
    found = None
    paths = sorted(TRACES.glob("*/plugins/profile/*/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime, reverse=True)
    for path in paths:
        profile = ProfileData.from_file(str(path))
        ev = reduction.events_from_profile(profile)
        again = reduction.reduce(ev)
        if again is not None and _same(again, r):
            found = in_window(ev, from_profile(profile))
            if found is not None:
                print(f"{report(found, r.calls)}; traced calls median "
                      f"{_median_call_s(ev) * 1e3:.6f} ms",
                      file=sys.stderr, flush=True)
            break
    _last = (r, found)
    return found
