"""Runtime-wide failure domains: fault injection, degradation, retries.

The paper's promise is that Mozart optimizes *unmodified* library functions
while "respecting each function's correctness constraints" — which must
include the constraint of returning a correct answer when something breaks.
An intrusive IR (Weld) controls failure semantics inside the IR; an
annotation-based runtime proves instead that it can DEGRADE: fall down the
executor ladder, retry at chunk granularity, and shed serving load, without
ever returning a wrong result.  This module is the one place that policy
lives; the boundaries it guards call in from ``stage_exec``, ``executor``,
``cost_model``, ``plan_cache``, ``pipeline`` and ``serving``.

Three legs:

1. **Deterministic fault injection.**  ``MOZART_FAULTS=<spec>`` (or
   ``mozart.inject_faults(spec)`` as a context manager) arms failures at
   named boundaries — ``split``, ``chunk`` (drive), ``merge``, ``ingest``
   (handoff), ``compile`` (executor driver build), ``persist`` (plan-cache
   save), ``serve_step`` (batcher step).  Each armed spec fires a bounded
   number of times and then disarms, so every recovery path is testable and
   CI-gated with *exact* reproducibility: same spec, same crossing order,
   same failures.  Fired faults (and every recovery action) are recorded as
   MZ4xx events in the ``core/analysis.py`` vocabulary.

2. **Graceful degradation.**  ``run_stage`` is the stage-dispatch wrapper:
   when an executor raises a recoverable error at compile or drive time it
   demotes along ``DEGRADE_ORDER`` (pallas → scan/fused → pipelined →
   eager) until the stage completes, quarantines the broken choice in the
   plan entry (persisted — warm calls and restarted processes skip it) and
   ages the quarantine so the executor is eventually retried.  A kernel
   the compiler refuses (``KernelRefused``) is not recoverable: it
   propagates, so a run never reports one path while running another.
   Chunk-loop resource exhaustion is handled below the ladder:
   ``core/executor.py`` halves the chunk batch with bounded retries and
   re-pins the surviving size into the tuner state.

3. **Shared error taxonomy.**  ``TRANSIENT_ERRORS`` / ``PROBE_ERRORS``
   replace the runtime's bare ``except Exception`` swallows: probe/measure
   sites catch exactly the classes a library call can legitimately raise
   for "unavailable here" (never programming errors), and every swallow is
   counted (``stats["swallowed_errors"]``) so it is observable.  The
   seed-era ``repro.runtime.fault`` helpers (``with_retries``,
   ``StepTimer``, ``run_with_restarts``) live here now, on the same
   taxonomy and backoff policy; ``repro.runtime.fault`` re-exports them.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import logging
import os
import threading
import time
from typing import Any, Callable

log = logging.getLogger("repro.resilience")

__all__ = [
    "BOUNDARIES", "DEGRADE_ORDER", "FaultPlan", "FaultSpec", "InjectedFault",
    "InjectedResourceExhausted", "KernelRefused", "PROBE_ERRORS",
    "QUARANTINE_TTL", "StepFailure", "StepTimer", "FaultConfig",
    "TRANSIENT_ERRORS", "clear_events", "events", "inject_faults",
    "is_resource_exhausted", "maybe_fail", "note_swallowed", "record_event",
    "run_stage", "run_with_restarts", "stats", "with_retries",
]


# ---------------------------------------------------------------------------
# Error taxonomy
# ---------------------------------------------------------------------------


class StepFailure(RuntimeError):
    """A training/serving step failed after exhausting its retries."""


class InjectedFault(RuntimeError):
    """A deterministic fault armed by a :class:`FaultPlan` fired."""


class InjectedResourceExhausted(InjectedFault):
    """Injected stand-in for an XLA RESOURCE_EXHAUSTED / host MemoryError."""


class KernelRefused(Exception):
    """The compiler refused to lower or compile a kernel launch.

    A deterministic fault, not a transient one: the same launch is refused
    every time.  It derives from neither ``TRANSIENT_ERRORS`` nor
    ``PROBE_ERRORS``, so no handler here catches it — the ladder does not
    demote around it, the chunk loop does not halve for it, and the tuner
    and ``auto`` do not swallow it.  It propagates to the caller."""


#: errors a *retry* can plausibly fix: infrastructure/runtime failures
#: (XLA's XlaRuntimeError is a RuntimeError subclass), host I/O, memory
#: pressure.  ``TimeoutError``/``ConnectionError`` are OSError subclasses.
#: Programming errors (NameError, AttributeError, AssertionError) and
#: control-flow exceptions (KeyboardInterrupt, SystemExit) are deliberately
#: NOT here — retrying those hides bugs.
TRANSIENT_ERRORS: tuple = (RuntimeError, OSError, MemoryError)

#: errors a *probe* of one candidate/path may legitimately raise for "not
#: available on this input" — the transient classes plus the shape/dtype
#: rejections a library call makes before doing any work.  This is the
#: narrow replacement for the runtime's former bare ``except Exception``
#: swallows (tuner samples, cost-model measurement, fast-path equality,
#: best-effort device syncs).
PROBE_ERRORS: tuple = TRANSIENT_ERRORS + (
    ValueError, TypeError, ArithmeticError, NotImplementedError)


def is_resource_exhausted(e: BaseException) -> bool:
    """Whether ``e`` is memory pressure (halve the chunk batch and retry)
    rather than a generic failure (demote down the executor ladder)."""
    if isinstance(e, (MemoryError, InjectedResourceExhausted)):
        return True
    msg = str(e)
    return "RESOURCE_EXHAUSTED" in msg or "Out of memory" in msg


#: process-global resilience counters (benchmarks and tests read these;
#: per-session counts additionally land in ``ctx.stats``).
stats: collections.Counter = collections.Counter()

_stats_lock = threading.Lock()


def note_swallowed(where: str, e: BaseException, ctx=None) -> None:
    """Count a deliberately swallowed transient error so it is observable
    (the satellite fix for the former invisible ``except Exception`` sites)."""
    with _stats_lock:
        stats["swallowed_errors"] += 1
        stats[f"swallowed:{where}"] += 1
    if ctx is not None:
        ctx.stats["swallowed_errors"] += 1
    record_event("MZ406", f"{where}: {type(e).__name__}: {e}",
                 severity="info")


# ---------------------------------------------------------------------------
# Event log (MZ4xx records)
# ---------------------------------------------------------------------------

_EVENT_CAP = 512
_events: collections.deque = collections.deque(maxlen=_EVENT_CAP)


def record_event(code: str, where: str, severity: str = "warning") -> None:
    """Append one MZ4xx record (code, where) to the bounded process log and
    bump its counter.  Records become ``analysis.Diagnostic``s on demand
    (``events()``) — this path must not import the verifier."""
    with _stats_lock:
        stats[code] += 1
    _events.append((code, severity, where))


def events() -> list:
    """The recorded MZ4xx events as ``analysis.Diagnostic``s (most recent
    last)."""
    from repro.core.analysis import CODES, Diagnostic
    return [Diagnostic(code, sev, where, CODES.get(code, code))
            for code, sev, where in list(_events)]


def clear_events() -> None:
    """Reset the event log and the resilience counters (tests)."""
    _events.clear()
    with _stats_lock:
        stats.clear()


# ---------------------------------------------------------------------------
# Leg 1: deterministic fault injection
# ---------------------------------------------------------------------------

#: the named boundaries ``maybe_fail`` guards, in pipeline order.
BOUNDARIES = ("split", "chunk", "merge", "ingest", "compile", "persist",
              "serve_step")


@dataclasses.dataclass
class FaultSpec:
    """One armed failure: fire ``count`` times at ``boundary`` crossings
    whose ``where`` string contains ``match`` (empty = every crossing),
    after skipping the first ``after`` matching crossings."""

    boundary: str
    kind: str = "fail"                   # "fail" | "oom"
    count: int = 1
    match: str = ""
    after: int = 0

    def __post_init__(self) -> None:
        if self.boundary not in BOUNDARIES:
            raise ValueError(
                f"unknown fault boundary {self.boundary!r}; "
                f"known: {BOUNDARIES}")
        if self.kind not in ("fail", "oom"):
            raise ValueError(f"unknown fault kind {self.kind!r}")


class FaultPlan:
    """A set of armed :class:`FaultSpec`s with deterministic firing order.

    Firing is a pure function of the sequence of boundary crossings: each
    spec skips its first ``after`` matching crossings, then fires on the
    next ``count`` and disarms.  No randomness — the registry is seedable
    only in the sense that the *spec* decides everything, so a failing CI
    run reproduces exactly from its ``MOZART_FAULTS`` value."""

    def __init__(self, specs: list[FaultSpec]):
        self.specs = list(specs)
        self.fired: list[tuple[str, str]] = []      # (boundary, where)
        self._lock = threading.Lock()

    def check(self, boundary: str, where: str) -> None:
        armed = None
        with self._lock:
            for spec in self.specs:
                if spec.boundary != boundary or spec.count <= 0:
                    continue
                if spec.match and spec.match not in where:
                    continue
                if spec.after > 0:
                    spec.after -= 1
                    continue
                spec.count -= 1
                armed = spec
                self.fired.append((boundary, where))
                break
        if armed is None:
            return
        record_event("MZ401", f"{boundary} @ {where} (kind={armed.kind})")
        if armed.kind == "oom":
            raise InjectedResourceExhausted(
                f"injected RESOURCE_EXHAUSTED at {boundary} ({where})")
        raise InjectedFault(f"injected fault at {boundary} ({where})")

    @classmethod
    def parse(cls, spec: str) -> "FaultPlan":
        """Parse a ``MOZART_FAULTS`` spec string.

        Comma-separated entries ``boundary[:kind[:count[:match]]]``, e.g.
        ``compile:fail:1`` (first driver build fails),
        ``chunk:oom:2`` (first two chunk drives hit injected OOM),
        ``merge:fail:1:stage 0`` (first merge whose location names stage 0).
        An entry may append ``+N`` to the count to skip N crossings first:
        ``chunk:fail:1+3`` fires on the 4th crossing only."""
        specs = []
        for raw in spec.split(","):
            raw = raw.strip()
            if not raw:
                continue
            parts = raw.split(":", 3)
            boundary = parts[0]
            kind = parts[1] if len(parts) > 1 and parts[1] else "fail"
            count_s = parts[2] if len(parts) > 2 and parts[2] else "1"
            match = parts[3] if len(parts) > 3 else ""
            after = 0
            if "+" in count_s:
                count_s, after_s = count_s.split("+", 1)
                after = int(after_s)
            specs.append(FaultSpec(boundary, kind, int(count_s or 1),
                                   match, after))
        return cls(specs)


_active_plan: FaultPlan | None = None
_env_spec_seen: str | None = None


def _plan() -> FaultPlan | None:
    """The active plan: an explicit ``inject_faults`` install wins; else the
    ``MOZART_FAULTS`` env var (parsed once per distinct value, so a spent
    plan stays spent — deterministic counts, not per-read re-arming)."""
    global _active_plan, _env_spec_seen
    if _active_plan is not None:
        return _active_plan
    spec = os.environ.get("MOZART_FAULTS", "")
    if not spec:
        return None
    if spec != _env_spec_seen:
        _env_spec_seen = spec
        _active_plan = FaultPlan.parse(spec)
    return _active_plan


@contextlib.contextmanager
def inject_faults(spec: "str | FaultPlan"):
    """``mozart.inject_faults("chunk:oom:1")``: arm a fault plan for the
    duration of the ``with`` block; yields the plan so callers can inspect
    ``plan.fired`` afterwards.  Nesting replaces (the inner plan wins) and
    restores on exit."""
    global _active_plan
    plan = FaultPlan.parse(spec) if isinstance(spec, str) else spec
    prev = _active_plan
    _active_plan = plan
    try:
        yield plan
    finally:
        _active_plan = prev


def clear_faults() -> None:
    """Disarm everything, including an env-armed plan (tests)."""
    global _active_plan, _env_spec_seen
    _active_plan = None
    _env_spec_seen = os.environ.get("MOZART_FAULTS", "")


def maybe_fail(boundary: str, where: str = "") -> None:
    """The instrumented-boundary hook: a no-op (one global read) unless a
    plan is armed for ``boundary``."""
    plan = _plan()
    if plan is not None:
        plan.check(boundary, where)


# ---------------------------------------------------------------------------
# Leg 2: the executor degradation ladder
# ---------------------------------------------------------------------------

#: demotion order: on failure of an executor, the ladder continues from the
#: position after it — progressively fewer moving parts, ending at the
#: un-annotated library baseline which cannot be demoted further.  (Distinct
#: from ``cost_model.CANDIDATE_ORDER``, which is a *preference* order for
#: scoring; this is a *simplification* order for recovery.)
DEGRADE_ORDER = ("pallas", "sharded", "scan", "fused", "pipelined", "eager")

#: warm calls a quarantined executor sits out before it is retried — the
#: aging that keeps one transient compile failure from banning a strategy
#: forever.  Override per process with ``MOZART_QUARANTINE_TTL``.
QUARANTINE_TTL = int(os.environ.get("MOZART_QUARANTINE_TTL", "32"))


def demotion_ladder(name: str) -> list[str]:
    """Executors to try, in order, after ``name`` failed.  Unknown names
    (custom registrations, "auto") restart the ladder from the top minus
    the failed name; known names continue strictly downward."""
    if name in DEGRADE_ORDER:
        i = DEGRADE_ORDER.index(name)
        return list(DEGRADE_ORDER[i + 1:])
    return [n for n in DEGRADE_ORDER if n != name]


def _stage_retry_safe(ctx) -> bool:
    """A failed stage execution may be re-driven only if it has not already
    really donated chunk buffers to a driver (re-reading a donated chunk
    returns freed memory).  Donation marks are applied post-loop
    (``mark_stream_consumed``), so mid-loop failures leave streams intact —
    but a *successful* donate-then-fail-later sequence inside one attempt is
    detected via the per-attempt donation counter snapshot the caller
    takes."""
    return True   # the per-attempt check lives in run_stage via stats deltas


def run_stage(name: str, stage, graph, ctx, _tick: bool = True) -> None:
    """Dispatch one stage with the degradation ladder armed.

    The stage-dispatch sites (``runtime.evaluate``, the Pipeline build/fast
    paths, ``AutoExecutor``'s delegate) call this instead of
    ``get_executor(name).run``.  On a recoverable failure the stage is
    re-driven by the next executor down ``DEGRADE_ORDER``; the broken
    choice is quarantined in the plan entry (persisted — warm calls skip
    it) with TTL aging so it is eventually retried.  Unrecoverable errors
    (programming errors, sanitizer trips) propagate unchanged."""
    from repro.core.stage_exec import get_executor

    entry = getattr(ctx, "_plan_entry", None)
    blocked: set = set()
    if entry is not None:
        blocked = (entry.tick_quarantine(stage.id, QUARANTINE_TTL)
                   if _tick else entry.quarantined_execs(stage.id))

    first = name
    if name in blocked:
        # The requested executor is quarantined for this stage: skip straight
        # to the first healthy rung below it (counted, evented).
        for alt in demotion_ladder(name):
            if alt not in blocked:
                first = alt
                break
        ctx.stats["exec_quarantine_skips"] += 1
        record_event("MZ404", f"stage {stage.id}: {name} quarantined, "
                              f"dispatching {first}", severity="info")

    donated_before = ctx.stats.get("donated_chunks", 0)
    try:
        get_executor(first).run(stage, graph, ctx)
        return
    except PROBE_ERRORS as e:
        if first == "auto":
            # AutoExecutor's own delegate dispatch already runs this ladder
            # (with the pinned choice quarantined); an error escaping it
            # means every rung failed — re-laddering here would only repeat
            # the walk.
            raise
        last = e
        if not _recoverable(e, ctx, donated_before):
            raise

    failed = first
    for alt in demotion_ladder(first):
        if alt in blocked:
            continue
        if entry is not None:
            entry.quarantine_exec(stage.id, failed)
            record_event("MZ404", f"stage {stage.id}: quarantined {failed} "
                                  f"({type(last).__name__}: {last})")
        ctx.stats["exec_demotions"] += 1
        ctx.stats[f"exec_demoted_to_{alt}"] += 1
        record_event("MZ402", f"stage {stage.id}: {failed} -> {alt} "
                              f"({type(last).__name__})")
        log.warning("stage %s: executor %s failed (%s); demoting to %s",
                    stage.id, failed, last, alt)
        donated_before = ctx.stats.get("donated_chunks", 0)
        try:
            get_executor(alt).run(stage, graph, ctx)
            return
        except PROBE_ERRORS as e:
            last = e
            if not _recoverable(e, ctx, donated_before):
                raise
            failed = alt
    raise last


def _recoverable(e: BaseException, ctx, donated_before: int) -> bool:
    """Whether a failed stage attempt may be re-driven by another executor.

    Sanitizer trips are invariant violations, never demoted around; and an
    attempt that already really donated chunk buffers must not be re-driven
    (the donated chunks are freed — re-reading them is undefined)."""
    from repro.core.stage_exec import SanitizerError
    if isinstance(e, SanitizerError):
        return False
    if ctx.stats.get("donated_chunks", 0) != donated_before:
        return False
    return True


# ---------------------------------------------------------------------------
# Leg 2b: chunk-granular OOM policy (used by core/executor.py)
# ---------------------------------------------------------------------------

#: bounded halvings of the chunk batch on resource exhaustion before the
#: failure propagates (to the ladder, which demotes executors).
MAX_OOM_HALVINGS = 4


# ---------------------------------------------------------------------------
# Leg 3 helpers + absorbed seed-era fault tolerance (runtime/fault.py)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class FaultConfig:
    max_retries_per_step: int = 2
    max_restarts: int = 3
    #: straggler watchdog: a step slower than median * factor is flagged
    straggler_factor: float = 3.0
    straggler_window: int = 20
    min_steps_for_baseline: int = 5
    #: base sleep between retries; attempt ``i`` backs off ``base * 2**i``
    backoff_s: float = 0.0


class StepTimer:
    """Rolling per-step wall-clock stats + straggler flagging.

    On a real fleet ``on_straggler`` triggers re-slicing or pod eviction; on
    this container it logs — the control flow is identical and unit-tested
    (tests/test_resilience.py), only the actuator differs."""

    def __init__(self, cfg: FaultConfig,
                 on_straggler: Callable[[int, float, float], None] | None = None):
        self.cfg = cfg
        self.times: list[float] = []
        self.stragglers: list[int] = []
        self.on_straggler = on_straggler

    def record(self, step: int, seconds: float) -> bool:
        """Returns True if this step is a straggler vs the rolling median."""
        window = self.times[-self.cfg.straggler_window:]
        is_straggler = False
        if len(window) >= self.cfg.min_steps_for_baseline:
            med = sorted(window)[len(window) // 2]
            if seconds > med * self.cfg.straggler_factor:
                is_straggler = True
                self.stragglers.append(step)
                with _stats_lock:
                    stats["stragglers"] += 1
                log.warning("step %d took %.3fs (median %.3fs): straggler",
                            step, seconds, med)
                if self.on_straggler:
                    self.on_straggler(step, seconds, med)
        self.times.append(seconds)
        return is_straggler


def with_retries(fn: Callable[[], Any], *, retries: int,
                 on_retry: Callable[[int, Exception], None] | None = None,
                 backoff_s: float = 0.0) -> Any:
    """Run ``fn``; retry the shared transient classes with exponential
    backoff (the paper-world analogue of a preempted host re-issuing a
    step).  Non-transient errors propagate immediately."""
    last: Exception | None = None
    for attempt in range(retries + 1):
        try:
            return fn()
        except TRANSIENT_ERRORS as e:
            last = e
            with _stats_lock:
                stats["step_retries"] += 1
            log.warning("step attempt %d failed: %s", attempt, e)
            if on_retry:
                on_retry(attempt, e)
            if backoff_s and attempt < retries:
                time.sleep(backoff_s * (2 ** attempt))
    raise StepFailure(f"exhausted {retries} retries") from last


def run_with_restarts(
    make_state: Callable[[int | None], tuple[Any, int]],
    run_from: Callable[[Any, int], Any],
    *,
    fault_cfg: FaultConfig,
    latest_step: Callable[[], int | None],
):
    """Full restart loop: build state (fresh or from the latest checkpoint),
    run; on a transient failure rebuild from the newest complete checkpoint
    and continue.  Returns the final result of ``run_from``.

    make_state(step|None) -> (state, start_step)
    run_from(state, start_step) -> result       (raises on fatal error)
    """
    restarts = 0
    while True:
        ckpt = latest_step()
        state, start = make_state(ckpt)
        try:
            return run_from(state, start)
        except TRANSIENT_ERRORS as e:       # restart boundary
            restarts += 1
            with _stats_lock:
                stats["restarts"] += 1
            log.error("run crashed at restart %d: %s", restarts, e)
            if restarts > fault_cfg.max_restarts:
                raise
            time.sleep(min(fault_cfg.backoff_s * (2 ** restarts), 2.0)
                       if fault_cfg.backoff_s else 0.1)
