"""The Mozart runtime facade: contexts, configuration, evaluation.

A ``MozartContext`` owns a dataflow graph (libmozart), a planner, and an
executor configuration.  ``evaluate()`` converts pending annotated calls into
stages and runs them (paper Figure 2).  Contexts nest; ``mozart.session``
is the user-facing way to scope configuration:

    with mozart.session(executor="scan"):
        out = bs.black_scholes(price, strike, ...)   # lazy
        print(out.value)                             # forces evaluation
"""

from __future__ import annotations

import collections
import contextlib
import os
import threading
import weakref
from typing import Any

from repro import hardware
from repro.core import resilience
from repro.core import split_types as st
from repro.core.future import Future
from repro.core.graph import DataflowGraph, NodeRef
from repro.core.planner import plan
from repro.core.resilience import inject_faults  # noqa: F401  (mozart.inject_faults)
from repro.core.stage_exec import BoundaryCounters, counter_scope, get_executor
from repro.core.trace import span


class MozartContext:
    def __init__(
        self,
        executor: str = "pipelined",
        chip: hardware.Chip = hardware.TARGET,
        mesh=None,
        data_axes: tuple[str, ...] = ("data",),
        lazy: bool = True,
        pedantic: bool = False,
        batch_elements: int | None = None,
        inner_executor: str = "fused",
        pipeline: bool = True,
        plan_cache: bool = True,
        autotune: bool = True,
        plan_cache_path: str | None = None,
        handoff: bool = True,
        rewrite: bool = True,
    ):
        self.executor = executor
        self.chip = chip
        self.mesh = mesh
        self.data_axes = data_axes
        self.lazy = lazy
        self.pedantic = pedantic
        self.batch_elements = batch_elements
        self.inner_executor = inner_executor    # per-shard strategy for "sharded"
        self.pipeline = pipeline                 # False: Table-4 "-pipe" ablation
        self.plan_cache = plan_cache             # reuse plans across evaluations
        self.autotune = autotune                 # measure+pin chunk sizes on cached plans
        self.handoff = handoff                   # cross-stage chunk handoff (core/handoff.py)
        self.rewrite = rewrite                   # static graph rewrite pass (core/rewrite.py)
        # Persist plans/tuned batches/executor choices across processes.  The
        # MOZART_PLAN_CACHE env var pre-warms every context (serving replicas
        # restart with pinned plans: zero planner calls, zero tuning runs).
        if plan_cache_path is None:
            plan_cache_path = os.environ.get("MOZART_PLAN_CACHE") or None
        self.plan_cache_path = plan_cache_path
        self.graph = DataflowGraph()
        self.stats: collections.Counter = collections.Counter()
        #: this context's scoped trace/boundary-traffic view — concurrent
        #: sessions never pollute each other's gates (stage_exec).
        self.counters = BoundaryCounters()
        self._plan_entry = None                  # active plan_cache.PlanEntry
        self._handoff = None                     # active handoff decisions
        self._batch_override: int | None = None  # set by the auto-tuner only
        self._n_cap: int | None = None           # set during sampled tuning only
        self._entry_keys: set = set()            # cache keys this context used
        self._last_rewrites: list = []           # RewriteRecords of the last plan
        if self.plan_cache_path:
            from repro.core.plan_cache import load_once
            load_once(self.plan_cache_path)

    # -- libmozart register() -------------------------------------------------
    def register_call(self, fn, bound: dict[str, Any]) -> Future:
        avals: dict[str, Any] = {}
        ctor_bound: dict[str, Any] = {}
        stored: dict[str, Any] = {}
        for name, v in bound.items():
            if isinstance(v, Future):
                node = v._node
                avals[name] = node.out_aval
                ctor_bound[name] = node.out_aval     # ctors may read .shape
                stored[name] = NodeRef(node.id)
            else:
                avals[name] = v
                ctor_bound[name] = v
                stored[name] = v

        # Dynamic-shape functions (and consumers of their outputs) cannot be
        # abstractly evaluated; they run un-jitted per chunk (paper: filters).
        if getattr(fn.sa, "dynamic", False) or any(a is None for a in avals.values()):
            out_aval = None
        else:
            out_aval = fn.abstract_eval(avals)
        arg_types, out_type = fn.construct_types(ctor_bound, avals, out_aval)
        node = self.graph.register(fn, stored, arg_types, out_type, out_aval)
        fut = Future(self, node)
        node.future_ref = weakref.ref(fut)
        self.stats["registered"] += 1
        return fut

    # -- libmozart evaluate() ---------------------------------------------------
    def evaluate(self) -> None:
        pending = self.graph.pending()
        if not pending:
            return
        from repro.core.handoff import resolve_decisions
        from repro.core.plan_cache import lookup_or_plan
        with span("mozart.evaluate") as evaluate_span:
            stages, entry = lookup_or_plan(pending, self.graph, self)
            self.stats["evaluations"] += 1
            evaluate_span.set_metadata(stages=len(stages))
            # Handoff decisions: replayed from the cache entry (zero analysis
            # on warm calls); uncacheable pipelines analyze fresh per
            # evaluation.
            ho = resolve_decisions(self, entry, stages)
            # Save/restore (not clear): a dynamic node forcing a Future of
            # this same session re-enters evaluate(), and the outer plan's
            # entry must survive the nested call.
            prev_entry, prev_ho = self._plan_entry, self._handoff
            self._plan_entry = entry
            self._handoff = ho
            try:
                # Dispatch PER STAGE: under ``executor="auto"`` each stage is
                # scored and routed independently (cost_model.AutoExecutor).
                # Trace/boundary events attribute to THIS context's counters
                # (plus the process-global aggregate) for the duration.
                # ``resilience.run_stage`` arms the degradation ladder: a
                # failing executor is quarantined and the stage completes on
                # a lower rung.
                with counter_scope(self.counters):
                    for s in stages:
                        resilience.run_stage(self.executor, s, self.graph,
                                             self)
            finally:
                self._plan_entry, self._handoff = prev_entry, prev_ho
        self.graph.prune()

    def last_plan(self):
        """Plan (without executing) — used by tests and EXPLAIN tooling."""
        return plan(self.graph.pending(), self.graph,
                    max_stage_nodes=None if self.pipeline else 1)


_tls = threading.local()


def _stack() -> list[MozartContext]:
    if not hasattr(_tls, "stack"):
        _tls.stack = [MozartContext()]      # paper behaviour: lazy by default
    return _tls.stack


def current_context() -> MozartContext | None:
    s = _stack()
    return s[-1] if s else None


def configure(**kwargs) -> MozartContext:
    """Reconfigure the innermost context (flushes pending work first).

    Plan-cache-aware: when a knob that is part of the plan-cache key changes
    (executor, chip, mesh/data_axes, pipeline), the entries THIS context has
    used are re-keyed (copied) to the new configuration so the next
    evaluation hits the cache instead of replanning — see
    ``plan_cache.rekey_config``.  Scoped to this context's own entries:
    other sessions and compiled Pipelines sharing the old configuration keep
    their entries and pinned executables untouched."""
    ctx = current_context()
    if ctx is None:
        if kwargs:
            raise AttributeError("no active Mozart context to configure")
        return ctx
    ctx.evaluate()
    from repro.core import plan_cache as _pc
    old_prefix = _pc.context_key_prefix(ctx)
    for k, v in kwargs.items():
        if not hasattr(ctx, k):
            raise AttributeError(f"unknown Mozart option {k!r}")
        setattr(ctx, k, v)
    new_prefix = _pc.context_key_prefix(ctx)
    if old_prefix != new_prefix and getattr(ctx, "plan_cache", True):
        ctx.stats["configure_rekeyed"] += _pc.rekey_config(
            old_prefix, new_prefix, only_keys=ctx._entry_keys)
    return ctx


@contextlib.contextmanager
def session(**kwargs):
    """Scope a Mozart configuration (paper-style usage).

    Implemented on top of the AOT pipeline API: a session is an anonymous
    :class:`repro.core.pipeline.Pipeline`'s ``scope()`` — the same context,
    evaluation flush and plan persistence drive both entry points."""
    from repro.core.pipeline import Pipeline
    with Pipeline(None, **kwargs).scope() as ctx:
        yield ctx


def pipeline(fn=None, **config):
    """AOT entry point: ``mozart.pipeline(fn, ...)`` with an explicit
    ``lower → compile → call`` lifecycle.  See ``repro.core.pipeline``."""
    from repro.core.pipeline import pipeline as _pipeline
    return _pipeline(fn, **config)


def evaluate() -> None:
    ctx = current_context()
    if ctx is not None:
        ctx.evaluate()


def verify(target=None, *args, **kwargs):
    """``mozart.verify()``: lint every registered split annotation, or
    ``mozart.verify(fn, *args, executor=...)``: trace one pipeline and run
    the dataflow analyzer over its plan.  Returns an
    ``analysis.Report``; see ``repro.core.analysis`` for the MZ codes."""
    from repro.core import analysis
    return analysis.verify(target, *args, **kwargs)
