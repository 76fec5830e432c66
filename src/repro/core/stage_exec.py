"""The StageExecutor subsystem: shared split → drive → merge machinery.

Every Mozart execution strategy follows the same three-phase shape from the
paper (§5.2): split the stage inputs into fast-memory-sized batches, drive
each batch through the unmodified library functions, and merge the partial
results associatively.  This module extracts that machinery into one base
class and a registry so that strategies are *pluggable*:

    @register_executor("my-strategy")
    class MyExecutor(StageExecutor):
        def execute(self, stage, concrete, ctx):
            ...split / drive / merge using the shared helpers...

``runtime.MozartContext.evaluate`` dispatches through ``get_executor`` — no
string ``if/elif`` chains.  The built-in strategies live in
``core/executor.py`` ("eager", "pipelined", "fused", "scan"),
``core/sharded.py`` ("sharded") and ``core/pallas_exec.py`` ("pallas") and
are registered as a side effect of importing those modules.

Batch sizing goes through ``StageExecutor.choose_batch`` which layers, in
priority order: an explicit per-context override (``batch_elements``), the
auto-tuner's pinned size for a cached plan (``core/plan_cache.py``), and the
paper's §5.2 fast-memory estimate (``hardware.mozart_batch_elements``).
"""

from __future__ import annotations

import collections
import contextlib
import os
import threading
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro import hardware
from repro.core import resilience
from repro.core import split_types as st
from repro.core.graph import DataflowGraph, Node, NodeRef
from repro.core.planner import Stage, _count_of_type, _value_key
from repro.core.trace import span


class PedanticError(RuntimeError):
    pass


def sanitize_active() -> bool:
    """True when ``MOZART_SANITIZE`` is set (and not "0"): the boundary
    sanitizer poisons donated chunk buffers, validates stream grids before
    ingest, and cross-checks scoped counters (codes MZ301/MZ302/MZ303,
    ``core/analysis.py``).  Read per call — tests flip it mid-process."""
    return os.environ.get("MOZART_SANITIZE", "") not in ("", "0")


class SanitizerError(RuntimeError):
    """A boundary invariant the sanitizer caught red-handed (MZ3xx)."""


class _PoisonedChunks(list):
    """Donated chunk list stand-in under MOZART_SANITIZE=1.

    Stays EMPTY (``len`` 0 keeps ``__repr__`` and consumed-first code paths
    benign) but any attempt to read a chunk out of it — iterating or
    indexing — raises with the donating stage/edge, instead of silently
    handing back buffers XLA has already reused."""

    def __init__(self, donor: str):
        super().__init__()
        self.donor = donor

    def _blow(self) -> None:
        raise SanitizerError(
            f"[MZ301] use-after-donate: chunk buffers were donated at "
            f"{self.donor or 'an unknown stage/edge'} and then read")

    def __getitem__(self, i):
        self._blow()

    def __iter__(self):
        self._blow()


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, type["StageExecutor"]] = {}
_INSTANCES: dict[str, "StageExecutor"] = {}


def register_executor(name: str) -> Callable[[type], type]:
    """Class decorator: make a StageExecutor reachable as ``executor=name``."""

    def deco(cls: type) -> type:
        cls.name = name
        _REGISTRY[name] = cls
        _INSTANCES.pop(name, None)
        return cls

    return deco


def _ensure_builtin_executors() -> None:
    # Importing these modules registers their executor classes.
    import repro.core.executor      # noqa: F401  (eager/pipelined/fused/scan)
    import repro.core.pallas_exec   # noqa: F401  (pallas)
    import repro.core.sharded       # noqa: F401  (sharded)
    import repro.core.cost_model    # noqa: F401  (auto)


def get_executor(name: str) -> "StageExecutor":
    if name not in _REGISTRY:
        _ensure_builtin_executors()
    try:
        cls = _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown executor {name!r}; registered: {sorted(_REGISTRY)}"
        ) from None
    inst = _INSTANCES.get(name)
    if inst is None or type(inst) is not cls:
        inst = _INSTANCES[name] = cls()
    return inst


def available_executors() -> tuple[str, ...]:
    _ensure_builtin_executors()
    return tuple(sorted(_REGISTRY))


# ---------------------------------------------------------------------------
# Runtime parameter discovery (paper §5.2 step 1)
# ---------------------------------------------------------------------------


def _info_arg(v: Any) -> Any:
    """What to hand a split type's ``info``: a handed-off ChunkStream stands
    in for its full value via its aval (same shapes/dtypes, and pytree avals
    flatten where the stream object itself would not)."""
    return v.aval if isinstance(v, ChunkStream) else v


def stage_num_elements(stage: Stage, concrete: dict[tuple, Any], pedantic: bool) -> int:
    counts = set()
    for key, si in stage.inputs.items():
        if not si.split_type.splittable:
            continue
        info = si.split_type.info(_info_arg(concrete[key]))
        if info is not None:
            counts.add(info.num_elements)
    if len(counts) > 1:
        raise PedanticError(f"stage {stage.id}: inputs disagree on element count: {counts}")
    return counts.pop() if counts else 1


def stage_elem_bytes(stage: Stage, concrete: dict[tuple, Any], n: int) -> int:
    """Σ sizeof(element) over live pipeline values (inputs + outputs)."""
    total = 0
    for key, si in stage.inputs.items():
        if not si.split_type.splittable:
            continue
        info = si.split_type.info(_info_arg(concrete[key]))
        if info is not None:
            total += info.elem_bytes
    for node in stage.nodes:
        t = stage.out_types[node.id]
        if t.splittable and node.out_aval is not None:
            leaves = jax.tree_util.tree_leaves(node.out_aval)
            nb = sum(st.nbytes_of(l) for l in leaves)
            total += max(nb // max(n, 1), 1)
    return total


def batch_ranges(n: int, batch: int) -> list[tuple[int, int]]:
    if n <= 0:
        # Empty splits: one degenerate chunk, so the chain still runs (on
        # zero-size slices) and merges produce the library's empty-input
        # result instead of crashing on an empty partial list.
        return [(0, 0)]
    return [(s, min(s + batch, n)) for s in range(0, n, batch)]


def effective_elements(ctx, n: int) -> int:
    """Stage element count, clamped during sampled tuning measurements.

    Split-type ``info`` reports the FULL value's element count (it reads the
    type's recorded geometry, not the concrete value), so executors running
    on a sliced sample must cap their chunk ranges explicitly."""
    cap = getattr(ctx, "_n_cap", None)
    return n if cap is None else min(n, cap)


# ---------------------------------------------------------------------------
# Trace + stage-boundary traffic accounting (scoped per execution context)
# ---------------------------------------------------------------------------

#: bounded trail of recent materialization events ``(kind, where, nbytes)``
#: — enough context for the smoke gate to NAME the offending boundary in a
#: diff-style message instead of failing on a bare byte count.
_EVENT_LIMIT = 256


class BoundaryCounters:
    """One scope's view of trace and stage-boundary traffic accounting.

    TRACES count jax traces of Mozart-built drivers and annotated library
    functions: the driver bodies call ``note_trace()`` as a Python side
    effect — it runs while jax is *tracing*, never on a compiled-cache hit,
    so the delta across a call counts exactly the (re)traces that call
    caused.  The zero-retrace guarantee of warm ``mozart.pipeline`` calls is
    asserted against this (tests/test_pipeline.py, the smoke gate).

    BOUNDARY BYTES split into two components.  INTERIOR bytes are the round
    trips the handoff subsystem exists to remove: merges of multi-chunk
    partials (``finish_stage``, ``SplitType.rechunk`` copies,
    materialize-on-ingest by a stream-incapable executor) plus bytes
    re-sliced when a stage splits a value that another stage produced.
    TERMINAL bytes are the lazy ``ChunkStream.materialize`` of an *observed*
    pipeline output (``Future.value`` forcing the merge) — inherent to
    observation, not a boundary round trip, and therefore accounted
    separately so gates never pass or fail for the wrong reason.  Splitting
    EXTERNAL pipeline inputs is counted by neither (that split is inherent
    to chunking).

    Every ``MozartContext`` owns one of these (``ctx.counters``): executor
    dispatch and terminal observation run inside ``counter_scope``, so two
    concurrent sessions/pipelines never pollute each other's gates.  The
    module-level functions below (``trace_count``, ``bytes_interior``, …)
    read the PROCESS-GLOBAL aggregate, which every event also updates —
    single-session callers and cross-session totals keep working unchanged.
    """

    __slots__ = ("traces", "interior", "terminal", "events")

    def __init__(self) -> None:
        self.traces = 0
        self.interior = 0
        self.terminal = 0
        self.events: "collections.deque[tuple[str, str, int]]" = \
            collections.deque(maxlen=_EVENT_LIMIT)

    # -- the same read surface as the module-level aggregate ----------------
    def trace_count(self) -> int:
        return self.traces

    def bytes_interior(self) -> int:
        return self.interior

    def bytes_terminal(self) -> int:
        return self.terminal

    def bytes_materialized(self) -> int:
        return self.interior + self.terminal

    def materialize_events(self) -> list[tuple[str, str, int]]:
        return list(self.events)

    def reset(self) -> None:
        self.traces = 0
        self.interior = 0
        self.terminal = 0
        self.events.clear()


#: the process-global aggregate: every note_* call lands here in addition to
#: whatever scopes are active.
_GLOBAL_COUNTERS = BoundaryCounters()

_scope_tls = threading.local()


def _scopes() -> list:
    s = getattr(_scope_tls, "stack", None)
    if s is None:
        s = _scope_tls.stack = []
    return s


@contextlib.contextmanager
def counter_scope(counters: "BoundaryCounters | None"):
    """Attribute trace/boundary events to ``counters`` for the duration.

    Scopes nest (a dynamic node re-entering ``evaluate`` keeps one
    attribution, not two: re-entering with a scope already active is a
    no-op), and distinct scopes stack — an outer session observing a value
    while an inner session runs each see only their own events.  Thread
    local; the process-global aggregate is always updated regardless."""
    if counters is None:
        yield
        return
    stack = _scopes()
    if any(c is counters for c in stack):
        yield                             # already attributed: no double count
        return
    stack.append(counters)
    snap = None
    if sanitize_active():
        with _counts_lock:
            snap = (counters.traces, counters.interior, counters.terminal,
                    _GLOBAL_COUNTERS.traces, _GLOBAL_COUNTERS.interior,
                    _GLOBAL_COUNTERS.terminal)
    clean = False
    try:
        yield
        clean = True
    finally:
        stack.remove(counters)
        if snap is not None and clean:
            # MZ303: every event lands on the global aggregate AND every
            # active scope under one lock, so a scope can never see MORE
            # than the global did over the same window.  (Other threads may
            # inflate the global side; that is fine and expected.)
            with _counts_lock:
                deltas = (
                    ("traces", counters.traces - snap[0],
                     _GLOBAL_COUNTERS.traces - snap[3]),
                    ("interior", counters.interior - snap[1],
                     _GLOBAL_COUNTERS.interior - snap[4]),
                    ("terminal", counters.terminal - snap[2],
                     _GLOBAL_COUNTERS.terminal - snap[5]),
                )
            for field, scoped, global_ in deltas:
                if scoped > global_:
                    raise SanitizerError(
                        f"[MZ303] scoped BoundaryCounters recorded more "
                        f"{field} ({scoped}) than the process-global "
                        f"aggregate ({global_}) over the same scope — "
                        "counter attribution is corrupt")


#: guards counter increments: concurrent pipelines (the serving scheduler's
#: pattern) must never lose an increment to a racing ``+=`` — a dropped
#: trace count would let a real retrace read as warm.
_counts_lock = threading.Lock()


def note_trace() -> None:
    with _counts_lock:
        _GLOBAL_COUNTERS.traces += 1
        for c in _scopes():
            c.traces += 1


def note_materialized(nbytes: int, terminal: bool = False,
                      kind: str = "merge", where: str = "") -> None:
    nbytes = int(nbytes)
    event = (("terminal:" if terminal else "interior:") + kind, where, nbytes)
    with _counts_lock:
        for c in (_GLOBAL_COUNTERS, *_scopes()):
            if terminal:
                c.terminal += nbytes
            else:
                c.interior += nbytes
            c.events.append(event)


def trace_count() -> int:
    """Process-global trace count (aggregate across every scope)."""
    return _GLOBAL_COUNTERS.traces


def bytes_materialized() -> int:
    """Total boundary bytes (interior + terminal), process-global."""
    return _GLOBAL_COUNTERS.bytes_materialized()


def bytes_interior() -> int:
    """Interior-boundary bytes only (must be 0 on a fully handed-off chain).
    Process-global; per-session gates read ``ctx.counters`` instead."""
    return _GLOBAL_COUNTERS.interior


def bytes_terminal() -> int:
    """Bytes merged lazily at *observed* terminal outputs only (global)."""
    return _GLOBAL_COUNTERS.terminal


def reset_materialized() -> None:
    """Zero the GLOBAL byte counters and drop its event trail (tests).
    Scoped counters are unaffected — reset those via ``ctx.counters.reset()``."""
    _GLOBAL_COUNTERS.interior = 0
    _GLOBAL_COUNTERS.terminal = 0
    _GLOBAL_COUNTERS.events.clear()


def materialize_events() -> list[tuple[str, str, int]]:
    """Recent ``(kind, where, nbytes)`` materialization events (global)."""
    return _GLOBAL_COUNTERS.materialize_events()


def _value_nbytes(v: Any) -> int:
    return sum(st.nbytes_of(l) for l in jax.tree_util.tree_leaves(v)
               if hasattr(l, "shape") or isinstance(l, (int, float, complex, bool)))


# ---------------------------------------------------------------------------
# ChunkStream: the unmerged stage-output value form (cross-stage handoff)
# ---------------------------------------------------------------------------


#: pinned message of the donated-stream late-merge backstop raise.  The
#: plan-time veto in ``handoff.analyze`` (observable producers never donate)
#: should make this unreachable; it stays as the runtime guard of last
#: resort and its text is asserted by tests/test_handoff.py.
DONATED_MERGE_ERROR = (
    "[MZ301] ChunkStream buffers were donated to a driver and can no longer "
    "be merged (handoff analysis bug: a donated stream was observed "
    "afterwards)")


class ChunkStream:
    """A stage output left as its chunk list + grid metadata.

    When every consumer of a node can ingest the producer's chunk grid
    directly (``core/handoff.py`` records the decision in the plan entry),
    ``finish_stage`` stores one of these instead of merging — the
    merge→re-split round trip at the stage boundary disappears.  The merge
    happens lazily, and only if the value is actually *observed* (a
    ``Future`` forces it, or a stream-incapable executor resolves it);
    ``materialize`` caches the merged value so it is paid at most once.

    Two storage forms share this class.  The chunk-LIST form holds one
    buffer per grid range (the chunk-loop executors' native output); a
    stream built by ``from_merged`` (the ``scan`` driver's output, which is
    already the whole value) is the same form with its merge done: a scan
    or pallas consumer reads the value whole with zero copies,
    ``materialize`` is free, and a chunk-loop consumer slices its chunk
    views from it (paying, and counting, one slice pass).  The SHARDED form
    (``from_sharded``) holds the sharded driver's device-resident global
    ``jax.Array`` plus its ``Sharding`` — one grid range per mesh shard — so
    a sharded→sharded boundary passes the global array straight through
    (zero interior bytes, no all-gather) and a chunk-loop consumer derives
    per-shard chunk views from ``addressable_shards`` without copying.
    """

    __slots__ = ("_chunks", "ranges", "split_type", "aval", "_merged",
                 "consumed", "donor", "sharded", "sharding")

    def __init__(self, chunks: list | None, ranges: list,
                 split_type: st.SplitType, aval: Any):
        self._chunks = list(chunks) if chunks is not None else None
        self.ranges = list(ranges)
        self.split_type = split_type
        self.aval = aval                   # full-value ShapeDtypeStruct pytree
        self._merged = None
        self.consumed = False              # chunk buffers donated to a driver
        self.donor = ""                    # "stage N input K" that donated them
        self.sharded = None                # device-resident global jax.Array
        self.sharding = None               # its jax.sharding.Sharding

    @classmethod
    def from_merged(cls, merged: Any, ranges: list,
                    split_type: st.SplitType, aval: Any) -> "ChunkStream":
        """Wrap a whole value (the scan driver's output) with the grid it
        was computed on; no chunk list is held until one is asked for."""
        s = cls(None, ranges, split_type, aval)
        s._merged = merged
        return s

    @classmethod
    def from_sharded(cls, sharded: Any, ranges: list,
                     split_type: st.SplitType, aval: Any,
                     sharding: Any) -> "ChunkStream":
        """Wrap the sharded driver's global array without gathering it.

        ``sharded`` is a device-resident ``jax.Array`` laid out by
        ``sharding`` along the stream's split axis; ``ranges`` is the
        per-shard grid (one range per mesh shard).  A sharded consumer with
        the same layout takes ``sharded`` as-is; any other consumer either
        derives the per-shard chunk views (``.chunks``, zero-copy) or
        materializes (counted ``interior:gather`` — the honest cost of
        leaving the mesh)."""
        s = cls(None, ranges, split_type, aval)
        s.sharded = sharded
        s.sharding = sharding
        return s

    # -- aval-like surface (batch sizing reads .shape/.dtype) ---------------
    @property
    def shape(self):
        return self.aval.shape

    @property
    def dtype(self):
        return self.aval.dtype

    @property
    def ndim(self):
        return len(self.aval.shape)

    @property
    def n(self) -> int:
        return self.ranges[-1][1] if self.ranges else 0

    def _axis(self) -> int:
        ax = split_axis_of(self.split_type)
        return 0 if ax is None else ax

    def _empty_value(self) -> Any:
        """A zero-element value shaped like the aval (zero-chunk streams)."""
        return jax.tree_util.tree_map(
            lambda a: jnp.zeros(a.shape, a.dtype), self.aval)

    @property
    def merged(self) -> Any:
        """The whole value when this stream already holds it (a scan
        driver's output, or a stream merged once), else None: free to read."""
        return self._merged

    @property
    def chunks(self) -> list:
        """The chunk list, deriving (and counting) it from a held value.

        A ``from_merged`` stream only pays this slice pass when a chunk-loop
        consumer actually iterates it; a scan or pallas consumer reads the
        value whole and the derivation never happens.  A sharded stream
        derives zero-copy per-shard views (``addressable_shards`` in grid
        order) — the buffers stay committed to their devices, so only
        shard-aware consumers may iterate them."""
        if self._chunks is None:
            if self.sharded is not None:
                ax = self._axis()
                shards = sorted(self.sharded.addressable_shards,
                                key=lambda sh: sh.index[ax].start or 0)
                self._chunks = [sh.data for sh in shards]
                return self._chunks
            self._chunks = [self.chunk(i) for i in range(len(self.ranges))]
        return self._chunks

    def chunk(self, i: int) -> Any:
        """Chunk ``i`` of the grid without deriving the whole list.

        Degenerate zero-element grids (``ranges == [(0, 0)]``) may carry no
        buffer at all; they resolve to an empty value built from the aval."""
        if self._chunks is None and self.sharded is not None:
            return self.chunks[i]          # zero-copy per-shard views
        if self._chunks is None and self._merged is not None:
            s, e = self.ranges[i]
            piece = self.split_type.split(self._merged, s, e)
            note_materialized(_value_nbytes(piece), kind="resplit",
                              where=f"stream chunk [{s},{e})")
            return piece
        if not self._chunks and self.n == 0:
            return self._empty_value()
        return self._chunks[i]

    def uniform_batch(self) -> int | None:
        """Chunk size when the grid is regular (ragged tail allowed)."""
        if not self.ranges:
            return None
        sizes = [e - s for s, e in self.ranges]
        body = sizes[:-1] or sizes
        return body[0] if len(set(body)) == 1 else None

    def compatible(self, consumer_type: st.SplitType) -> bool:
        return (not self.consumed
                and self.split_type.can_handoff(consumer_type))

    def materialize(self, terminal: bool = False) -> Any:
        """Merge (once) and return the full value; counts boundary bytes.

        ``terminal=True`` marks the merge as observation of a pipeline
        output (``Future.value``) — accounted under ``bytes_terminal`` so
        the interior-boundary gate never charges observation costs."""
        if self.consumed:
            # A held merged value may itself be the donated buffer.
            raise RuntimeError(
                DONATED_MERGE_ERROR
                + f" [donated at {self.donor or 'unknown stage/edge'}]")
        if self._merged is None:
            if self.sharded is not None:
                # The global array IS the merged value; returning it is free
                # NOW, but a non-mesh consumer forces XLA to gather/reshard
                # it on use — count that honestly as a "gather" event (the
                # sharded→sharded smoke gate asserts no interior:gather).
                self._merged = self.sharded
                note_materialized(_value_nbytes(self._merged),
                                  terminal=terminal, kind="gather",
                                  where=f"stream n={self.n} {self.split_type}")
                return self._merged
            if not self._chunks:
                # Zero-chunk stream (empty pipeline): merge([]) would crash
                # in the library's concat; the aval names the empty result.
                self._merged = self._empty_value()
            else:
                self._merged = self.split_type.merge(self._chunks)
            if len(self._chunks or ()) > 1:
                note_materialized(_value_nbytes(self._merged),
                                  terminal=terminal,
                                  kind="materialize",
                                  where=f"stream n={self.n} {self.split_type}")
        return self._merged

    def __repr__(self) -> str:
        if self.sharded is not None:
            form = f"sharded×{len(self.ranges)}"
        elif self._chunks is None and self._merged is not None:
            form = "merged"
        else:
            form = f"{len(self._chunks or ())} chunks"
        return f"ChunkStream({form}, n={self.n}, {self.split_type})"


def materialize(v: Any) -> Any:
    """ChunkStream -> merged value; anything else passes through."""
    return v.materialize() if isinstance(v, ChunkStream) else v


# ---------------------------------------------------------------------------
# Per-chunk chain driving (position-keyed)
# ---------------------------------------------------------------------------
#
# Chunk envs are keyed CANONICALLY — ``("in", input_position)`` for stage
# inputs and ``("n", node_position)`` for node outputs (``Stage.ckey``) —
# never by per-call node ids or value ids.  Two instantiations of the same
# plan template therefore produce envs with the identical pytree structure,
# which is what lets a pinned jitted driver from an earlier call accept this
# call's env without retracing.


def chunk_env_for(stage: Stage, concrete: dict[tuple, Any], s: int, e: int,
                  pedantic: bool, chunk_index: int | None = None,
                  force_slice: frozenset | tuple = ()) -> dict[tuple, Any]:
    """Build one chunk's canonical env.  ``force_slice`` lists canonical keys
    that must be REAL slices even for identity ranges — buffers about to be
    donated must never alias a producer's retained result."""
    resilience.maybe_fail("split", f"stage {stage.id} range [{s},{e})")
    env: dict[tuple, Any] = {}
    for key, si in stage.inputs.items():
        v = concrete[key]
        if isinstance(v, ChunkStream):
            # Handed-off input: chunk ``chunk_index`` of the producer's grid
            # IS this range's piece — no slice, no boundary traffic.
            env[stage.ckey(key)] = v.chunk(chunk_index)
            continue
        if si.split_type.splittable:
            if s == 0 and not pedantic and stage.ckey(key) not in force_slice:
                info = si.split_type.info(v)
                if info is not None and e == info.num_elements:
                    # Identity slice (single-chunk stage): pass the whole
                    # value through — no dispatch, no boundary traffic.
                    env[stage.ckey(key)] = v
                    continue
            piece = si.split_type.split(v, s, e)
            if isinstance(si.value, NodeRef):
                # Re-slicing another stage's merged output: the round trip
                # the handoff subsystem exists to remove.
                note_materialized(_value_nbytes(piece), kind="resplit",
                                  where=f"stage {stage.id} input {stage.ckey(key)}"
                                        f" range [{s},{e})")
            if pedantic and hasattr(piece, "shape") and 0 in piece.shape:
                raise PedanticError(f"empty split for {key} range [{s},{e})")
            env[stage.ckey(key)] = piece
        else:
            env[stage.ckey(key)] = v          # "_" values: pointer copy
    return env


def chain_plan(stage: Stage) -> tuple:
    """Capture-safe driving recipe for the stage chain.

    Per node: ``(fn, out_key, ((argname, env_key | None, static_value), ...),
    raw)``.  The plan holds only ``AnnotatedFn`` identities, static argument
    values and canonical env keys — no concrete call data and no ``Stage`` —
    so a jitted driver closed over it can be pinned in the plan cache and
    reused by every later instantiation of the same template without
    retaining the first call's input arrays.
    """
    cached = getattr(stage, "_chain_plan", None)
    if cached is not None:
        return cached
    steps = []
    for node in stage.nodes:
        srcs = []
        for name, v in node.bound.items():
            if name in node.fn.sa.static:
                srcs.append((name, None, v))
            else:
                srcs.append((name, stage.ckey(_value_key(v)), None))
        raw = getattr(node.fn.sa, "dynamic", False) or node.out_aval is None
        steps.append((node.fn, stage.out_key(node), tuple(srcs), raw))
    stage._chain_plan = tuple(steps)
    return stage._chain_plan


def run_plan(plan: tuple, env: dict[tuple, Any], jit_each: bool = False) -> None:
    """Drive one chunk env through every function of a chain plan in order."""
    for fn, out_key, srcs, raw in plan:
        kw = {name: (static if key is None else env[key])
              for name, key, static in srcs}
        if raw:
            res = fn.call_raw(kw)
        elif jit_each:
            res = fn.jitted(**kw)             # black-box library call
        else:
            res = fn.fn(**kw)                 # traced into enclosing jit
        env[out_key] = res


def run_chain(stage: Stage, env: dict[tuple, Any], jit_each: bool) -> None:
    """Drive one (canonically keyed) chunk env through the stage chain."""
    run_plan(chain_plan(stage), env, jit_each=jit_each)


def finish_stage(stage: Stage, partials: dict[int, list[Any]],
                 ranges: list[tuple[int, int]] | None = None,
                 ctx=None) -> None:
    """Merge per-chunk partials (keyed by stage-local node POSITION).

    With a handoff plan active (``ctx._handoff``), nodes whose every
    consumer accepts the producer grid are left UNMERGED as a
    :class:`ChunkStream` over ``ranges`` — the boundary merge happens lazily
    and only if the value is actually observed."""
    resilience.maybe_fail("merge", f"stage {stage.id}")
    ho = None
    if ctx is not None and ranges is not None:
        plan = getattr(ctx, "_handoff", None)
        ho = plan.get(stage.id) if plan else None
    for node in stage.nodes:
        p = stage.pos[node.id]
        if p in partials:
            t = stage.out_types[node.id]
            pieces = partials[p]
            if (ho is not None and p in ho.stream_out
                    and len(pieces) == len(ranges) and len(pieces) > 1):
                node.result = ChunkStream(pieces, ranges, t, node.out_aval)
                ctx.stats["streamed_outputs"] += 1
            else:
                node.result = t.merge(pieces)
                if len(pieces) > 1 and not isinstance(t, st.ScalarSplit):
                    note_materialized(_value_nbytes(node.result), kind="merge",
                                      where=f"stage {stage.id} node {p}")
        node.done = True


# ---------------------------------------------------------------------------
# Pinned compiled executables
# ---------------------------------------------------------------------------


def pinned_table(stage: Stage, ctx) -> dict:
    """The in-process table ``stage``'s pinned drivers (and decisions that
    hold for them) live in: the plan entry's executable table when the
    stage belongs to a cached plan, else the Stage instance itself."""
    entry = getattr(ctx, "_plan_entry", None)
    table = entry.exec_table() if entry is not None else None
    if table is None:
        table = getattr(stage, "_jit_cache", None)
        if table is None:
            table = stage._jit_cache = {}
    return table


def pinned_jit(stage: Stage, ctx, kind: str, extra_key: tuple,
               build: Callable[[], Callable]) -> Callable:
    """One compiled driver per (plan entry, stage position, kind, extra_key).

    When the stage belongs to a cached plan, the driver built by ``build()``
    is pinned into the plan cache's in-process executable table
    (``PlanEntry.exec_table``, keyed by the persisted fingerprint): every
    later instantiation of the same template — this session or any other —
    reuses the SAME callable, so warm calls hit jax's compile cache instead
    of retracing a fresh closure.  ``build`` must return a capture-safe
    callable (close over ``chain_plan``, never over the Stage or concrete
    values).  Without an entry (uncacheable pipeline) the driver is cached on
    the Stage instance, preserving same-call reuse (tuner candidates,
    warmup-then-time runs).
    """
    key = (stage.id, kind) + tuple(extra_key)
    table = pinned_table(stage, ctx)
    fn = table.get(key)
    if fn is None:
        resilience.maybe_fail("compile", f"stage {stage.id} {kind}")
        fn = table[key] = build()
        ctx.stats["exec_builds"] += 1
    return fn


def has_dynamic(stage: Stage) -> bool:
    return any(
        getattr(n.fn.sa, "dynamic", False) or n.out_aval is None
        for n in stage.nodes
    )


# ---------------------------------------------------------------------------
# Stream-aware input resolution (cross-stage handoff)
# ---------------------------------------------------------------------------


def adapt_stream(v: "ChunkStream", consumer: st.SplitType) -> "ChunkStream | None":
    """Reinterpret a fresh-output (ConcatSplit) stream under the consumer's
    concrete grid — the runtime half of the ConcatSplit→{ArraySplit,
    PytreeSplit} handoff rules.

    A ConcatSplit producer's piece sizes are unknowable at plan time, so the
    analysis only records that the conversion is *permitted*
    (``StageHandoff.convert_in``); here the sizes are read off the concrete
    chunk buffers, and when they tile the consumer's geometry exactly the
    SAME buffers are re-wrapped under the consumer's split type — zero
    copies.  An ArraySplit consumer requires single-leaf chunks; a
    PytreeSplit consumer accepts pytree chunks, deciding PER LEAF — every
    leaf of a chunk must agree on its split-axis extent for the chunk to
    contribute one grid range.  Returns None when the pieces do not form
    the consumer's grid (axis out of range, leaves disagree, total
    mismatch); the caller materializes instead, which is always correct."""
    if not isinstance(v.split_type, st.ConcatSplit):
        return None
    if v._chunks is None:              # merged ConcatSplit streams don't exist
        return None
    if isinstance(consumer, st.ArraySplit) and consumer.shape:
        ax, total = consumer.axis, consumer.shape[consumer.axis]
        sizes = []
        for c in v._chunks:
            leaves = jax.tree_util.tree_leaves(c)
            if len(leaves) != 1 or len(getattr(leaves[0], "shape", ())) <= ax:
                return None
            sizes.append(int(leaves[0].shape[ax]))
    elif isinstance(consumer, st.PytreeSplit):
        ax, total = consumer.axis, consumer.length
        sizes = []
        for c in v._chunks:
            leaf_sizes = set()
            for l in jax.tree_util.tree_leaves(c):
                shp = getattr(l, "shape", ())
                if len(shp) <= ax:
                    return None
                leaf_sizes.add(int(shp[ax]))
            if len(leaf_sizes) != 1:   # leaves disagree (or chunk is leafless)
                return None
            sizes.append(leaf_sizes.pop())
    else:
        return None
    if sum(sizes) != total:
        return None
    ranges, s = [], 0
    for z in sizes:
        ranges.append((s, s + z))
        s += z
    if not ranges:                     # zero-chunk stream of an empty value
        ranges = [(0, 0)]
    return ChunkStream(v._chunks, ranges, consumer, v.aval)


def resolve_stage_inputs(stage: Stage, graph: DataflowGraph, ctx,
                         streams_ok: bool, tally: bool = True,
                         shard_ok: bool = False) -> dict[tuple, Any]:
    """Resolve stage inputs, ingesting producer ChunkStreams where allowed.

    An input keeps its stream form only when (a) the executor can iterate a
    chunk list (``streams_ok``), (b) the handoff plan marked this input
    position as a stream ingest, and (c) the stream's grid actually fits the
    input's split type at run time (always re-checked: cross-evaluation
    edges carry whatever grid the *previous* evaluation produced).  A
    permitted ConcatSplit→{ArraySplit,PytreeSplit} edge re-wraps the
    producer's fresh pieces under the consumer's grid (``adapt_stream``).
    SHARDED-form streams (device-resident global array) additionally require
    ``shard_ok`` — their chunks are committed to different devices, so a
    single-device chunk loop must not iterate them; materializing instead
    lets XLA reshard (counted ``interior:gather``).  Anything else is
    materialized — correct by construction, merely the old cost.
    ``tally=False`` skips the ingest/materialize stats (scoring-only
    resolves, e.g. ``AutoExecutor``, whose delegate re-resolves and counts)."""
    if tally:
        resilience.maybe_fail("ingest", f"stage {stage.id}")
    plan = getattr(ctx, "_handoff", None)
    ho = plan.get(stage.id) if plan else None
    sanitize = sanitize_active()
    concrete: dict[tuple, Any] = {}
    for i, (key, si) in enumerate(stage.inputs.items()):
        v = graph.resolve(si.value)
        if isinstance(v, ChunkStream):
            reason = _stream_fallback_reason(v, si, i, ho, streams_ok,
                                             shard_ok)
            if reason is None and type(v.split_type) is not type(si.split_type):
                # Grid conversion only where the PLAN permitted it — the
                # recorded ``convert_in`` decision replays, never a fresh
                # type-level judgement.
                if i in getattr(ho, "convert_in", frozenset()):
                    adapted = adapt_stream(v, si.split_type)
                    if adapted is None:
                        reason = "non-tiling ConcatSplit pieces"
                    else:
                        v = adapted
                        if tally:
                            ctx.stats["stream_converted"] += 1
                else:
                    reason = "grid conversion not planned"
            if reason is None:
                if sanitize:
                    _check_stream_tiles(v, si.split_type,
                                        f"stage {stage.id} input "
                                        f"{stage.ckey(key)}")
                if tally:
                    ctx.stats["stream_ingests"] += 1
            else:
                if tally:
                    # Zero-byte breadcrumb: the dataflow analyzer predicts
                    # fallbacks from the plan (MZ203); this event records
                    # the ones that actually happened, with the reason.
                    note_materialized(
                        0, kind="fallback",
                        where=f"[MZ203] stage {stage.id} input "
                              f"{stage.ckey(key)}: {reason}")
                v = v.materialize()
                if tally:
                    ctx.stats["stream_materialized"] += 1
        concrete[key] = v
    return concrete


def _stream_fallback_reason(v: "ChunkStream", si, i: int, ho,
                            streams_ok: bool, shard_ok: bool) -> str | None:
    """Why this stream input must materialize, or None to ingest it.

    The SAME predicate ``resolve_stage_inputs`` always applied — decomposed
    so the fallback event (and ``core/analysis.py``) can say WHY."""
    if not streams_ok:
        return "stream-incapable executor"
    if ho is None or i not in ho.stream_in:
        return "edge not planned for streaming"
    if v.consumed:
        return "stream already donated"
    if not v.split_type.can_handoff(si.split_type):
        pa, ca = split_axis_of(v.split_type), split_axis_of(si.split_type)
        if pa is not None and ca is not None and pa != ca:
            return f"axis mismatch (producer axis {pa}, consumer axis {ca})"
        return f"grid geometry mismatch ({v.split_type} vs {si.split_type})"
    if v.sharded is not None and not shard_ok:
        return "shard-incapable consumer"
    return None


def _check_stream_tiles(v: "ChunkStream", consumer_type: st.SplitType,
                        where: str) -> None:
    """MZ302 (MOZART_SANITIZE=1): a stream about to be ingested must carry
    sorted, contiguous ranges tiling [0, n) — and n must match the extent
    the consumer's split type declares.  A hole or overlap here means the
    consumer would silently skip or double-process rows."""
    prev = 0
    for s, e in v.ranges:
        if s != prev or e < s:
            raise SanitizerError(
                f"[MZ302] {where}: stream ranges {v.ranges} do not tile "
                f"[0, {v.n}) (hole/overlap at ({s}, {e}))")
        prev = e
    expect = _count_of_type(consumer_type)
    if expect is not None and prev != expect:
        raise SanitizerError(
            f"[MZ302] {where}: stream extent {prev} != consumer extent "
            f"{expect} declared by {consumer_type}")


# ---------------------------------------------------------------------------
# Chunk-buffer donation (shared by the fused / scan / pallas drivers)
# ---------------------------------------------------------------------------


def _aval_sig(aval) -> tuple:
    return tuple((tuple(l.shape), str(l.dtype))
                 for l in jax.tree_util.tree_leaves(aval)
                 if hasattr(l, "shape"))


def donatable_input_keys(stage: Stage, ctx) -> tuple:
    """Canonical env keys of inputs whose per-chunk buffers die here.

    STRUCTURAL only — a pure function of the handoff plan (this stage is
    the handed-off value's LAST in-plan consumer, and the plan-time veto in
    ``handoff.analyze`` already excluded observable producers) and the stage
    template (NodeRef-sourced, splittable, some escaping output chunk can
    absorb the buffer) — so a pinned driver's donate variant is identical on
    every call and the zero-retrace warm-call invariant holds.  Whether a
    producer is still observable *now* is a runtime question answered by
    ``undonatable_stream_keys`` (an observable stream donates a defensive
    COPY, never its own buffers)."""
    plan = getattr(ctx, "_handoff", None)
    ho = plan.get(stage.id) if plan else None
    if ho is None or not ho.last_use:
        return ()

    # XLA can only reuse a donated buffer for an output of the same
    # shape/dtype: donate at most ONE input per matching escaping output
    # (else jax warns about unusable donations).
    out_sigs: dict[tuple, int] = {}
    for n in stage.nodes:
        if (n.id in stage.escaping and n.out_aval is not None
                and stage.out_types[n.id].splittable):
            sig = _aval_sig(n.out_aval)
            out_sigs[sig] = out_sigs.get(sig, 0) + 1
    keys = []
    for i, (key, si) in enumerate(stage.inputs.items()):
        if not (i in ho.last_use and isinstance(si.value, NodeRef)
                and si.split_type.splittable):
            continue
        node = ctx.graph.nodes.get(si.value.node_id)
        aval = node.out_aval if node is not None else None
        if aval is not None and out_sigs.get(_aval_sig(aval), 0) > 0:
            out_sigs[_aval_sig(aval)] -= 1
            keys.append(stage.ckey(key))
    return tuple(sorted(keys))


def undonatable_stream_keys(stage: Stage, concrete: dict[tuple, Any], ctx,
                            donate: tuple) -> set:
    """Donate-marked keys whose ChunkStream may still be observed (the
    producer's Future is alive): their chunks are copied before donation so
    the stream's own buffers survive.  The plan-time veto makes this rare —
    it still fires when liveness flapped between analysis and this call."""
    unsafe = set()
    for key, si in stage.inputs.items():
        ck = stage.ckey(key)
        if ck in donate and isinstance(concrete.get(key), ChunkStream):
            node = ctx.graph.nodes.get(si.value.node_id)
            if node is None or node.future_alive():
                unsafe.add(ck)
    return unsafe


def mark_stream_consumed(stage: Stage, concrete: dict[tuple, Any], ctx,
                         consumed: "set | frozenset | tuple") -> None:
    """After real (non-copy) donation of the canonical keys in ``consumed``:
    flag the stream AND its graph-node original so a late ``materialize``
    hits the pinned backstop error instead of returning freed buffers.
    Under ``MOZART_SANITIZE=1`` the chunk storage itself is also poisoned
    (``_PoisonedChunks``): any read raises MZ301 naming this stage/edge,
    instead of depending on every consumer checking ``consumed`` first."""
    sanitize = sanitize_active()
    for key, si in stage.inputs.items():
        v = concrete.get(key)
        if stage.ckey(key) in consumed and isinstance(v, ChunkStream):
            donor = f"stage {stage.id} input {stage.ckey(key)}"
            targets = [v]                  # the stream and its graph-node
            orig = ctx.graph.nodes[si.value.node_id].result
            if isinstance(orig, ChunkStream) and orig is not v:
                targets.append(orig)       # original / adapted aliases
            for t in targets:
                t.consumed = True
                t.donor = t.donor or donor
                if sanitize:
                    t._chunks = _PoisonedChunks(t.donor)
                    t._merged = t.sharded = None
            if sanitize:
                note_materialized(0, kind="donate", where=donor)


def materialize_inputs(stage: Stage, concrete: dict[tuple, Any],
                       ctx=None) -> dict[tuple, Any]:
    """Merge any stream inputs (tuning/measurement paths need real arrays)."""
    out = dict(concrete)
    for key, v in concrete.items():
        if isinstance(v, ChunkStream):
            out[key] = v.materialize()
            if ctx is not None:
                ctx.stats["stream_materialized"] += 1
    return out


def split_axis_of(t: st.SplitType) -> int | None:
    if isinstance(t, st.ArraySplit):
        return t.axis
    if isinstance(t, st.PytreeSplit):
        return t.axis
    return None


def _block_stage_outputs(stage: Stage) -> None:
    """Best-effort device sync so tuner timings measure real work."""
    for node in stage.nodes:
        if node.id in stage.escaping and node.result is not None:
            try:
                r = node.result
                if isinstance(r, ChunkStream):
                    # Raw storage, never the derived chunk list: blocking must
                    # not charge a slice pass to the boundary counters.
                    r = [x for x in (r._chunks, r._merged, r.sharded)
                         if x is not None]
                jax.block_until_ready(r)
            except resilience.PROBE_ERRORS as e:
                # non-array results (tables, corpora): nothing async
                resilience.note_swallowed("block_stage_outputs", e)


def batch_is_explicit(ctx) -> bool:
    """Whether the batch is set by hand (``batch_elements``) or by the tuner
    timing one candidate (``_batch_override``): then it is used exactly."""
    return (getattr(ctx, "_batch_override", None) is not None
            or bool(ctx.batch_elements))


def candidate_batches(est: int, n: int) -> list[int]:
    """2–3 chunk sizes around the §5.2 fast-memory estimate."""
    if n <= 0:
        return [1]                    # empty split: nothing to tune
    est = max(1, min(est, n))
    if est >= n:
        return [n]                    # one chunk: nothing to tune
    cands = {max(1, est // 2), est, min(est * 2, n)}
    return sorted(cands)


#: chunks per timed sample when the tuner measures a candidate.  Sampling a
#: couple of chunks and extrapolating replaces the old protocol of two FULL
#: stage executions per candidate, bounding first-cached-run overhead to well
#: under one extra full execution (see ``StageExecutor.sampled_time``).
SAMPLE_CHUNKS = 2


# ---------------------------------------------------------------------------
# Base class
# ---------------------------------------------------------------------------


class StageExecutor:
    """One execution strategy: split inputs → drive chunks → merge partials.

    Subclasses implement ``execute``; ``run`` is the template method the
    runtime calls per stage.  It resolves concrete inputs, optionally runs
    the chunk-size auto-tuner (first execution of a *cached* plan), and does
    the done/stats bookkeeping shared by every strategy.
    """

    name: str = "abstract"
    #: whether ``choose_batch`` output meaningfully affects this strategy —
    #: only tunable executors participate in chunk-size auto-tuning.
    tunable: bool = False
    #: whether ``execute`` can iterate a ChunkStream input directly (the
    #: chunk-loop drivers can; whole-array strategies materialize instead).
    stream_capable: bool = False
    #: whether ``execute`` accepts SHARDED-form streams (chunks committed to
    #: different mesh devices).  Only the sharded executor places per-shard
    #: buffers; everything else materializes and lets XLA reshard.
    shard_capable: bool = False

    # -- template method ----------------------------------------------------
    def run(self, stage: Stage, graph: DataflowGraph, ctx) -> None:
        laid = ctx.stats.get("layout_bytes", 0)
        with span("mozart.stage", stage=stage.id,
                  executor=self.name) as stage_span:
            with span("mozart.inputs"):
                concrete = resolve_stage_inputs(
                    stage, graph, ctx, self.stream_capable,
                    shard_ok=self.shard_capable)
            entry = getattr(ctx, "_plan_entry", None)
            if self._should_tune(stage, ctx, entry):
                # Sampled tuning re-slices inputs at arbitrary offsets: a
                # one-time event, so streams are merged rather than
                # complicating sampling.
                concrete = materialize_inputs(stage, concrete, ctx)
                self._tune(stage, concrete, ctx, entry)
            else:
                self.execute(stage, concrete, ctx)
            stage_span.set_metadata(
                layout_bytes=ctx.stats.get("layout_bytes", 0) - laid)
        ctx.stats["stages"] += 1
        for node in stage.nodes:
            node.done = True

    def execute(self, stage: Stage, concrete: dict[tuple, Any], ctx) -> None:
        raise NotImplementedError

    # -- batch sizing (paper §5.2 + auto-tuner) -----------------------------
    def estimate_batch(self, stage: Stage, concrete: dict[tuple, Any], ctx,
                       n: int) -> int:
        elem_bytes = stage_elem_bytes(stage, concrete, n)
        return hardware.mozart_batch_elements(elem_bytes, ctx.chip)

    def choose_batch(self, stage: Stage, concrete: dict[tuple, Any], ctx,
                     n: int) -> int:
        override = getattr(ctx, "_batch_override", None)
        if override is not None:
            return max(1, min(override, n))
        if ctx.batch_elements:
            return max(1, min(ctx.batch_elements, n))
        entry = getattr(ctx, "_plan_entry", None)
        if entry is not None:
            pinned = entry.tuned_batch.get(stage.id)
            if pinned:
                return max(1, min(pinned, n))
        return max(1, min(self.estimate_batch(stage, concrete, ctx, n), n))

    # -- auto-tuner ---------------------------------------------------------
    def _should_tune(self, stage: Stage, ctx, entry) -> bool:
        return (
            self.tunable
            and entry is not None
            and entry.hits > 0                      # first execution of a CACHED plan
            and getattr(ctx, "autotune", True)
            and not batch_is_explicit(ctx)
            and stage.id not in entry.tuned_batch
            # dynamic (call_raw) functions may carry side effects and their
            # runtime is value-dependent: never re-execute them to time them
            and not has_dynamic(stage)
            # claim atomically so concurrent sessions never tune in duplicate
            and entry.try_claim_tuning(stage.id)
        )

    def _tune(self, stage: Stage, concrete: dict[tuple, Any], ctx, entry) -> None:
        pinned = False
        try:
            n = stage_num_elements(stage, concrete, ctx.pedantic)
            est = self.estimate_batch(stage, concrete, ctx, n)
            cands = self.tuning_candidates(stage, concrete, ctx, est, n)
            if len(cands) == 1:
                entry.pin(stage.id, cands[0])
                self.note_pinned(stage, ctx, entry, cands[0], n)
                pinned = True
                self.execute(stage, concrete, ctx)
                return
            best, best_dt = None, None
            for b in cands:
                try:
                    dt = self.sampled_time(stage, concrete, ctx, b, n)
                except resilience.PROBE_ERRORS as e:
                    # unsampleable candidate: skip it (but visibly)
                    resilience.note_swallowed("tune_sample", e, ctx)
                    continue
                entry.record_trial(stage.id, b, dt)
                if best_dt is None or dt < best_dt:
                    best, best_dt = b, dt
            chosen = best if best is not None else est
            entry.pin(stage.id, chosen)
            self.note_pinned(stage, ctx, entry, chosen, n)
            pinned = True
            if best is not None:
                ctx.stats["autotuned_stages"] += 1
        finally:
            if not pinned:
                entry.release_tuning(stage.id)
        # One real execution with the pinned size produces the stage results
        # (sampled runs above computed throwaway partial outputs only).
        self.execute(stage, concrete, ctx)

    # -- sampled measurement ------------------------------------------------
    def tuning_candidates(self, stage: Stage, concrete: dict[tuple, Any], ctx,
                          est: int, n: int) -> list[int]:
        """Chunk-size candidates the tuner measures (§5.2 bracket by default;
        executors with extra geometry constraints — e.g. ``sharded``'s
        per-shard loop, ``pallas``'s hardware block multiples — override to
        reshape the candidate space)."""
        return candidate_batches(est, n)

    def note_pinned(self, stage: Stage, ctx, entry, batch: int, n: int) -> None:
        """Hook after the tuner pins ``batch`` (e.g. ``pallas`` records the
        hardware block *shape* the winning element count resolves to)."""

    def sample_elems(self, ctx, batch: int, n: int) -> int:
        """Elements one timed sample re-executes.  ``sharded`` rounds this to
        the mesh extent so sample slices stay shardable."""
        return min(n, SAMPLE_CHUNKS * batch) if n > 0 else 0

    def sampled_time(self, stage: Stage, concrete: dict[tuple, Any], ctx,
                     batch: int, n: int) -> float:
        """Estimated seconds for a full stage execution at ``batch``, measured
        on a bounded sample of chunks.

        Splits every splittable input down to ``SAMPLE_CHUNKS`` chunks, runs
        the chain twice (warmup absorbs per-chunk-shape jit tracing; the
        second run is timed) and extrapolates linearly to ``n`` elements.
        ``ctx.stats["tuning_sample_elems"]`` accrues the elements actually
        re-executed so tests can assert the overhead bound structurally."""
        batch = max(1, min(batch, n)) if n > 0 else 1
        s = self.sample_elems(ctx, batch, n)
        sample: dict[tuple, Any] = {}
        for key, si in stage.inputs.items():
            v = concrete[key]
            sample[key] = (si.split_type.split(v, 0, s)
                           if si.split_type.splittable else v)
        prev_cap = getattr(ctx, "_n_cap", None)
        prev_override = ctx._batch_override
        ctx._n_cap = s
        ctx._batch_override = batch
        try:
            self.execute(stage, sample, ctx)
            _block_stage_outputs(stage)
            t0 = time.perf_counter()
            self.execute(stage, sample, ctx)
            _block_stage_outputs(stage)
            dt = time.perf_counter() - t0
        finally:
            ctx._n_cap = prev_cap
            ctx._batch_override = prev_override
        ctx.stats["tuning_sample_elems"] += 2 * s
        return dt * (n / s) if s else dt
