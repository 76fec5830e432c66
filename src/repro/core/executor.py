"""Built-in Mozart executor strategies (paper §5.2) as ``StageExecutor``s.

Per stage: (1) discover runtime parameters — the batch size is chosen so one
batch of *every* live pipeline value fits in fast memory (L2 on the paper's
CPUs, VMEM on our TPU target), or taken from the plan cache's auto-tuner;
(2) split inputs and drive each batch through the whole function chain;
(3) merge partial results associatively.

Strategies registered here (see ``core/stage_exec.py`` for the registry):

* ``"eager"``      — no splitting: each function runs whole.  This is the
                     un-annotated library baseline.
* ``"pipelined"``  — paper-faithful: a Python driver loop calls each
                     *separately jit-compiled* (black-box) function on one
                     chunk at a time.
* ``"fused"``      — beyond-paper: the whole per-chunk chain is traced into
                     ONE jitted function (still driven chunk-by-chunk).
* ``"scan"``       — beyond-paper: equal-size chunks are stacked and the
                     fused chain is driven by ``lax.map`` so the chunk loop
                     itself compiles to a single streaming XLA loop.

``"sharded"`` (mesh scale-out) and ``"pallas"`` (TPU split-pipeline kernel)
live in ``core/sharded.py`` / ``core/pallas_exec.py``.

The jitted drivers built here are *capture-safe* (closed over ``chain_plan``
and canonical env keys, never over a Stage or concrete arrays) and pinned
into the plan cache via ``pinned_jit``: warm executions of a cached plan
reuse the same compiled executable — zero retraces (``note_trace``).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core import resilience
from repro.core.graph import NodeRef
from repro.core.planner import Stage
from repro.core.trace import count_layout, span
from repro.core.stage_exec import (
    ChunkStream,
    PedanticError,
    StageExecutor,
    batch_ranges,
    chain_plan,
    chunk_env_for,
    donatable_input_keys,
    effective_elements,
    finish_stage,
    get_executor,
    has_dynamic,
    mark_stream_consumed,
    note_materialized,
    note_trace,
    pinned_jit,
    register_executor,
    run_chain,
    run_plan,
    split_axis_of,
    stage_num_elements,
    undonatable_stream_keys,
)

__all__ = [
    "PedanticError", "EagerExecutor", "PipelinedExecutor",
    "FusedExecutor", "ScanExecutor",
]


@register_executor("eager")
class EagerExecutor(StageExecutor):
    """The un-annotated library baseline: every function runs whole."""

    tunable = False
    stream_capable = False       # whole-value strategy: streams materialize

    def execute(self, stage: Stage, concrete: dict[tuple, Any], ctx) -> None:
        env = {stage.ckey(key): v for key, v in concrete.items()}
        run_chain(stage, env, jit_each=True)
        for node in stage.nodes:
            node.result = env[stage.out_key(node)]
            node.done = True
            ctx.stats["calls"] += 1


def _build_fused_driver(stage: Stage, esc: tuple[int, ...],
                        donate: tuple = ()) -> Callable:
    plan = chain_plan(stage)

    if donate:
        # Handed-off chunk buffers whose stream dies after this stage arrive
        # as a separate (donated) argument: XLA reuses the dead intermediate's
        # memory for this chunk's outputs instead of allocating fresh buffers.
        def mozart_fused_driver_donate(donated, env):
            note_trace()
            env = dict(env)
            env.update(donated)
            run_plan(plan, env)
            return {p: env[("n", p)] for p in esc}

        return jax.jit(mozart_fused_driver_donate, donate_argnums=(0,))

    def mozart_fused_driver(env):
        note_trace()
        run_plan(plan, env)
        return {p: env[("n", p)] for p in esc}

    return jax.jit(mozart_fused_driver)


class ChunkedExecutor(StageExecutor):
    """Shared Python-driver chunk loop; ``mode`` picks the per-chunk style.

    Chunk handoff: stream inputs (producer chunk lists) are iterated without
    re-slicing.  The loop itself never blocks between chunks — jax dispatch
    is asynchronous, so host-side split work for chunk *i+1* always overlaps
    device compute of chunk *i* — and chunk buffers that die here are
    donated to the fused driver (``_build_fused_driver``) so XLA reuses the
    dead intermediate's memory for this chunk's outputs."""

    tunable = True
    stream_capable = True
    mode = "pipelined"

    #: a producer grid whose chunks are up to this factor over the consumer's
    #: own batch estimate is adopted as-is: the §5.2 estimate deliberately
    #: leaves fast-memory headroom, and adopting the grid costs zero copies
    #: while re-gridding costs one per chunk.  Beyond it the stream is
    #: re-gridded to protect the fast-memory budget.
    GRID_SLACK = 2.0

    def _ingest_streams(self, stage: Stage, concrete: dict[tuple, Any], ctx,
                        n: int, batch: int):
        """Align every stream input onto ONE chunk grid.

        The producer's grid is adopted as-is when its chunks (approximately)
        fit this stage's fast-memory budget — finer grids always fit, and up
        to ``GRID_SLACK``x oversized chunks are tolerated; grids beyond that
        (or streams disagreeing with the adopted grid) convert via
        ``SplitType.rechunk`` — at most one copy, never the merge +
        re-split two."""
        streams = [(k, v) for k, v in concrete.items()
                   if isinstance(v, ChunkStream)]
        if not streams:
            return concrete, batch_ranges(n, batch)
        base = streams[0][1]
        grid = base.ranges
        ub = base.uniform_batch()
        if ub is not None and ub > batch * self.GRID_SLACK and n > 0:
            grid = batch_ranges(n, batch)
        if not grid:
            grid = batch_ranges(n, batch)  # zero-chunk stream: degenerate grid
        out = dict(concrete)
        for k, v in streams:
            if v.ranges != grid:
                chunks, copied = v.split_type.rechunk(v.chunks, v.ranges, grid)
                out[k] = ChunkStream(chunks, grid, v.split_type, v.aval)
                note_materialized(copied, kind="rechunk",
                                  where=f"stage {stage.id} input {stage.ckey(k)}")
                ctx.stats["handoff_rechunks"] += 1
        return out, grid

    def execute(self, stage: Stage, concrete: dict[tuple, Any], ctx) -> None:
        mode = self.mode
        if has_dynamic(stage):
            mode = "pipelined"           # dynamic-shape fns cannot be traced
        n = effective_elements(ctx, stage_num_elements(stage, concrete, ctx.pedantic))
        batch = self.choose_batch(stage, concrete, ctx, n)
        # Chunk-granular OOM policy (resilience leg 2): on resource
        # exhaustion, halve the batch and re-drive — bounded, and only while
        # no chunk buffer was REALLY donated (a freed buffer must never be
        # re-read; defensive copies are safe).  The surviving size is
        # re-pinned into the tuner state so warm calls start from it.
        halvings = 0
        while True:
            real_donated = (ctx.stats.get("donated_chunks", 0)
                            - ctx.stats.get("donation_copies", 0))
            try:
                self._drive(stage, concrete, ctx, mode, n, batch)
                break
            except resilience.PROBE_ERRORS as e:
                still_clean = real_donated == (
                    ctx.stats.get("donated_chunks", 0)
                    - ctx.stats.get("donation_copies", 0))
                if (not resilience.is_resource_exhausted(e) or batch <= 1
                        or halvings >= resilience.MAX_OOM_HALVINGS
                        or not still_clean):
                    raise
                halvings += 1
                batch = max(1, batch // 2)
                ctx.stats["chunk_oom_halvings"] += 1
                resilience.record_event(
                    "MZ403", f"stage {stage.id}: {type(e).__name__}, "
                             f"batch halved to {batch}")
        if halvings:
            entry = getattr(ctx, "_plan_entry", None)
            if entry is not None:
                entry.pin(stage.id, batch)   # survive into warm calls

    def _drive(self, stage: Stage, concrete: dict[tuple, Any], ctx,
               mode: str, n: int, batch: int) -> None:
        concrete, ranges = self._ingest_streams(stage, concrete, ctx, n, batch)
        ctx.stats["chunks"] += len(ranges)

        esc = tuple(stage.escape_positions())
        fused_fn: Callable | None = None
        donate: tuple = ()
        unsafe: set = set()
        if mode == "fused":
            # The donate key set is structural (plan-derived), so the pinned
            # driver variant is the same on every warm call — zero retraces.
            donate = donatable_input_keys(stage, ctx)
            if donate:
                unsafe = undonatable_stream_keys(stage, concrete, ctx, donate)
            fused_fn = pinned_jit(stage, ctx, "fused", (esc, donate),
                                  lambda: _build_fused_driver(stage, esc, donate))

        partials: dict[int, list[Any]] = {p: [] for p in esc}
        with span("mozart.drive", chunks=len(ranges)):
            for i, (s, e) in enumerate(ranges):
                resilience.maybe_fail("chunk",
                                      f"stage {stage.id} chunk [{s},{e})")
                env = chunk_env_for(stage, concrete, s, e, ctx.pedantic,
                                    chunk_index=i, force_slice=donate)
                if mode == "pipelined":
                    run_chain(stage, env, jit_each=True)
                    ctx.stats["calls"] += len(stage.nodes)
                    outs = {p: env[("n", p)] for p in esc}
                else:
                    if donate:
                        # Observable streams donate a defensive COPY — their
                        # own chunk buffers must survive a later
                        # Future.value.
                        donated = {}
                        for k in donate:
                            v = env.pop(k)
                            if k in unsafe:
                                v = jax.tree_util.tree_map(jnp.array, v)
                                ctx.stats["donation_copies"] += 1
                            donated[k] = v
                        outs = fused_fn(donated, env)
                        ctx.stats["donated_chunks"] += len(donated)
                    else:
                        outs = fused_fn(env)
                    ctx.stats["calls"] += 1
                for p, v in outs.items():
                    partials[p].append(v)
        with span("mozart.merge"):
            mark_stream_consumed(stage, concrete, ctx, set(donate) - unsafe)
            finish_stage(stage, partials, ranges, ctx)


@register_executor("pipelined")
class PipelinedExecutor(ChunkedExecutor):
    """Paper-faithful driver: separately jitted black-box calls per chunk."""

    mode = "pipelined"


@register_executor("fused")
class FusedExecutor(ChunkedExecutor):
    """Whole per-chunk chain traced into one jitted function."""

    mode = "fused"


def _build_scan_driver(stage: Stage, esc: tuple[int, ...],
                       split_axes: dict[tuple, int],
                       out_axes: dict[int, int | None],
                       donate: tuple = ()) -> Callable:
    plan = chain_plan(stage)

    def chain_fn(split_vals: dict, bcast_env: dict):
        env = dict(bcast_env)
        for key, v in split_vals.items():
            ax = split_axes[key]
            env[key] = jax.tree_util.tree_map(
                lambda l: jnp.moveaxis(l, 0, ax) if ax else l, v)
        run_plan(plan, env)
        outs = {}
        for p in esc:
            ax = out_axes[p]
            o = env[("n", p)]
            if ax is not None:
                o = jax.tree_util.tree_map(
                    lambda l: jnp.moveaxis(l, ax, 0) if ax else l, o)
            outs[p] = o
        return outs

    if donate:
        # Stacked carry buffers that die at this stage arrive as a separate
        # donated argument: XLA reuses the dead (n_chunks, batch, …) buffer
        # for this stage's stacked outputs instead of allocating fresh ones —
        # the scan-driver rendering of the fused driver's chunk donation.
        def mozart_scan_driver_donate(donated: dict, stacked_inputs: dict,
                                      bcast_env: dict):
            note_trace()
            stacked_inputs = dict(stacked_inputs)
            stacked_inputs.update(donated)
            return jax.lax.map(lambda sv: chain_fn(sv, bcast_env),
                               stacked_inputs)

        return jax.jit(mozart_scan_driver_donate, donate_argnums=(0,))

    def mozart_scan_driver(stacked_inputs: dict, bcast_env: dict):
        # Broadcast values ride along as a real jit argument (not a closure
        # capture): the pinned executable must not bake one call's scalars
        # into the compiled program.
        note_trace()
        return jax.lax.map(lambda sv: chain_fn(sv, bcast_env), stacked_inputs)

    return jax.jit(mozart_scan_driver)


@register_executor("scan")
class ScanExecutor(StageExecutor):
    """Stack equal-size chunks and drive the fused chain with ``lax.map``.

    The chunk loop compiles into a single XLA while-loop whose body touches
    one fast-memory-sized batch at a time — the TPU-native rendering of the
    paper's driver loop.  The ragged tail chunk is handled separately.

    Chunk handoff: an incoming ``ChunkStream`` is stacked DIRECTLY into the
    driver's carry layout — the producer's own stacked carry passes through
    untouched when the grids agree (scan→scan is zero-copy), a chunk list
    stacks in one gather (equal-grid fast path), and disagreeing grids
    convert through ``SplitType.rechunk`` first — ``materialize()`` is never
    called on ingest.  Streamed outputs keep the carry layout
    (``ChunkStream.from_stacked``), and dying stacked inputs are donated to
    the driver under the same structural (plan-derived) donate-key rules as
    the fused driver, so pinned variants never flap and warm calls stay
    zero-retrace.
    """

    tunable = True
    stream_capable = True

    #: same grid-adoption slack as the chunk-loop drivers: a producer grid
    #: whose chunks are at most this factor over the §5.2 estimate is
    #: adopted as the scan batch (zero copies); beyond it the stream is
    #: re-gridded to protect the fast-memory budget.
    GRID_SLACK = 2.0

    def _ingest_streams(self, stage: Stage, concrete: dict[tuple, Any], ctx,
                        n: int, batch: int) -> tuple[dict[tuple, Any], int]:
        """Align stream inputs onto ONE regular grid; returns the batch.

        The scan layout needs equal-size main chunks + one ragged tail,
        which is exactly the shape of a ``batch_ranges`` grid: a stream
        whose grid already is one (within ``GRID_SLACK`` of the estimate)
        fixes the batch; anything else rechunks — at most one copy."""
        streams = [(k, v) for k, v in concrete.items()
                   if isinstance(v, ChunkStream)]
        if not streams or n <= 0:
            return concrete, batch
        base = streams[0][1]
        ub = base.uniform_batch()
        if (ub and ub <= batch * self.GRID_SLACK
                and base.ranges == batch_ranges(n, ub)):
            batch = ub                     # adopt the producer's grid as-is
        grid = batch_ranges(n, batch)
        out = dict(concrete)
        for k, v in streams:
            if v.ranges != grid:
                chunks, copied = v.split_type.rechunk(v.chunks, v.ranges, grid)
                out[k] = ChunkStream(chunks, grid, v.split_type, v.aval)
                note_materialized(copied, kind="rechunk",
                                  where=f"stage {stage.id} input {stage.ckey(k)}")
                ctx.stats["handoff_rechunks"] += 1
        return out, batch

    def execute(self, stage: Stage, concrete: dict[tuple, Any], ctx) -> None:
        if has_dynamic(stage):
            return get_executor("pipelined").execute(stage, concrete, ctx)

        n = effective_elements(ctx, stage_num_elements(stage, concrete, ctx.pedantic))
        if n == 0:
            # Empty split: the stacked driver has no chunks to map over; the
            # fused driver runs one degenerate zero-size chunk instead (and
            # handles any zero-element stream input itself).
            return get_executor("fused").execute(stage, concrete, ctx)
        batch = self.choose_batch(stage, concrete, ctx, n)
        concrete, batch = self._ingest_streams(stage, concrete, ctx, n, batch)
        n_main = (n // batch) * batch
        n_chunks = n_main // batch

        # Outputs whose split axis we know get stacked; everything else falls
        # back to the fused python driver.
        for nid in stage.escaping:
            if split_axis_of(stage.out_types[nid]) is None and stage.out_types[nid].splittable:
                return get_executor("fused").execute(stage, concrete, ctx)

        split_keys = [k for k, si in stage.inputs.items() if si.split_type.splittable]
        if not split_keys or any(
            split_axis_of(stage.inputs[k].split_type) is None for k in split_keys
        ):
            return get_executor("fused").execute(stage, concrete, ctx)

        fresh_stacked: set[tuple] = set()    # ckeys whose stacked buffer is ours

        def stacked(key):
            si = stage.inputs[key]
            ax = split_axis_of(si.split_type)
            v = concrete[key]
            if isinstance(v, ChunkStream):
                if (v.stacked is not None and v._chunks is None
                        and v.uniform_batch() == batch):
                    # scan→scan: the producer's carry layout IS this stage's
                    # stacked input — zero copies, zero dispatches.
                    return v.stacked
                # Equal-grid fast path: stack the chunk list straight into
                # the carry layout (one gather — the merge+reshape round
                # trip is gone).
                fresh_stacked.add(stage.ckey(key))
                main = [jax.tree_util.tree_map(
                            lambda l: jnp.moveaxis(l, ax, 0) if ax else l,
                            v.chunk(i))
                        for i in range(n_chunks)]
                if not main:
                    return None
                slabs = jax.tree_util.tree_map(
                    lambda *ls: jnp.stack(ls), *main)
                count_layout(ctx, slabs, main if ax else ())
                return slabs

            def stack_leaf(leaf):
                lead = jnp.moveaxis(leaf, ax, 0) if ax else leaf
                head = lead[:n_main]
                main = head.reshape((n_chunks, batch) + lead.shape[1:])
                # jax returns its argument where a move or slice is trivial:
                # count the copies actually asked for.
                count_layout(ctx, main, [x for x, src in
                                         ((lead, leaf), (head, lead))
                                         if x is not src])
                return main
            return jax.tree_util.tree_map(stack_leaf, v)

        with span("mozart.layout"):
            stacked_inputs = {stage.ckey(key): stacked(key)
                              for key in split_keys}
        bcast_env = {stage.ckey(k): concrete[k] for k, si in stage.inputs.items()
                     if not si.split_type.splittable}

        esc = tuple(stage.escape_positions())
        split_axes = {stage.ckey(k): split_axis_of(stage.inputs[k].split_type)
                      for k in split_keys}
        out_axes = {stage.pos[nid]: split_axis_of(stage.out_types[nid])
                    for nid in stage.escaping}

        # Donation: structural key set shared with the fused driver.  The
        # donated value is always the STACKED buffer; whether it may be the
        # stream's own storage is a runtime question (a fresh stack we built
        # is always safe; a passed-through carry or a plain reshaped array
        # donates a defensive copy unless provably dead).
        donate = tuple(k for k in donatable_input_keys(stage, ctx)
                       if k in stacked_inputs) if n_chunks else ()
        unsafe = undonatable_stream_keys(stage, concrete, ctx, donate) \
            if donate else set()
        driver = pinned_jit(
            stage, ctx, "scan", (esc, batch, donate),
            lambda: _build_scan_driver(stage, esc, split_axes, out_axes,
                                       donate))

        consumed_keys: tuple = ()
        n_ran = n_chunks + (1 if n_main < n else 0)
        with span("mozart.drive", chunks=n_ran):
            if n_chunks:
                resilience.maybe_fail("chunk",
                                      f"stage {stage.id} scan driver")
                if donate:
                    key_of = {stage.ckey(k): k for k in stage.inputs}
                    donated = {}
                    for ck in donate:
                        val = stacked_inputs.pop(ck)
                        if ck in fresh_stacked:
                            # Our own stack: the stream's chunk buffers
                            # survive regardless — donate without copying or
                            # consuming.
                            donated[ck] = val
                        elif (ck in unsafe or not isinstance(
                                concrete.get(key_of[ck]), ChunkStream)):
                            # Observable carry pass-through, or a plain array
                            # whose reshape may alias the producer's retained
                            # result: donate a defensive copy.
                            donated[ck] = jax.tree_util.tree_map(jnp.array,
                                                                 val)
                            ctx.stats["donation_copies"] += 1
                        else:
                            donated[ck] = val    # dead carry: real donation
                            consumed_keys += (ck,)
                    stacked_outs = driver(donated, stacked_inputs, bcast_env)
                    ctx.stats["donated_chunks"] += len(donated)
                else:
                    stacked_outs = driver(stacked_inputs, bcast_env)
            else:
                stacked_outs = {p: None for p in esc}
            ctx.stats["chunks"] += n_ran
            ctx.stats["calls"] += 1

            tail_env = None
            if n_main < n:  # ragged tail
                tail_env = chunk_env_for(stage, concrete, n_main, n,
                                         ctx.pedantic, chunk_index=n_chunks)
                run_chain(stage, tail_env, jit_each=False)

        # Which outputs stay in carry form (the handoff plan's decision).
        plan_ho = getattr(ctx, "_handoff", None)
        ho = plan_ho.get(stage.id) if plan_ho else None
        ranges = batch_ranges(n, batch)
        with span("mozart.merge"):
            partials: dict[int, list[Any]] = {}
            for nid in stage.escaping:
                p = stage.pos[nid]
                t = stage.out_types[nid]
                ax = split_axis_of(t)
                node = next(nd for nd in stage.nodes if nd.id == nid)
                tail_piece = (tail_env[("n", p)] if tail_env is not None
                              else None)
                if (ho is not None and p in ho.stream_out and ax is not None
                        and n_chunks and len(ranges) > 1):
                    # Streamed output: keep the driver's carry layout — a
                    # scan consumer ingests it with zero copies, a chunk-loop
                    # consumer derives the chunk list lazily, and observation
                    # merges lazily via Future.value.
                    node.result = ChunkStream.from_stacked(
                        stacked_outs[p], tail_piece, ranges, t,
                        node.out_aval)
                    node.done = True
                    ctx.stats["streamed_outputs"] += 1
                    continue
                pieces: list[Any] = []
                if n_chunks:
                    so = stacked_outs[p]
                    if ax is not None:
                        def unstack(l):
                            flat = l.reshape((n_chunks * batch,) + l.shape[2:])
                            out = jnp.moveaxis(flat, 0, ax) if ax else flat
                            count_layout(ctx, flat, out if ax else ())
                            return out
                        pieces.append(jax.tree_util.tree_map(unstack, so))
                    else:  # ReduceSplit etc.: merge over the stacked dim
                        per_chunk = [jax.tree_util.tree_map(lambda l: l[i], so)
                                     for i in range(n_chunks)]
                        count_layout(ctx, per_chunk)
                        pieces.extend(per_chunk)
                if tail_piece is not None:
                    pieces.append(tail_piece)
                partials[p] = pieces
            mark_stream_consumed(stage, concrete, ctx, consumed_keys)
            finish_stage(stage, partials, ctx=ctx)
