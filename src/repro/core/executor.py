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
* ``"scan"``       — beyond-paper: the chunk loop itself compiles to one
                     XLA ``fori_loop`` that reads each chunk in place from
                     the flat inputs and writes its results into whole-size
                     outputs in the loop carry; the ragged tail runs in the
                     same program.

``"sharded"`` (mesh scale-out) and ``"pallas"`` (TPU split-pipeline kernel)
live in ``core/sharded.py`` / ``core/pallas_exec.py``.

The jitted drivers built here are *capture-safe* (closed over ``chain_plan``
and canonical env keys, never over a Stage or concrete arrays) and pinned
into the plan cache via ``pinned_jit``: warm executions of a cached plan
reuse the same compiled executable — zero retraces (``note_trace``).
"""

from __future__ import annotations

import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import resilience
from repro.core import split_types as st
from repro.core.graph import NodeRef
from repro.core.planner import Stage
from repro.core.trace import count_layout, span
from repro.core.stage_exec import (
    ChunkStream,
    PedanticError,
    StageExecutor,
    batch_is_explicit,
    batch_ranges,
    candidate_batches,
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


def split_tile(shape: tuple, axis: int) -> int:
    """Elements along ``axis`` in one tile of the TPU's default layout, so
    a chunk starting on a multiple of it reads and writes whole tiles.

    Read from layouts the v5e compiler assigns (``tests/test_tpu_compile.py``
    checks them): a 1-D array is tiled by 1024 elements
    (``f32[n]{0:T(1024)}``; 16- and 8-bit types add sub-tiles inside the
    same 1024), the last two axes of a larger one by 8 rows of 128 lanes
    (``{…,1,0:T(8,128)}``), and the axes above those are not tiled."""
    rank = len(shape)
    if rank == 1:
        return 1024
    if rank >= 2 and axis == rank - 1:
        return 128
    if rank >= 2 and axis == rank - 2:
        return 8
    return 1


def _stage_tile(stage: Stage, concrete: dict[tuple, Any]) -> int:
    """The tile every split input and split output of the stage agrees on
    (the least common multiple of their ``split_tile``)."""
    tile = 1
    values = []
    for key, si in stage.inputs.items():
        v = concrete[key]
        values.append((split_axis_of(si.split_type),
                       v.aval if isinstance(v, ChunkStream) else v))
    for node in stage.nodes:
        if node.id in stage.escaping:
            values.append((split_axis_of(stage.out_types[node.id]),
                           node.out_aval))
    for ax, v in values:
        if ax is None:
            continue
        for leaf in jax.tree.leaves(v):
            if len(getattr(leaf, "shape", ())) > ax:
                tile = math.lcm(tile, split_tile(leaf.shape, ax))
    return tile


#: lanes of a TPU vector register: the minor tile of every layout.
_LANES = 128


def _aligned(batch: int, n: int, tile: int) -> int:
    """``batch`` rounded down to a whole number of tiles; a single chunk
    (``batch >= n``) and a batch under one tile stay as they are."""
    return batch if batch >= n or batch < tile else batch // tile * tile


def _build_scan_driver(stage: Stage, esc: tuple[int, ...],
                       split_axes: dict[tuple, int],
                       out_axes: dict[int, int | None], batch: int,
                       donate: tuple = ()) -> Callable:
    """One compiled program for the whole stage: a ``fori_loop`` over the
    main chunks and one more call of the chain on the ragged tail.

    Split inputs arrive flat, in their own shapes; chunk ``i`` is a
    ``dynamic_slice`` of each at ``i * batch`` (a 1-D value sliced as rows
    of 128 lanes), which XLA fuses into the chain.  Split outputs are allocated whole in the loop carry and each
    chunk's result goes in with an in-place ``dynamic_update_slice``, so
    the driver's outputs are the merged values; a reduction output (the
    one kind without a split axis) folds into the carry in chunk order
    (``ReduceSplit.merge``).  ``n`` is read from the inputs' shapes, so one
    pinned driver serves every length at this batch."""
    plan = chain_plan(stage)
    reduce_types = {stage.pos[nid]: t for nid, t in stage.out_types.items()
                    if nid in stage.escaping and isinstance(t, st.ReduceSplit)}

    def chain(vals: dict, bcast_env: dict) -> dict:
        env = dict(bcast_env)
        env.update(vals)
        run_plan(plan, env)
        return {p: env[("n", p)] for p in esc}

    def as_rows(l) -> bool:
        # A 1-D value is laid out as rows of 128 lanes, 8 rows a tile, so a
        # reshape to rows is free; sliced as rows, a chunk of whole tiles
        # moves no lane.  On a v5e, a Black–Scholes loop over 2^27 elements
        # ran 1.3-2.3x faster this way than sliced along the 1-D axis.
        return (l.ndim == 1 and l.shape[0] % _LANES == 0
                and batch % (8 * _LANES) == 0)

    def cut(l, ax: int, start, size: int):
        if as_rows(l):
            rows = lax.dynamic_slice_in_dim(l.reshape(-1, _LANES),
                                            start // _LANES, size // _LANES)
            return rows.reshape(size)
        return lax.dynamic_slice_in_dim(l, start, size, ax)

    def paste(buf, v, ax: int, start):
        if as_rows(buf):
            rows = lax.dynamic_update_slice_in_dim(
                buf.reshape(-1, _LANES), v.reshape(-1, _LANES),
                start // _LANES, 0)
            return rows.reshape(buf.shape)
        return lax.dynamic_update_slice_in_dim(buf, v, start, ax)

    def window(vals: dict, start, size: int) -> dict:
        return {k: jax.tree.map(
                    lambda l, ax=split_axes[k]: cut(l, ax, start, size), v)
                for k, v in vals.items()}

    def put(bufs: dict, outs: dict, i, start) -> dict:
        """Chunk ``i``'s results, written at ``start`` or folded in."""
        new = {}
        for p, buf in bufs.items():
            ax, val = out_axes[p], outs[p]
            if ax is None:
                new[p] = jnp.where(i == 0, val,
                                   reduce_types[p].merge([buf, val]))
            else:
                new[p] = jax.tree.map(
                    lambda b, v, ax=ax: paste(b, v, ax, start), buf, val)
        return new

    def carry_aliases(split_vals: dict, part: dict, n: int) -> dict:
        """Output position -> donated input key whose buffer becomes that
        output's carry, paired as jax pairs donated arguments with results
        (outputs in order, each taking the first donated input of its shape
        and dtype).  An input read from its own carry chunk by chunk is
        overwritten in place only after it was read, so XLA needs no copy;
        a pair that splits along different axes gets no carry."""
        free = [k for k in sorted(donate) if hasattr(split_vals[k], "shape")]
        alias = {}
        for p in esc:
            ax, leaf = out_axes[p], part[p]
            if ax is None or not hasattr(leaf, "shape"):
                continue
            shape = leaf.shape[:ax] + (n,) + leaf.shape[ax + 1:]
            for k in free:
                v = split_vals[k]
                if v.shape == shape and v.dtype == leaf.dtype:
                    free.remove(k)
                    if split_axes[k] == ax:
                        alias[p] = k
                    break
        return alias

    def drive(split_vals: dict, bcast_env: dict) -> dict:
        note_trace()
        k0 = next(iter(split_vals))
        n = jax.tree.leaves(split_vals[k0])[0].shape[split_axes[k0]]
        n_chunks = n // batch
        part = jax.eval_shape(chain, window(split_vals, 0, batch), bcast_env)
        alias = carry_aliases(split_vals, part, n)
        read_from = {k: p for p, k in alias.items()}

        def empty(p, l):
            ax = out_axes[p]
            if ax is None:
                return jnp.zeros(l.shape, l.dtype)
            return lax.empty(l.shape[:ax] + (n,) + l.shape[ax + 1:], l.dtype)

        def sources(bufs: dict) -> dict:
            return {k: bufs[read_from[k]] if k in read_from else v
                    for k, v in split_vals.items()}

        bufs = lax.fori_loop(
            0, n_chunks,
            lambda i, bufs: put(bufs, chain(window(sources(bufs), i * batch,
                                                   batch), bcast_env),
                                i, i * batch),
            {p: split_vals[alias[p]] if p in alias
             else jax.tree.map(lambda l, p=p: empty(p, l), part[p])
             for p in esc})
        n_main = n_chunks * batch
        if n_main < n:
            tail = chain(window(sources(bufs), n_main, n - n_main), bcast_env)
            bufs = put(bufs, tail, n_chunks, n_main)
        return bufs

    if donate:
        # Dead split inputs arrive as a separate donated argument; the
        # pairing above lets XLA write an output into a donated input's
        # buffer as it goes.
        def mozart_scan_driver_donate(donated: dict, split_vals: dict,
                                      bcast_env: dict):
            return drive({**split_vals, **donated}, bcast_env)

        return jax.jit(mozart_scan_driver_donate, donate_argnums=(0,))

    def mozart_scan_driver(split_vals: dict, bcast_env: dict):
        # Broadcast values ride along as a real jit argument (not a closure
        # capture): the pinned executable must not bake one call's scalars
        # into the compiled program.
        return drive(split_vals, bcast_env)

    return jax.jit(mozart_scan_driver)


@register_executor("scan")
class ScanExecutor(StageExecutor):
    """The whole chunk loop as one compiled XLA program over flat values.

    The driver (``_build_scan_driver``) takes each split input whole, reads
    chunk ``i`` in place with a ``dynamic_slice`` and writes each result
    into whole-size outputs held in the loop carry, so nothing is stacked,
    sliced or reshaped outside it; the ragged tail runs inside the same
    program.  The batch from the §5.2 estimate or the tuner is rounded down
    to the stage's layout tile (``split_tile``) so every chunk starts on a
    tile boundary; an explicit ``batch_elements`` is kept as given.

    Chunk handoff: the driver's outputs are already merged, so a streamed
    output is a ``ChunkStream.from_merged``; a scan or pallas consumer reads
    it whole with zero copies, and an incoming chunk-list stream is
    concatenated once (a counted layout copy).  Dead split inputs are
    donated to the driver under the same structural (plan-derived)
    donate-key rules as the fused driver, so pinned variants never flap and
    warm calls stay zero-retrace.  Stages the driver cannot run (dynamic
    functions, empty splits, an input or a non-reduction output with no
    split axis) go to ``pipelined`` or ``fused``, counted in
    ``ctx.stats["scan_fallbacks"]``.
    """

    tunable = True
    stream_capable = True

    def _batch_and_tile(self, stage: Stage, concrete: dict[tuple, Any], ctx,
                        n: int) -> tuple[int, int]:
        batch = super().choose_batch(stage, concrete, ctx, n)
        if batch_is_explicit(ctx):
            return batch, 1
        tile = _stage_tile(stage, concrete)
        aligned = _aligned(batch, n, tile)
        return aligned, (tile if aligned < n and tile <= batch else 1)

    def choose_batch(self, stage: Stage, concrete: dict[tuple, Any], ctx,
                     n: int) -> int:
        return self._batch_and_tile(stage, concrete, ctx, n)[0]

    def tuning_candidates(self, stage: Stage, concrete: dict[tuple, Any], ctx,
                          est: int, n: int) -> list[int]:
        tile = _stage_tile(stage, concrete)
        return sorted({_aligned(c, n, tile)
                       for c in candidate_batches(est, n)})

    def _fallback(self, name: str, stage: Stage, concrete: dict[tuple, Any],
                  ctx) -> None:
        ctx.stats["scan_fallbacks"] += 1
        get_executor(name).execute(stage, concrete, ctx)

    def execute(self, stage: Stage, concrete: dict[tuple, Any], ctx) -> None:
        if has_dynamic(stage):
            return self._fallback("pipelined", stage, concrete, ctx)
        n = effective_elements(ctx, stage_num_elements(stage, concrete, ctx.pedantic))
        # An empty split has no chunk to loop over: the fused driver runs
        # one degenerate zero-size chunk.  An input or output with no split
        # axis (bar a reduction) cannot be sliced or written in place.
        split_keys = [k for k, si in stage.inputs.items() if si.split_type.splittable]
        if (n == 0 or not split_keys
                or any(split_axis_of(stage.inputs[k].split_type) is None
                       for k in split_keys)
                or any(split_axis_of(t) is None
                       and not isinstance(t, st.ReduceSplit)
                       for nid, t in stage.out_types.items()
                       if nid in stage.escaping)):
            return self._fallback("fused", stage, concrete, ctx)
        batch, tile = self._batch_and_tile(stage, concrete, ctx, n)

        fresh: set[tuple] = set()       # ckeys whose flat value we built
        split_vals: dict[tuple, Any] = {}
        with span("mozart.layout"):
            for key in split_keys:
                v = concrete[key]
                if isinstance(v, ChunkStream):
                    if v.merged is not None:
                        v = v.merged         # already whole: zero copies
                    else:
                        chunks = v.chunks    # one concatenation
                        v = v.split_type.merge(chunks)
                        if len(chunks) > 1:
                            count_layout(ctx, v)
                            fresh.add(stage.ckey(key))
                split_vals[stage.ckey(key)] = v
        bcast_env = {stage.ckey(k): concrete[k] for k, si in stage.inputs.items()
                     if not si.split_type.splittable}

        esc = tuple(stage.escape_positions())
        split_axes = {stage.ckey(k): split_axis_of(stage.inputs[k].split_type)
                      for k in split_keys}
        out_axes = {stage.pos[nid]: split_axis_of(stage.out_types[nid])
                    for nid in stage.escaping}

        # Donation: structural key set shared with the fused driver.  A flat
        # value we concatenated is always safe to donate; a dead stream's
        # held value is donated for real (and the stream marked consumed);
        # an observable stream or a plain array, which may be a producer's
        # retained result, donates a defensive copy.
        donate = tuple(k for k in donatable_input_keys(stage, ctx)
                       if k in split_vals)
        unsafe = undonatable_stream_keys(stage, concrete, ctx, donate) \
            if donate else set()
        driver = pinned_jit(
            stage, ctx, "scan", (esc, batch, donate),
            lambda: _build_scan_driver(stage, esc, split_axes, out_axes,
                                       batch, donate))

        consumed_keys: tuple = ()
        ranges = batch_ranges(n, batch)
        with span("mozart.drive", chunks=len(ranges), tile=tile):
            resilience.maybe_fail("chunk", f"stage {stage.id} scan driver")
            if donate:
                key_of = {stage.ckey(k): k for k in stage.inputs}
                donated = {}
                for ck in donate:
                    val = split_vals.pop(ck)
                    if ck in fresh:
                        donated[ck] = val
                    elif (ck in unsafe or not isinstance(
                            concrete.get(key_of[ck]), ChunkStream)):
                        donated[ck] = jax.tree.map(jnp.array, val)
                        ctx.stats["donation_copies"] += 1
                    else:
                        donated[ck] = val
                        consumed_keys += (ck,)
                outs = driver(donated, split_vals, bcast_env)
                ctx.stats["donated_chunks"] += len(donated)
            else:
                outs = driver(split_vals, bcast_env)
            ctx.stats["chunks"] += len(ranges)
            ctx.stats["calls"] += 1

        # Which outputs stay streams (the handoff plan's decision).
        plan_ho = getattr(ctx, "_handoff", None)
        ho = plan_ho.get(stage.id) if plan_ho else None
        with span("mozart.merge"):
            partials: dict[int, list[Any]] = {}
            for nid in stage.escaping:
                p = stage.pos[nid]
                t = stage.out_types[nid]
                if (ho is not None and p in ho.stream_out
                        and out_axes[p] is not None and len(ranges) > 1):
                    node = next(nd for nd in stage.nodes if nd.id == nid)
                    node.result = ChunkStream.from_merged(outs[p], ranges, t,
                                                          node.out_aval)
                    ctx.stats["streamed_outputs"] += 1
                else:
                    partials[p] = [outs[p]]
            mark_stream_consumed(stage, concrete, ctx, consumed_keys)
            finish_stage(stage, partials, ctx=ctx)
