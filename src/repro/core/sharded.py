"""Mesh execution of Mozart stages: splits = shards (beyond-paper scale-out).

The paper parallelizes chunks over threads of one CPU.  Here the *first*
level of splitting maps onto devices of a ``jax.make_mesh`` via
``shard_map`` — Mozart's split function becomes the sharding rule, and its
associative merge becomes either "already sharded correctly" (concat-style
merges) or a ``psum``-family collective (ReduceSplit).  Within each device
the stage runs the ``scan`` executor's chunk loop (``executor.chunk_loop``)
on its shard, so the two memory tiers (HBM across devices, VMEM within one)
are both handled by the same SA, and the shard program's size does not grow
with its chunk count.

The jitted ``shard_map`` closure is built capture-safe (from ``chain_plan``)
and pinned into the plan cache via ``pinned_jit``; the per-shard chunk size
participates in chunk-size auto-tuning (``tunable = True``), rounded down
to the stage's layout tile as ``scan`` rounds its own, with sample slices
rounded to the mesh extent so they stay shardable.
"""

from __future__ import annotations

import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import split_types as st
from repro.core.executor import chunk_loop, tiled_batch, tiled_candidates
from repro.core.planner import Stage
from repro.core.stage_exec import (
    ChunkStream,
    PedanticError,
    SAMPLE_CHUNKS,
    StageExecutor,
    batch_ranges,
    chain_plan,
    effective_elements,
    get_executor,
    note_materialized,
    note_trace,
    pinned_jit,
    register_executor,
    run_plan,
    split_axis_of,
    stage_num_elements,
)
from repro.core.trace import count_layout, span


@register_executor("sharded")
class ShardedExecutor(StageExecutor):
    """Splits = mesh shards; per-device chunk loop handles the VMEM tier."""

    tunable = True           # tunes the per-shard chunk size
    # Handed-off streams enter WITHOUT a host-side merge: chunk lists are
    # placed per shard (``_ingest_streams`` — device_put on the shard grid,
    # ``rechunk`` at most once for disagreeing grids) and SHARDED-form
    # streams from an earlier sharded stage pass the device-resident global
    # array straight through (zero interior bytes, no all-gather).
    stream_capable = True
    shard_capable = True

    def execute(self, stage: Stage, concrete: dict[tuple, Any], ctx) -> None:
        execute_stage_sharded(stage, concrete, ctx, self)

    # -- tuner integration ---------------------------------------------------
    def _mesh_extent(self, ctx) -> int:
        if ctx.mesh is None:
            return 1
        return math.prod(ctx.mesh.shape[a] for a in ctx.data_axes)

    def tuning_candidates(self, stage: Stage, concrete: dict[tuple, Any], ctx,
                          est: int, n: int) -> list[int]:
        # The tuned quantity is the PER-SHARD chunk size: bracket the §5.2
        # estimate within one local shard's element count.
        n_local = max(1, n // self._mesh_extent(ctx))
        return tiled_candidates(stage, concrete, est, n_local)

    def sample_elems(self, ctx, batch: int, n: int) -> int:
        # Sample slices must stay divisible by the mesh extent or the
        # shard_map split rejects them: give every shard SAMPLE_CHUNKS
        # chunks and round to a multiple of the extent.
        if n <= 0:
            return 0
        m = self._mesh_extent(ctx)
        s = min(n, SAMPLE_CHUNKS * batch * m)
        return max(m, (s // m) * m)


def _pspec_for(split_type: st.SplitType, ndim: int, axes: tuple[str, ...]):
    ax = split_axis_of(split_type)
    if ax is None:
        return P()
    spec = [None] * ndim
    spec[ax] = axes if len(axes) > 1 else axes[0]
    return P(*spec)


def _specs(stage: Stage, concrete: dict[tuple, Any],
           axes: tuple[str, ...]) -> tuple[list, list]:
    """``(in_specs, out_specs)`` of the stage's inputs, in ``stage.inputs``
    order, and of its escaping outputs, in node-id order.  Any input or
    output that cannot be expressed as an axis sharding is replicated."""
    in_specs = []
    for k, si in stage.inputs.items():
        aval = concrete[k]
        ndim = getattr(aval, "ndim", None)
        if si.split_type.splittable and ndim is not None:
            in_specs.append(_pspec_for(si.split_type, ndim, axes))
        else:
            in_specs.append(jax.tree.map(lambda _: P(), aval))
    out_specs = []
    for nid in sorted(stage.escaping):
        t = stage.out_types[nid]
        aval = next(nd.out_aval for nd in stage.nodes if nd.id == nid)
        out_specs.append(jax.tree_util.tree_map(
            lambda l, t=t: _pspec_for(t, len(l.shape), axes), aval))
    return in_specs, out_specs


def _loopable(stage: Stage, concrete: dict[tuple, Any]) -> bool:
    """Whether ``chunk_loop`` can run the stage on a shard: every split
    input is an array with a split axis, and every escaping output has a
    split axis or is a reduction (the loop folds it in its carry)."""
    return (all(split_axis_of(si.split_type) is not None
                and hasattr(concrete[k], "ndim")
                for k, si in stage.inputs.items()
                if si.split_type.splittable)
            and all(split_axis_of(t) is not None
                    or isinstance(t, st.ReduceSplit)
                    for nid, t in stage.out_types.items()
                    if nid in stage.escaping))


def _build_sharded_driver(stage: Stage, mesh, axes, in_specs, out_specs,
                          esc: tuple[int, ...], batch: int,
                          loop: bool) -> Callable:
    """The ``shard_map`` program: on each shard, ``chunk_loop`` over the
    local values at ``batch`` (``loop``), or the chain once on the whole
    shard; reduction outputs are then combined across shards."""
    plan = chain_plan(stage)
    axis_name = axes if len(axes) > 1 else axes[0]
    in_ckeys = [stage.ckey(k) for k in stage.inputs]
    split_axes = {stage.ckey(k): split_axis_of(si.split_type)
                  for k, si in stage.inputs.items() if si.split_type.splittable}
    reduce_types = {stage.pos[nid]: t for nid, t in stage.out_types.items()
                    if nid in stage.escaping and isinstance(t, st.ReduceSplit)}
    out_axes = {stage.pos[nid]: split_axis_of(stage.out_types[nid])
                for nid in stage.escaping}
    drive = chunk_loop(stage, esc, split_axes, out_axes, batch) if loop else None

    def mozart_sharded_driver(*vals):
        env = dict(zip(in_ckeys, vals))
        if drive is not None:
            outs = drive({k: env.pop(k) for k in split_axes}, env)
        else:
            note_trace()
            run_plan(plan, env)
            outs = {p: env[("n", p)] for p in esc}
        return tuple(_psum_like(reduce_types[p], outs[p], axis_name)
                     if p in reduce_types else outs[p] for p in esc)

    return jax.jit(jax.shard_map(
        mozart_sharded_driver, mesh=mesh, in_specs=tuple(in_specs),
        out_specs=tuple(out_specs), check_vma=False))


def _ingest_streams(stage: Stage, concrete: dict[tuple, Any], ctx, mesh,
                    axes, n: int, n_local: int,
                    shard_ranges: list[tuple[int, int]], ho) -> None:
    """Place handed-off ChunkStream inputs onto the mesh without merging.

    Three paths, in order of preference: a SHARDED-form stream whose layout
    already matches the target (same Sharding, shard-grid ranges) passes its
    device-resident global array through untouched (zero interior bytes, no
    all-gather); a chunk-list or merged stream is regrouped onto the shard
    grid (``rechunk`` at most once — counted) and ``device_put`` per shard
    into one global array (device placement is inherent to sharding, like
    splitting an external input, so it is NOT counted as interior traffic);
    anything the shard grid cannot express (pytree leaves, foreign meshes)
    materializes — correct, merely the old cost, counted honestly by
    ``ChunkStream.materialize``.  The copies made here (a rechunk, the
    concatenation of a chunk list) count in ``layout_bytes``."""

    def gather(v: ChunkStream):
        copies = v.merged is None and v.sharded is None and len(v.ranges) > 1
        value = v.materialize()
        if copies:
            count_layout(ctx, value)
        ctx.stats["stream_materialized"] += 1
        return value

    for i, (key, si) in enumerate(stage.inputs.items()):
        v = concrete.get(key)
        if not isinstance(v, ChunkStream):
            continue
        t = si.split_type
        ax = split_axis_of(t)
        leaves = jax.tree_util.tree_leaves(v.aval)
        if (ax is None or len(leaves) != 1
                or v.n != n or len(leaves[0].shape) <= ax):
            concrete[key] = gather(v)
            continue
        global_shape = tuple(leaves[0].shape)
        sharding = NamedSharding(mesh, _pspec_for(t, len(global_shape), axes))
        if v.sharded is not None:
            # Sharded-form stream: reuse the global array as-is when the
            # plan permits it and the layout agrees; a foreign layout
            # (different mesh/spec) gathers and re-splits through shard_map.
            if (ho is not None and i in ho.shard_in
                    and v.sharding == sharding
                    and list(v.ranges) == shard_ranges):
                concrete[key] = v.sharded
                ctx.stats["shard_passthrough"] += 1
            else:
                concrete[key] = gather(v)
            continue
        chunks = list(v.chunks)
        if list(v.ranges) != shard_ranges:
            if len(chunks) != len(v.ranges):
                concrete[key] = gather(v)
                continue
            chunks, copied = t.rechunk(chunks, list(v.ranges), shard_ranges)
            if copied:
                note_materialized(copied, kind="rechunk",
                                  where=f"stage {stage.id} shard ingest "
                                        f"input {i}")
                ctx.stats["layout_bytes"] += copied
            ctx.stats["handoff_rechunks"] += 1
        arrays = []
        for dev, idx in sharding.devices_indices_map(global_shape).items():
            j = (idx[ax].start or 0) // n_local
            if j >= len(chunks):
                break
            arrays.append(jax.device_put(chunks[j], dev))
        else:
            concrete[key] = jax.make_array_from_single_device_arrays(
                global_shape, sharding, arrays)
            ctx.stats["shard_ingests"] += 1
            continue
        concrete[key] = gather(v)


def execute_stage_sharded(stage: Stage, concrete: dict[tuple, Any], ctx,
                          executor: StageExecutor | None = None) -> None:
    mesh = ctx.mesh
    if mesh is None:
        raise ValueError("sharded executor requires mozart.session(mesh=...)")
    axes = ctx.data_axes
    executor = executor or get_executor("sharded")
    n_shards = executor._mesh_extent(ctx)

    n = effective_elements(ctx, stage_num_elements(stage, concrete, ctx.pedantic))
    if n == 0:
        # An empty value has no shard layout (XLA replicates it): the fused
        # driver runs the one degenerate zero-size chunk off the mesh.
        get_executor("fused").execute(stage, concrete, ctx)
        return
    if n % n_shards != 0:
        raise PedanticError(f"stage element count {n} not divisible by mesh "
                            f"data extent {n_shards}")
    n_local = n // n_shards
    shard_ranges = [(i * n_local, (i + 1) * n_local) for i in range(n_shards)]
    plan_ho = getattr(ctx, "_handoff", None)
    ho = plan_ho.get(stage.id) if plan_ho else None
    concrete = dict(concrete)
    with span("mozart.layout"):
        _ingest_streams(stage, concrete, ctx, mesh, axes, n, n_local,
                        shard_ranges, ho)
        in_specs, out_specs = _specs(stage, concrete, axes)
        # An array committed to one device (or to another mesh) cannot enter
        # the shard_map as it is: place every array input on this mesh first
        # (a no-op for one already placed so).
        args = [jax.device_put(v, NamedSharding(mesh, spec))
                if isinstance(v, jax.Array) else v
                for v, spec in zip((concrete[k] for k in stage.inputs), in_specs)]
    # Per-shard chunk size: explicit override > auto-tuner pin > §5.2,
    # rounded down to the layout tile.
    batch, tile = tiled_batch(
        executor.choose_batch(stage, concrete, ctx, n_local),
        stage, concrete, ctx, n_local)
    loop = (ctx.inner_executor != "whole" and batch < n_local
            and _loopable(stage, concrete))
    chunks = len(batch_ranges(n_local, batch)) if loop else 1

    esc = tuple(stage.pos[nid] for nid in sorted(stage.escaping))
    # The plan-cache key records only mesh axis names/extents; the driver
    # bakes the concrete Mesh into the shard_map closure, so two same-shape
    # meshes over DIFFERENT devices must compile separate executables.
    mesh_devices = tuple(d.id for d in mesh.devices.flat)
    shard_fn = pinned_jit(
        stage, ctx, "sharded", (esc, batch, loop, mesh_devices),
        lambda: _build_sharded_driver(stage, mesh, axes, in_specs,
                                      out_specs, esc, batch, loop))
    with span("mozart.drive", chunks=chunks, shards=n_shards, tile=tile):
        results = shard_fn(*args)
        ctx.stats["chunks"] += chunks * n_shards
        ctx.stats["sharded_stages"] += 1

    with span("mozart.merge"):
        by_pos = dict(zip(esc, results))
        for node in stage.nodes:
            p = stage.pos[node.id]
            if p in by_pos:
                res = by_pos[p]
                t = stage.out_types[node.id]
                if (ho is not None and p in ho.stream_out and n_shards > 1
                        and split_axis_of(t) is not None
                        and getattr(res, "sharding", None) is not None):
                    # Emit a device-resident stream: the global array stays
                    # on the mesh carrying its Sharding, so a downstream
                    # sharded stage passes it through with zero interior
                    # bytes and no all-gather; any other consumer gathers
                    # lazily (counted).
                    node.result = ChunkStream.from_sharded(
                        res, shard_ranges, t, node.out_aval, res.sharding)
                    ctx.stats["streamed_outputs"] += 1
                else:
                    node.result = res
            node.done = True


def _psum_like(t: st.ReduceSplit, value, axis_name):
    if t.op_name == "add":
        return jax.lax.psum(value, axis_name)
    if t.op_name == "max":
        return jax.lax.pmax(value, axis_name)
    if t.op_name == "min":
        return jax.lax.pmin(value, axis_name)
    if t.op_name == "mul":
        # no pprod primitive (a log-domain sum is wrong for negatives)
        return jnp.prod(jax.lax.all_gather(value, axis_name), axis=0)
    raise ValueError(t.op_name)
