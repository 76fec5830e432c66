"""Mesh execution of Mozart stages: splits = shards (beyond-paper scale-out).

The paper parallelizes chunks over threads of one CPU.  Here the *first*
level of splitting maps onto devices of a ``jax.make_mesh`` via
``shard_map`` — Mozart's split function becomes the sharding rule, and its
associative merge becomes either "already sharded correctly" (concat-style
merges) or a ``psum``-family collective (ReduceSplit).  Within each device
the stage still runs the fast-memory chunk loop, so the two memory tiers
(HBM across devices, VMEM within one) are both handled by the same SA.

The jitted ``shard_map`` closure is built capture-safe (from ``chain_plan``)
and pinned into the plan cache via ``pinned_jit``; the inner per-shard chunk
loop participates in chunk-size auto-tuning (``tunable = True``), with
sample slices rounded to the mesh extent so they stay shardable.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import split_types as st
from repro.core.planner import Stage
from repro.core.stage_exec import (
    ChunkStream,
    PedanticError,
    SAMPLE_CHUNKS,
    StageExecutor,
    batch_ranges,
    chain_plan,
    effective_elements,
    note_materialized,
    note_trace,
    pinned_jit,
    register_executor,
    run_plan,
    split_axis_of,
    stage_num_elements,
)


@register_executor("sharded")
class ShardedExecutor(StageExecutor):
    """Splits = mesh shards; per-device chunk loop handles the VMEM tier."""

    tunable = True           # tunes the INNER per-shard chunk loop
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
        m = 1
        if ctx.mesh is not None:
            for a in ctx.data_axes:
                m *= ctx.mesh.shape[a]
        return m

    def tuning_candidates(self, stage: Stage, concrete: dict[tuple, Any], ctx,
                          est: int, n: int) -> list[int]:
        # The tuned quantity is the PER-SHARD chunk size: bracket the §5.2
        # estimate within one local shard's element count.
        from repro.core.stage_exec import candidate_batches
        n_local = max(1, n // max(self._mesh_extent(ctx), 1))
        return candidate_batches(est, n_local)

    def sample_elems(self, ctx, batch: int, n: int) -> int:
        # Sample slices must stay divisible by the mesh extent or the
        # shard_map split rejects them: give every shard SAMPLE_CHUNKS
        # chunks and round to a multiple of the extent.
        if n <= 0:
            return 0
        m = max(self._mesh_extent(ctx), 1)
        s = min(n, SAMPLE_CHUNKS * batch * m)
        return max(m, (s // m) * m)


def _pspec_for(split_type: st.SplitType, ndim: int, axes: tuple[str, ...]):
    ax = split_axis_of(split_type)
    if ax is None:
        return P()
    spec = [None] * ndim
    spec[ax] = axes if len(axes) > 1 else axes[0]
    return P(*spec)


def _build_sharded_driver(stage: Stage, mesh, axes, in_specs, out_specs,
                          in_ckeys: list[tuple], in_split_types: list,
                          esc_pos: list[int], out_types_by_pos: dict,
                          n_local: int, batch: int, whole: bool) -> Callable:
    plan = chain_plan(stage)
    axis_name = axes if len(axes) > 1 else axes[0]

    def mozart_sharded_driver(*vals):
        note_trace()
        env = dict(zip(in_ckeys, vals))
        # Per-device fast-memory chunk loop over the local shard.
        if whole or batch >= n_local:
            run_plan(plan, env)
            chunk_outs = {p: [env[("n", p)]] for p in esc_pos}
        else:
            chunk_outs = {p: [] for p in esc_pos}
            for (s, e) in batch_ranges(n_local, batch):
                cenv = {}
                for ck, t in zip(in_ckeys, in_split_types):
                    cenv[ck] = t.split(env[ck], s, e) if t is not None else env[ck]
                run_plan(plan, cenv)
                for p in esc_pos:
                    chunk_outs[p].append(cenv[("n", p)])

        outs = []
        for p in esc_pos:
            t = out_types_by_pos[p]
            merged = t.merge(chunk_outs[p])
            if split_axis_of(t) is None:
                # ReduceSplit & friends: combine partials across shards.
                if isinstance(t, st.ReduceSplit):
                    merged = _psum_like(t, merged, axis_name)
            outs.append(merged)
        return tuple(outs)

    return jax.jit(
        jax.shard_map(
            mozart_sharded_driver,
            mesh=mesh,
            in_specs=tuple(in_specs),
            out_specs=tuple(out_specs),
            check_vma=False,
        )
    )


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
    anything the shard grid cannot express (pytree leaves, zero-element
    grids, foreign meshes) materializes — correct, merely the old cost,
    counted honestly by ``ChunkStream.materialize``."""
    for i, (key, si) in enumerate(stage.inputs.items()):
        v = concrete.get(key)
        if not isinstance(v, ChunkStream):
            continue
        t = si.split_type
        ax = split_axis_of(t)
        leaves = jax.tree_util.tree_leaves(v.aval)
        if (ax is None or n_local <= 0 or len(leaves) != 1
                or v.n != n or len(leaves[0].shape) <= ax):
            concrete[key] = v.materialize()
            ctx.stats["stream_materialized"] += 1
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
                concrete[key] = v.materialize()
                ctx.stats["stream_materialized"] += 1
            continue
        chunks = list(v.chunks)
        if list(v.ranges) != shard_ranges:
            if len(chunks) != len(v.ranges):
                concrete[key] = v.materialize()
                ctx.stats["stream_materialized"] += 1
                continue
            chunks, copied = t.rechunk(chunks, list(v.ranges), shard_ranges)
            if copied:
                note_materialized(copied, kind="rechunk",
                                  where=f"stage {stage.id} shard ingest "
                                        f"input {i}")
            ctx.stats["handoff_rechunks"] += 1
        arrays = []
        ok = True
        for dev, idx in sharding.devices_indices_map(global_shape).items():
            j = (idx[ax].start or 0) // n_local
            if j >= len(chunks):
                ok = False
                break
            arrays.append(jax.device_put(chunks[j], dev))
        if not ok:
            concrete[key] = v.materialize()
            ctx.stats["stream_materialized"] += 1
            continue
        concrete[key] = jax.make_array_from_single_device_arrays(
            global_shape, sharding, arrays)
        ctx.stats["shard_ingests"] += 1


def execute_stage_sharded(stage: Stage, concrete: dict[tuple, Any], ctx,
                          executor: StageExecutor | None = None) -> None:
    mesh = ctx.mesh
    if mesh is None:
        raise ValueError("sharded executor requires mozart.session(mesh=...)")
    axes = ctx.data_axes
    n_shards = 1
    for a in axes:
        n_shards *= mesh.shape[a]

    n = effective_elements(ctx, stage_num_elements(stage, concrete, ctx.pedantic))
    if n % n_shards != 0:
        raise PedanticError(
            f"stage element count {n} not divisible by mesh data extent {n_shards}"
        )
    n_local = n // n_shards
    shard_ranges = [(i * n_local, (i + 1) * n_local) for i in range(n_shards)]
    plan_ho = getattr(ctx, "_handoff", None)
    ho = plan_ho.get(stage.id) if plan_ho else None
    concrete = dict(concrete)
    _ingest_streams(stage, concrete, ctx, mesh, axes, n, n_local,
                    shard_ranges, ho)
    from repro.core.stage_exec import get_executor
    executor = executor or get_executor("sharded")
    # Inner per-shard chunk size: explicit override > auto-tuner pin > §5.2.
    batch = executor.choose_batch(stage, concrete, ctx, max(n_local, 1))
    whole = ctx.inner_executor == "whole"

    # Any input/output we cannot express as an axis-sharding falls back to
    # replicated-in / merged-out handling.
    in_keys = list(stage.inputs)
    in_specs = []
    for k in in_keys:
        si = stage.inputs[k]
        aval = concrete[k]
        ndim = getattr(aval, "ndim", None)
        if si.split_type.splittable and ndim is not None:
            in_specs.append(_pspec_for(si.split_type, ndim, axes))
        else:
            in_specs.append(
                jax.tree_util.tree_map(lambda _: P(), aval)
                if not hasattr(aval, "ndim") else P()
            )

    out_ids = sorted(stage.escaping)
    esc_pos = [stage.pos[nid] for nid in out_ids]
    out_specs = []
    for nid in out_ids:
        t = stage.out_types[nid]
        aval = _aval_of_node(stage, nid)
        if split_axis_of(t) is not None:
            out_specs.append(jax.tree_util.tree_map(
                lambda l: _pspec_for(t, len(l.shape), axes), aval))
        else:
            out_specs.append(jax.tree_util.tree_map(lambda l: P(), aval))

    in_ckeys = [stage.ckey(k) for k in in_keys]
    in_split_types = [stage.inputs[k].split_type
                      if stage.inputs[k].split_type.splittable else None
                      for k in in_keys]
    out_types_by_pos = {stage.pos[nid]: stage.out_types[nid] for nid in out_ids}

    # The plan-cache key records only mesh axis names/extents; the driver
    # bakes the concrete Mesh into the shard_map closure, so two same-shape
    # meshes over DIFFERENT devices must compile separate executables.
    mesh_devices = tuple(d.id for d in mesh.devices.flat)
    shard_fn = pinned_jit(
        stage, ctx, "sharded",
        (tuple(esc_pos), batch, n_local, whole, mesh_devices),
        lambda: _build_sharded_driver(
            stage, mesh, axes, in_specs, out_specs, in_ckeys, in_split_types,
            esc_pos, out_types_by_pos, n_local, batch, whole))
    # An array committed to one device (or to another mesh) cannot enter
    # the shard_map as it is: place every array input on this mesh first
    # (a no-op for one already placed so).
    args = [jax.device_put(v, NamedSharding(mesh, spec))
            if isinstance(v, jax.Array) else v
            for v, spec in zip((concrete[k] for k in in_keys), in_specs)]
    results = shard_fn(*args)
    ctx.stats["sharded_stages"] += 1
    # merge() of a single piece is the identity for concat-style types.
    by_pos = dict(zip(esc_pos, results))
    for node in stage.nodes:
        p = stage.pos[node.id]
        if p in by_pos:
            res = by_pos[p]
            t = out_types_by_pos[p]
            if (ho is not None and p in ho.stream_out and n_shards > 1
                    and n_local > 0 and split_axis_of(t) is not None
                    and getattr(res, "sharding", None) is not None):
                # Emit a device-resident stream: the global array stays on
                # the mesh carrying its Sharding, so a downstream sharded
                # stage passes it through with zero interior bytes and no
                # all-gather; any other consumer gathers lazily (counted).
                node.result = ChunkStream.from_sharded(
                    res, shard_ranges, t, node.out_aval, res.sharding)
                ctx.stats["streamed_outputs"] += 1
            else:
                node.result = res
        node.done = True


def _aval_of_node(stage: Stage, nid: int):
    for n in stage.nodes:
        if n.id == nid:
            return n.out_aval
    raise KeyError(nid)


def _psum_like(t: st.ReduceSplit, value, axis_name):
    if t.op_name == "add":
        return jax.lax.psum(value, axis_name)
    if t.op_name == "max":
        return jax.lax.pmax(value, axis_name)
    if t.op_name == "min":
        return jax.lax.pmin(value, axis_name)
    if t.op_name == "mul":
        # no pprod primitive: log-domain trick is wrong for negatives; use
        # all_gather + sequential combine (rare path).
        g = jax.lax.all_gather(value, axis_name)
        out = g[0]
        for i in range(1, g.shape[0]):
            out = out * g[i]
        return out
    raise ValueError(t.op_name)
