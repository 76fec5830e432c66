"""Lower a planned Mozart stage onto the split-pipeline Pallas kernel.

Eligibility (checked, with fallback to the fused executor):
  * every node is annotated ``elementwise=True``, or is a whole-array
    reduction whose output type is ``ReduceSplit`` (sum/max/min/prod);
  * every splittable stage input is a 1-D ``ArraySplit`` along axis 0 and
    all agree on length;
  * broadcast inputs are scalars ();
  * reductions are only consumed outside the stage (they produce partials);
  * the chain uses only primitives the kernel can lower
    (``split_pipeline.LOWERABLE_PRIMITIVES``).  A chain that fails this
    last rule is *declined*: it runs on ``fused`` and is counted in
    ``ctx.stats["pallas_declined"]`` with the primitive named
    (``pallas_declined:<primitive>``).

The stage chain itself is *reused as-is*: the kernel body calls each
annotated function's original implementation on VMEM-resident tiles — the
library function is still unmodified, it simply runs on a ``(rows, 128)``
block.

The whole kernel launch (pad → pallas_call → unpad/combine) is wrapped in
one jitted driver and pinned into the plan cache (``pinned_jit``), so warm
executions of a cached plan reuse the compiled program instead of re-tracing
``pallas_call`` every evaluation.  The driver is compiled explicitly the
first time it sees an argument signature: anything the compiler raises
there is a ``resilience.KernelRefused``, which propagates — a refused kernel
is never silently replaced by another executor.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core import resilience
from repro.core import split_types as st
from repro.core.graph import NodeRef
from repro.core.planner import Stage
from repro.core.stage_exec import (
    ChunkStream,
    StageExecutor,
    batch_ranges,
    chain_plan,
    donatable_input_keys,
    effective_elements,
    get_executor,
    note_materialized,
    note_trace,
    pinned_jit,
    pinned_table,
    register_executor,
    stage_num_elements,
)
from repro.core.trace import count_layout, span
from repro.kernels import split_pipeline as sp


def _effective_block(batch: int, n: int, cap: int) -> int:
    """The hardware block an element-count candidate actually compiles to
    (mirrors ``padded_layout``: clamp to n, round up to the 8x128 sublane x
    lane tile), bounded by the VMEM ``cap``."""
    return min(sp.padded_layout(n, batch)[0], cap)


def _itemsizes(x) -> int:
    """Summed element width of the array leaves of ``x`` (value or aval)."""
    return sum(jnp.dtype(leaf.dtype).itemsize for leaf in jax.tree.leaves(x)
               if hasattr(leaf, "dtype"))


def _block_cap(stage: Stage, ctx) -> int:
    """Largest block whose VMEM footprint fits the chip's kernel limit:
    double-buffered input and concat-output tiles, plus one live tile per
    chain value (every node output) and the tail mask's index tiles."""
    io = 0
    for si in stage.inputs.values():
        if si.split_type.splittable:
            v = si.value
            if isinstance(v, NodeRef):
                v = ctx.graph.nodes[v.node_id].out_aval
            io += _itemsizes(v)
    live = 8                                  # int32 index + bool mask tiles
    for node in stage.nodes:
        size = _itemsizes(node.out_aval)
        live += size
        if (node.id in stage.escaping
                and not isinstance(stage.out_types[node.id], st.ReduceSplit)):
            io += size
    return sp.block_cap(ctx.chip.kernel_vmem_limit_bytes, 2 * io + live)


@register_executor("pallas")
class PallasExecutor(StageExecutor):
    """Lower eligible elementwise stages onto the split-pipeline TPU kernel;
    anything the kernel cannot express falls back to the fused driver.

    Chunk handoff: an incoming chunk-list ``ChunkStream`` is stacked
    DIRECTLY into the kernel's padded ``(rows, 128)`` launch layout
    (equal-grid fast path; ``rechunk`` for disagreeing grids) instead of
    being merged and re-padded, and a stream that holds its whole value (a
    scan output) is laid out as a plain array;
    launch buffers the stage's handoff plan proves dead here are donated to
    the jitted launch driver under the same structural donate-key rules as
    the fused/scan drivers."""

    tunable = True
    stream_capable = True

    def execute(self, stage: Stage, concrete: dict[tuple, Any], ctx) -> None:
        if not try_execute_stage_pallas(stage, concrete, ctx, self):
            get_executor("fused").execute(stage, concrete, ctx)

    # -- block-shape-aware tuning --------------------------------------------
    def tuning_candidates(self, stage: Stage, concrete: dict[tuple, Any], ctx,
                          est: int, n: int) -> list[int]:
        """Round the §5.2 bracket to valid hardware blocks.

        The kernel only ever launches BLOCK = k x 1024 (8 sublanes x 128
        lanes) up to the stage's VMEM cap, so raw element-count candidates
        that resolve to the SAME block are duplicates — measuring them would
        time one compiled shape twice and call the timer noise a tuning
        decision.  Candidates are therefore rounded to their effective
        block first and deduplicated; the chosen block *shape* is recorded
        in the plan entry (``PlanEntry.block_shape``)."""
        from repro.core.stage_exec import candidate_batches
        if n <= 0:
            return [1]
        cap = _block_cap(stage, ctx)
        seen: dict[int, int] = {}
        for c in candidate_batches(est, n):
            b = _effective_block(c, n, cap)
            seen.setdefault(b, min(b, n))
        return sorted(set(seen.values()))

    def note_pinned(self, stage: Stage, ctx, entry, batch: int, n: int) -> None:
        block = _effective_block(batch, n, _block_cap(stage, ctx))
        entry.pin_block_shape(stage.id, sp.block_shape(block))


def _eligible(stage: Stage, concrete: dict[tuple, Any]) -> bool:
    for node in stage.nodes:
        t = stage.out_types[node.id]
        if node.fn.sa.elementwise:
            continue
        if isinstance(t, st.ReduceSplit):
            continue
        return False
    for key, si in stage.inputs.items():
        v = concrete[key]
        if si.split_type.splittable:
            if not isinstance(si.split_type, st.ArraySplit):
                return False
            if si.split_type.axis != 0 or len(si.split_type.shape) != 1:
                return False
        else:
            if getattr(v, "shape", ()) not in ((), (1,)):
                return False
    # reductions must not feed later nodes inside this stage
    for node in stage.nodes:
        if isinstance(stage.out_types[node.id], st.ReduceSplit):
            for other in stage.nodes:
                for v in other.bound.values():
                    if isinstance(v, NodeRef) and v.node_id == node.id:
                        return False
    return True


def _make_chain_fn(stage: Stage, split_ckeys: list[tuple],
                   bcast_ckeys: list[tuple], esc_pos: list[int],
                   out_kinds: list[tuple[str, str]]) -> Callable:
    """The stage chain as the kernel body calls it: ``chain_fn(blocks,
    bcasts)`` -> escaping values (a reduce output is its PRE-reduction
    block; the kernel masks the tail padding and reduces)."""
    plan = chain_plan(stage)
    reduce_keys = {("n", stage.pos[n.id]) for n in stage.nodes
                   if isinstance(stage.out_types[n.id], st.ReduceSplit)}

    def chain_fn(blocks, bcasts):
        env: dict[Any, Any] = dict(zip(split_ckeys, blocks))
        env.update(zip(bcast_ckeys, bcasts))
        reduce_src: dict[tuple, Any] = {}
        for fn, out_key, srcs, _raw in plan:
            kw = {}
            src = None
            for name, key, static in srcs:
                if key is None:
                    kw[name] = static
                    continue
                kw[name] = env[key]
                if src is None:
                    src = kw[name]
            if out_key in reduce_keys:
                # Nothing in the stage reads a reduction (eligibility), and
                # the kernel reduces the block itself, tail masked.
                reduce_src[out_key] = src
                continue
            env[out_key] = fn.fn(**kw)        # unmodified library fn
        return [reduce_src[("n", p)] if kind == "reduce" else env[("n", p)]
                for p, (kind, _) in zip(esc_pos, out_kinds)]

    return chain_fn


class _KernelLaunch:
    """The jitted launch driver, compiled explicitly the first time each
    argument signature arrives.  That compile is where the TPU compiler
    refuses a kernel (block layout, unimplemented primitive, VMEM), so
    whatever it raises becomes ``KernelRefused`` — told apart from a
    failure of the run itself.  The call after it reuses the executable."""

    def __init__(self, jitted: Callable, where: str):
        self._jitted = jitted
        self._where = where
        self._compiled: set = set()

    def __call__(self, donated: dict, rest: dict, bcast_vals: list, n: int):
        args = (donated, rest, bcast_vals)
        sig = (n, jax.tree.structure(args),
               tuple(jax.typeof(x) for x in jax.tree.leaves(args)))
        if sig not in self._compiled:
            try:
                self._jitted.lower(*args, n).compile()
            except Exception as e:  # noqa: BLE001 — every compiler refusal
                raise resilience.KernelRefused(
                    f"{self._where}: {type(e).__name__}: {e}") from e
            self._compiled.add(sig)
        return self._jitted(*args, n)


def _build_pallas_driver(stage: Stage, chain_fn: Callable, n_split: int,
                         out_kinds: list[tuple[str, str]], out_dtypes: list,
                         block: int, vmem_limit: int) -> _KernelLaunch:
    def mozart_pallas_driver(donated: dict, rest: dict, bcast_vals, n: int):
        # Launch buffers arrive prebuilt in the padded (rows, 128) layout
        # (position-keyed so donated and retained buffers reassemble in
        # split-key order); the true length ``n`` is a static argument —
        # the tail mask must never come from a stale closure.
        note_trace()
        bufs = {**rest, **donated}
        split2d = [bufs[i] for i in range(n_split)]
        return sp.split_pipeline_call_2d(
            chain_fn, split2d, bcast_vals, out_kinds, out_dtypes, n, block,
            vmem_limit)

    return _KernelLaunch(
        jax.jit(mozart_pallas_driver, static_argnums=(3,),
                donate_argnums=(0,)),
        f"stage {stage.id} split-pipeline kernel, block {block}")


def _to_launch_layout(v: Any, n: int, block: int, stage: Stage, ck: tuple,
                      ctx) -> tuple[Any, bool]:
    """One split input as its ``(rows, 128)`` launch buffer.

    Returns ``(buffer, fresh)`` — ``fresh`` means the buffer was assembled
    here (stack/pad/reshape copies) and may be donated without endangering
    anyone else's storage; a chunk list's buffer always is.  A stream that
    already holds its whole value (a scan driver's output, scan→pallas)
    takes the plain-array path; a chunk-list ``ChunkStream`` stacks its
    chunks straight into the layout (equal-grid fast path; ``rechunk`` for
    disagreeing grids) — ``materialize()`` is never called.

    Building the buffer EAGERLY (outside the pinned driver) costs a few
    extra dispatches per call, and is deliberate twice over: the driver's
    argument shape is identical whether a stream arrived or a whole array
    did (cross-evaluation arrival can flap call-to-call — inside-jit
    padding would retrace on every flap, breaking the warm zero-retrace
    invariant), and only an argument buffer can be DONATED (a padded
    intermediate built inside the jit has no donation story).  Every copy
    issued here counts in ``ctx.stats["layout_bytes"]``."""
    if isinstance(v, ChunkStream) and v.merged is not None:
        v = v.merged
    if not isinstance(v, ChunkStream):
        buf = sp.pad_to_layout(v, n, block)
        count_layout(ctx, buf, buf)        # the pad, then its reshape
        return buf, sp._round_up(n, block) > n

    grid_ranges = batch_ranges(n, block)
    chunks, ranges = v.chunks, v.ranges
    if ranges != grid_ranges:
        chunks, copied = v.split_type.rechunk(chunks, ranges, grid_ranges)
        note_materialized(copied, kind="rechunk",
                          where=f"stage {stage.id} input {ck}")
        ctx.stats["handoff_rechunks"] += 1
    sizes = [e - s for s, e in grid_ranges]
    ragged = sizes[-1] < block
    main = chunks[:-1] if ragged else chunks
    rows = []
    if main:
        rows.append(jnp.stack(main).reshape(-1, sp.LANES))
    if ragged:
        rows.append(jnp.pad(chunks[-1], (0, block - sizes[-1]))
                    .reshape(-1, sp.LANES))
    count_layout(ctx, rows, rows)          # stack or pad, then reshape
    buf = rows[0] if len(rows) == 1 else jnp.concatenate(rows, axis=0)
    count_layout(ctx, buf if len(rows) > 1 else ())
    return buf, True


def _declined(stage: Stage, concrete: dict[tuple, Any], ctx,
              split_keys: list, bcast_keys: list, chain_fn: Callable) -> list:
    """The chain's primitives the kernel cannot lower (empty: launch it).
    Decided once per stage template and kept with its pinned drivers."""
    table = pinned_table(stage, ctx)
    key = (stage.id, "pallas_unlowerable")
    if key not in table:
        def dtype_of(v):
            v = v.aval if isinstance(v, ChunkStream) else v
            return getattr(v, "dtype", None) or jnp.result_type(v)
        table[key] = sp.unlowerable_primitives(
            chain_fn, [dtype_of(concrete[k]) for k in split_keys],
            [dtype_of(concrete[k]) for k in bcast_keys])
    return table[key]


def try_execute_stage_pallas(stage: Stage, concrete: dict[tuple, Any], ctx,
                             executor: StageExecutor | None = None) -> bool:
    if not _eligible(stage, concrete):
        return False

    split_keys = [k for k, si in stage.inputs.items() if si.split_type.splittable]
    bcast_keys = [k for k, si in stage.inputs.items() if not si.split_type.splittable]
    if not split_keys:
        return False

    escape_ids = sorted(stage.escaping)
    esc_pos = [stage.pos[nid] for nid in escape_ids]
    out_kinds = []
    out_dtypes = []
    for nid in escape_ids:
        t = stage.out_types[nid]
        node = next(nd for nd in stage.nodes if nd.id == nid)
        if isinstance(t, st.ReduceSplit):
            out_kinds.append(("reduce", t.op_name))
        else:
            out_kinds.append(("concat", ""))
        out_dtypes.append(node.out_aval.dtype)
    chain_fn = _make_chain_fn(
        stage, [stage.ckey(k) for k in split_keys],
        [stage.ckey(k) for k in bcast_keys], esc_pos, out_kinds)

    unlowerable = _declined(stage, concrete, ctx, split_keys, bcast_keys,
                            chain_fn)
    if unlowerable:
        ctx.stats["pallas_declined"] += 1
        for prim in unlowerable:
            ctx.stats[f"pallas_declined:{prim}"] += 1
        return False

    executor = executor or get_executor("pallas")
    n = effective_elements(ctx, stage_num_elements(stage, concrete, ctx.pedantic))
    if n == 0:
        return False                   # empty split: no grid to launch
    batch = executor.choose_batch(stage, concrete, ctx, n)
    block = _effective_block(batch, n, _block_cap(stage, ctx))

    entry = getattr(ctx, "_plan_entry", None)
    if entry is not None:
        # The block SHAPE this launch compiles to, persisted for warm starts
        # and EXPLAIN tooling (idempotent: no-op when already recorded).
        entry.pin_block_shape(stage.id, sp.block_shape(block))

    # Structural donate set (shared rules with the fused/scan drivers): the
    # positions are part of the pinned variant key, so warm calls never flap.
    donate_cks = set(donatable_input_keys(stage, ctx))
    donate_pos = tuple(i for i, k in enumerate(split_keys)
                       if stage.ckey(k) in donate_cks)

    vmem_limit = ctx.chip.kernel_vmem_limit_bytes
    driver = pinned_jit(
        stage, ctx, "pallas",
        (tuple(esc_pos), block, donate_pos),
        lambda: _build_pallas_driver(
            stage, chain_fn, len(split_keys), out_kinds, out_dtypes, block,
            vmem_limit))

    donated: dict[int, Any] = {}
    rest: dict[int, Any] = {}
    with span("mozart.layout"):
        for i, k in enumerate(split_keys):
            buf, fresh = _to_launch_layout(concrete[k], n, block, stage,
                                           stage.ckey(k), ctx)
            if i not in donate_pos:
                rest[i] = buf
            elif fresh:
                donated[i] = buf       # our own assembly: donation is free
            else:
                # A whole array whose launch view may alias the producer's
                # retained result: donate a copy.
                donated[i] = jnp.array(buf)
                ctx.stats["donation_copies"] += 1
    if donated:
        ctx.stats["donated_chunks"] += len(donated)

    grid = sp._round_up(n, block) // block
    with span("mozart.drive", chunks=grid):
        outs = driver(donated, rest, [concrete[k] for k in bcast_keys], n)
    ctx.stats["chunks"] += grid
    with span("mozart.merge"):
        results = sp.unpad_outputs(outs, out_kinds, n)
        for o, r, (kind, _) in zip(outs, results, out_kinds):
            if kind == "concat":           # the reshape, then the slice
                count_layout(ctx, o, r if r.size < o.size else ())
        for nid, res in zip(escape_ids, results):
            node = next(nd for nd in stage.nodes if nd.id == nid)
            node.result = res
    for node in stage.nodes:
        node.done = True
    ctx.stats["pallas_stages"] += 1
    return True
