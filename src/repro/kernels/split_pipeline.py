"""Split-pipeline kernel: a Mozart stage as ONE VMEM-tiled Pallas kernel.

This is the paper's core mechanism adapted to the TPU memory hierarchy.
On CPU, Mozart keeps a chunk of every pipeline value resident in L2 while a
driver loop calls each black-box function on it.  On TPU the analogous fast
memory is VMEM: this kernel streams ``(block // 128, 128)`` tiles of every
input from HBM into VMEM (double-buffered by the Pallas pipeline machinery),
applies the *entire* stage chain while the tile is resident, and writes only
the stage's escaping outputs back to HBM.  Intermediates never touch HBM at
all — a strictly stronger guarantee than the CPU version (which still writes
chunk-sized intermediates to cache-resident buffers).

The stage chain is supplied as a traceable ``chain_fn`` built by
``repro.core.pallas_exec`` from the planned stage, so ANY elementwise-
annotated library function participates without modification.

Layout (what the TPU compiler accepts — checked by tests/test_tpu_compile.py):

* a 1-D logical array of ``n`` elements is padded to a multiple of ``block``
  and viewed as ``(n_pad // 128, 128)``: rows of 128 lanes.  A grid step
  owns ``block // 128`` rows, a multiple of 8 sublanes, so ``block`` is a
  multiple of ``MIN_BLOCK`` = 8 x 128 elements;
* broadcast scalars travel whole in SMEM as 32-bit ``(1,)`` vectors and are
  cast back to their own dtype inside the kernel;
* every reduce output writes one ``(8, 128)`` partial tile per grid step
  (the block folded by elementwise ops, no cross-lane reduction); the
  caller combines the ``(grid * 8, 128)`` partials.

The kernel's scoped VMEM limit is explicit (``vmem_limit_bytes``), and
``block_cap`` bounds the block so the double-buffered tiles plus the
chain's live tiles fit under it.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import hardware

LANES = 128
SUBLANES = 8
MIN_BLOCK = LANES * SUBLANES     # 1024

#: primitives a stage chain may use inside the kernel: the elementwise and
#: layout primitives the Pallas TPU lowering implements.  A chain with any
#: other primitive is declined before launch (``unlowerable_primitives``)
#: and runs elsewhere — ``erf`` (Black–Scholes), ``asin`` (Haversine),
#: ``atan2`` and ``expm1`` have no Pallas TPU lowering.  The compile tests
#: hold this set to what the compiler really accepts.
LOWERABLE_PRIMITIVES = frozenset({
    # elementwise arithmetic and math
    "abs", "add", "ceil", "clamp", "cos", "div", "exp", "exp2", "floor",
    "integer_pow", "log", "log1p", "logistic", "max", "min", "mul", "neg",
    "nextafter", "pow", "rem", "round", "rsqrt", "sign", "sin", "sqrt",
    "square", "sub", "tan", "tanh", "erf_inv",
    # comparisons, logic, selection, bits
    "and", "eq", "ge", "gt", "is_finite", "le", "lt", "ne", "not", "or",
    "xor", "select_n", "shift_left", "shift_right_arithmetic",
    "shift_right_logical", "population_count", "clz",
    # dtype and layout
    "convert_element_type", "bitcast_convert_type", "broadcast_in_dim",
    "reshape", "squeeze", "iota",
    # call wrappers (their bodies are checked too)
    "jit", "custom_jvp_call", "custom_vjp_call", "stop_gradient",
})

_COMBINE = {"add": jnp.add, "mul": jnp.multiply,
            "max": jnp.maximum, "min": jnp.minimum}
_REDUCE = {"add": jnp.sum, "mul": jnp.prod, "max": jnp.max, "min": jnp.min}


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def reduce_identity(op: str, dtype) -> jax.Array:
    """The value padding takes so it cannot change an ``op`` reduction."""
    dtype = jnp.dtype(dtype)
    if op == "add":
        return jnp.zeros((), dtype)
    if op == "mul":
        return jnp.ones((), dtype)
    if jnp.issubdtype(dtype, jnp.floating):
        v = -jnp.inf if op == "max" else jnp.inf
    elif dtype == jnp.bool_:
        v = op == "min"
    else:
        info = jnp.iinfo(dtype)
        v = info.min if op == "max" else info.max
    return jnp.asarray(v, dtype)


def _fold_rows(x: jax.Array, op: str) -> jax.Array:
    """Fold a ``(rows, 128)`` tile to ``(8, 128)`` with elementwise ``op``:
    a tree over 8-row groups, so every slice stays sublane-aligned."""
    combine = _COMBINE[op]
    while x.shape[0] > SUBLANES:
        groups = x.shape[0] // SUBLANES
        half = (groups // 2) * SUBLANES
        y = combine(x[:half], x[half:2 * half])
        if groups % 2:
            head = combine(y[:SUBLANES], x[2 * half:])
            y = head if half == SUBLANES else jnp.concatenate(
                [head, y[SUBLANES:]], axis=0)
        x = y
    return x


def _smem_dtype(dtype):
    """SMEM holds 32-bit words: the carrier dtype of a broadcast scalar."""
    dtype = jnp.dtype(dtype)
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.float32
    if jnp.issubdtype(dtype, jnp.unsignedinteger):
        return jnp.uint32
    return jnp.int32


def _pipeline_kernel(
    n_split: int,
    bcast_dtypes: Sequence[Any],
    out_kinds: Sequence[tuple[str, str]],   # ("concat", _) | ("reduce", op)
    chain_fn: Callable,
    n_total: int,
    block: int,
    *refs,
):
    n_bcast = len(bcast_dtypes)
    split_refs = refs[:n_split]
    bcast_refs = refs[n_split:n_split + n_bcast]
    out_refs = refs[n_split + n_bcast:]

    rows = block // LANES
    blocks = [r[...] for r in split_refs]                 # (rows, 128) in VMEM
    bcasts = [r[0].astype(dt) for r, dt in zip(bcast_refs, bcast_dtypes)]

    outs = chain_fn(blocks, bcasts)                       # whole stage in VMEM

    mask = None
    if n_total % block and any(kind == "reduce" for kind, _ in out_kinds):
        # Tail padding exists: keep it out of every reduction.
        shape = (rows, LANES)
        idx = (pl.program_id(0) * block
               + jax.lax.broadcasted_iota(jnp.int32, shape, 0) * LANES
               + jax.lax.broadcasted_iota(jnp.int32, shape, 1))
        mask = idx < n_total

    for (kind, op), o_ref, val in zip(out_kinds, out_refs, outs):
        val = jnp.broadcast_to(val, (rows, LANES)).astype(o_ref.dtype)
        if kind == "concat":
            o_ref[...] = val
            continue
        if mask is not None:
            val = jnp.where(mask, val, reduce_identity(op, o_ref.dtype))
        o_ref[...] = _fold_rows(val, op)


def _primitive_names(jaxpr) -> set:
    """Every primitive in ``jaxpr`` and the jaxprs nested in its params."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    names: set = set()
    stack = [jaxpr]
    while stack:
        jx = stack.pop()
        for eqn in jx.eqns:
            names.add(eqn.primitive.name)
            vals = list(eqn.params.values())
            while vals:
                v = vals.pop()
                if isinstance(v, ClosedJaxpr):
                    stack.append(v.jaxpr)
                elif isinstance(v, Jaxpr):
                    stack.append(v)
                elif isinstance(v, (tuple, list)):
                    vals.extend(v)
    return names


def unlowerable_primitives(chain_fn: Callable, split_dtypes: Sequence[Any],
                           bcast_dtypes: Sequence[Any]) -> list[str]:
    """The primitives of ``chain_fn`` outside ``LOWERABLE_PRIMITIVES``
    (sorted; empty means the kernel can lower the chain).  Traces the chain
    abstractly on one minimal tile — nothing is compiled or run."""
    blocks = [jax.ShapeDtypeStruct(block_shape(MIN_BLOCK), dt)
              for dt in split_dtypes]
    bcasts = [jax.ShapeDtypeStruct((), dt) for dt in bcast_dtypes]
    jaxpr = jax.make_jaxpr(chain_fn)(blocks, bcasts).jaxpr
    return sorted(_primitive_names(jaxpr) - LOWERABLE_PRIMITIVES)


def padded_layout(n: int, block_elems: int) -> tuple[int, int, int]:
    """(block, n_pad, grid) the kernel will launch for ``n`` elements."""
    block = max(MIN_BLOCK, _round_up(min(block_elems, max(n, 1)), MIN_BLOCK))
    n_pad = _round_up(n, block)
    return block, n_pad, n_pad // block


def block_shape(block: int) -> tuple[int, int]:
    """The ``(rows, lanes)`` tile one grid step owns for ``block`` elements."""
    return (block // LANES, LANES)


def block_cap(vmem_limit_bytes: int, bytes_per_element: int) -> int:
    """Largest block (a ``MIN_BLOCK`` multiple, at least ``MIN_BLOCK``) whose
    per-element VMEM footprint fits under ``vmem_limit_bytes``."""
    fit = vmem_limit_bytes // max(bytes_per_element, 1)
    return max(MIN_BLOCK, (fit // MIN_BLOCK) * MIN_BLOCK)


def pad_to_layout(x: jax.Array, n: int, block: int) -> jax.Array:
    """View a 1-D logical array as the kernel's ``(rows, 128)`` layout."""
    n_pad = _round_up(n, block)
    return jnp.pad(x, (0, n_pad - n)).reshape(n_pad // LANES, LANES)


def split_pipeline_call_2d(
    chain_fn: Callable,
    split2d: Sequence[jax.Array],
    bcast_inputs: Sequence[Any],
    out_kinds: Sequence[tuple[str, str]],
    out_dtypes: Sequence[Any],
    n: int,
    block: int,
    vmem_limit_bytes: int,
    interpret: bool | None = None,
):
    """Padded-layout entry point: launch on prebuilt ``(rows, 128)`` buffers.

    Returns the kernel's PADDED outputs — ``(rows, 128)`` for concat
    outputs, ``(grid * 8, 128)`` reduce partials — leaving the
    unpad/combine to the caller (``unpad_outputs``).  Splitting the
    lifecycle this way lets the caller build the launch buffers however it
    likes (pad a whole array, stack a handed-off chunk list) and DONATE them
    to a jitted wrapper: a donated ``(rows, 128)`` input can back a
    same-shaped padded output.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    total_rows = int(split2d[0].shape[0])
    rows, _ = block_shape(block)
    grid = total_rows // rows
    bcast_dtypes = [jnp.result_type(b) for b in bcast_inputs]
    bcast1d = [jnp.asarray(b).astype(_smem_dtype(dt)).reshape(1)
               for b, dt in zip(bcast_inputs, bcast_dtypes)]

    tile = pl.BlockSpec((rows, LANES), lambda i: (i, 0))
    in_specs = ([tile] * len(split2d)
                + [pl.BlockSpec(memory_space=pltpu.SMEM)] * len(bcast1d))
    out_specs = []
    out_shapes = []
    for (kind, _), dt in zip(out_kinds, out_dtypes):
        if kind == "concat":
            out_specs.append(tile)
            out_shapes.append(jax.ShapeDtypeStruct((total_rows, LANES), dt))
        else:
            out_specs.append(pl.BlockSpec((SUBLANES, LANES), lambda i: (i, 0)))
            out_shapes.append(
                jax.ShapeDtypeStruct((grid * SUBLANES, LANES), dt))

    kernel = functools.partial(
        _pipeline_kernel, len(split2d), tuple(bcast_dtypes), tuple(out_kinds),
        chain_fn, n, block,
    )
    return pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=int(vmem_limit_bytes)),
        interpret=interpret,
        name="mozart_split_pipeline",
    )(*list(split2d), *bcast1d)


def unpad_outputs(outs, out_kinds: Sequence[tuple[str, str]], n: int):
    """Strip the padded layout off kernel outputs and combine reductions."""
    results = []
    for (kind, op), o in zip(out_kinds, outs):
        if kind == "concat":
            results.append(o.reshape(-1)[:n])
        else:
            results.append(_REDUCE[op](o))
    return results


def split_pipeline_call(
    chain_fn: Callable,
    split_inputs: Sequence[jax.Array],
    bcast_inputs: Sequence[Any],
    out_kinds: Sequence[tuple[str, str]],
    out_dtypes: Sequence[Any],
    block_elems: int,
    vmem_limit_bytes: int | None = None,
    interpret: bool | None = None,
):
    """Run a Mozart stage as one Pallas kernel (whole-launch convenience).

    chain_fn(blocks, bcasts) -> list of escaping outputs (block-shaped; a
    reduce output is the PRE-reduction block, which the kernel masks and
    reduces itself).  ``vmem_limit_bytes`` defaults to the target chip's
    kernel limit.
    """
    if vmem_limit_bytes is None:
        vmem_limit_bytes = hardware.TARGET.kernel_vmem_limit_bytes
    n = int(split_inputs[0].shape[0])
    block, _n_pad, _grid = padded_layout(n, block_elems)
    split2d = [pad_to_layout(x, n, block) for x in split_inputs]
    outs = split_pipeline_call_2d(
        chain_fn, split2d, bcast_inputs, out_kinds, out_dtypes, n, block,
        vmem_limit_bytes, interpret=interpret)
    return unpad_outputs(outs, out_kinds, n)
