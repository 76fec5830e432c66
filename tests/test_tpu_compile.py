"""Ahead-of-time compiles of the main path's kernel for a TPU v5e.

No chip is attached: the TPU compiler compiles for a *described* ``v5e:2x2``
topology, which shows what interpret mode cannot — block layouts the
lowering refuses, primitives it does not implement, VMEM overruns.  The
topology is described inside a fixture (never at import), and every test
compiles in this process: the TPU library can be loaded by one process at
a time.

Covered: the split-pipeline kernel with concat outputs, broadcast scalars
and reduce outputs, at the blocks the executor picks for Black–Scholes
(2^27 options) and data cleaning (2^28 values) on ``TPU_V5E`` — the §5.2
estimate and the largest block the tuner may try — and the decline rule
(``unlowerable_primitives``) checked against the compiler for every
elementwise op of the annotated NumPy library.  The ``scan`` driver for
the Black–Scholes stage at 2^27, at the executor's batches, compiles to
in-place chunk reads and writes on flat values, and ``split_tile`` agrees
with the layouts the compiler assigns.  The ``sharded`` driver for the same
stage at 2^29 on the described four-chip mesh runs that chunk loop on each
2^27 shard.
"""

import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

from benchmarks import workloads as w
from repro import hardware
from repro.core import executor, mozart, plan_cache, sharded
from repro.core import split_types as st
from repro.core import annotated_numpy as anp
from repro.core.pallas_exec import _block_cap, _effective_block, _make_chain_fn
from repro.core.plan_cache import lookup_or_plan
from repro.core.stage_exec import get_executor, split_axis_of
from repro.kernels import split_pipeline as sp


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # A compile for a described chip cannot be read back from the
    # persistent cache without one: keep these compiles out of it.
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, chain_fn, n, block, split_dtypes, bcast_dtypes,
             out_kinds, out_dtypes):
    """AOT-compile one kernel launch at its real shapes for one v5e chip."""
    rows = sp._round_up(n, block) // sp.LANES
    split = [jax.ShapeDtypeStruct((rows, sp.LANES), dt, sharding=one_chip)
             for dt in split_dtypes]
    bcast = [jax.ShapeDtypeStruct((), dt, sharding=one_chip)
             for dt in bcast_dtypes]
    limit = hardware.TPU_V5E.kernel_vmem_limit_bytes

    def launch(split2d, bcasts):
        return sp.split_pipeline_call_2d(
            chain_fn, split2d, bcasts, out_kinds, out_dtypes, n, block, limit,
            interpret=False)

    return jax.jit(launch).lower(split, bcast).compile()


def test_topology_is_a_v5e(topo):
    assert topo.devices[0].device_kind == "TPU v5 lite"
    assert hardware.chip_for(topo.devices[0]) is hardware.TPU_V5E


# ---------------------------------------------------------------------------
# The executor's blocks for the smoke's stages, at their real sizes
# ---------------------------------------------------------------------------


def _black_scholes(monkeypatch):
    # erf has no Pallas TPU lowering, so the real stage is declined; a tanh
    # in its place keeps the stage's inputs, outputs and live values.
    monkeypatch.setattr(anp, "erf", anp.tanh)
    d = w.black_scholes_data(4096)
    return (lambda: w.black_scholes(**d)), 1 << 27


def _data_cleaning(monkeypatch):
    vals = jnp.linspace(-1.0, 2e6, 4096, dtype=jnp.float32)
    return (lambda: w.clean_column(vals)), 1 << 28


def _stage_launches(monkeypatch, workload):
    """The workload's one kernel stage on TPU_V5E at its real element
    count: its chain, dtypes, output kinds, ``n`` and the labelled blocks
    the executor would launch."""
    build, n = workload(monkeypatch)
    plan_cache.clear()
    ex = get_executor("pallas")
    with mozart.session(executor="pallas", chip=hardware.TPU_V5E) as ctx:
        outs = build()          # held: dead-stage elimination drops the rest
        # The stage as the executor sees it: planned and rewritten.
        (stage,), _entry = lookup_or_plan(ctx.graph.pending(), ctx.graph, ctx)
        concrete = {k: si.value for k, si in stage.inputs.items()}
        split_keys = [k for k, si in stage.inputs.items()
                      if si.split_type.splittable]
        bcast_keys = [k for k in stage.inputs if k not in split_keys]
        escape_ids = sorted(stage.escaping)
        nodes = {nd.id: nd for nd in stage.nodes}
        kinds = [("reduce", stage.out_types[i].op_name)
                 if isinstance(stage.out_types[i], st.ReduceSplit)
                 else ("concat", "") for i in escape_ids]
        out_dtypes = [nodes[i].out_aval.dtype for i in escape_ids]
        chain = _make_chain_fn(
            stage, [stage.ckey(k) for k in split_keys],
            [stage.ckey(k) for k in bcast_keys],
            [stage.pos[i] for i in escape_ids], kinds)
        small_n = int(concrete[split_keys[0]].shape[0])
        est = ex.estimate_batch(stage, concrete, ctx, small_n)
        cap = _block_cap(stage, ctx)
        tuned = ex.tuning_candidates(stage, concrete, ctx, est, n)
    del outs
    split_dtypes = [concrete[k].dtype for k in split_keys]
    bcast_dtypes = [jnp.result_type(concrete[k]) for k in bcast_keys]
    launches = [("estimate", _effective_block(est, n, cap)),
                ("tuner_max", _effective_block(max(tuned), n, cap))]
    return chain, split_dtypes, bcast_dtypes, kinds, out_dtypes, n, launches


@pytest.mark.parametrize("which", ["estimate", "tuner_max"])
@pytest.mark.parametrize("workload", [_black_scholes, _data_cleaning],
                         ids=["black_scholes_2p27", "data_cleaning_2p28"])
def test_stage_kernel_compiles_at_real_blocks(one_chip, monkeypatch, workload,
                                              which):
    (chain, split_dt, bcast_dt, kinds, out_dt, n,
     launches) = _stage_launches(monkeypatch, workload)
    block = dict(launches)[which]
    assert block % sp.MIN_BLOCK == 0
    compiled = _compile(one_chip, chain, n, block, split_dt, bcast_dt, kinds,
                        out_dt)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("op", ["add", "mul", "max", "min"])
def test_concat_bcast_and_reduce_outputs_compile(one_chip, op):
    """All three output paths in one launch; 3 x 1024 elements per block
    makes an odd number of 8-row groups for the reduce fold, and the tail
    of n is padded (masked)."""
    def chain(blocks, bcasts):
        a, b = blocks
        (c,) = bcasts
        u = jnp.maximum(jnp.exp(a * 0.1) + b, c)
        return [u, u]

    kinds = [("concat", ""), ("reduce", op)]
    compiled = _compile(one_chip, chain, 10_000, 3 * sp.MIN_BLOCK,
                        [jnp.float32, jnp.float32], [jnp.float32], kinds,
                        [jnp.float32, jnp.float32])
    assert "tpu_custom_call" in compiled.as_text()


# ---------------------------------------------------------------------------
# The decline rule agrees with the compiler
# ---------------------------------------------------------------------------

_UNARY = sorted(anp._UNARY)
_BINARY = sorted(anp._BINARY)


def _refused(one_chip, chain, n_in) -> bool:
    out_dt = jax.eval_shape(
        chain, [jax.ShapeDtypeStruct((8, 128), jnp.float32)] * n_in, [])[0].dtype
    try:
        _compile(one_chip, chain, 4 * sp.MIN_BLOCK, sp.MIN_BLOCK,
                 [jnp.float32] * n_in, [], [("concat", "")], [out_dt])
    except (ValueError, NotImplementedError, jax.errors.JaxRuntimeError):
        return True
    return False


@pytest.mark.parametrize("name", _UNARY + _BINARY)
def test_decline_rule_matches_compiler(one_chip, name):
    fn = (anp._UNARY.get(name) or anp._BINARY[name])
    n_in = 1 if name in anp._UNARY else 2

    def chain(blocks, bcasts):
        return [fn(*blocks)]

    declined = sp.unlowerable_primitives(chain, [jnp.float32] * n_in, [])
    assert bool(declined) == _refused(one_chip, chain, n_in), declined


@pytest.mark.parametrize("name,prim", [("erf", "erf"), ("arcsin", "asin")])
def test_known_refusals_are_declined(one_chip, name, prim):
    fn = anp._UNARY[name]

    def chain(blocks, bcasts):
        return [fn(blocks[0] * 0.5)]

    assert sp.unlowerable_primitives(chain, [jnp.float32], []) == [prim]
    assert _refused(one_chip, chain, 1)


def test_lowerable_set_has_lowering_rules():
    """Every primitive the rule admits has a Pallas TPU lowering rule."""
    from jax._src.pallas.mosaic import core, lowering

    rules = {p.name for p in lowering.lowering_rules[core.KernelType.TC]}
    assert sp.LOWERABLE_PRIMITIVES <= rules, sp.LOWERABLE_PRIMITIVES - rules



# ---------------------------------------------------------------------------
# The scan driver reads and writes flat values in whole tiles
# ---------------------------------------------------------------------------

def _computations(hlo: str) -> dict:
    """``{computation: ({instruction: (type, op, operands, attrs)}, root)}``
    from a compiled module's text; the entry computation is ``"ENTRY"``."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        head = re.match(r"(ENTRY )?%(\S+) \(.*\{$", line)
        if head:
            cur = "ENTRY" if head.group(1) else head.group(2)
            comps[cur] = ({}, None)
            continue
        m = re.match(r"\s*(ROOT )?%(\S+) = (.*?) ([a-z][a-z0-9-]*)\(([^)]*)\)"
                     r"(.*)$", line)
        if cur is None or not m:
            continue
        instrs, root = comps[cur]
        operands = [re.sub(r"/\*.*?\*/", "", o).strip().lstrip("%")
                    for o in m.group(5).split(",") if o.strip()]
        instrs[m.group(2)] = (m.group(3), m.group(4), operands, m.group(6))
        comps[cur] = (instrs, m.group(2) if m.group(1) else root)
    return comps


def _producer(comps: dict, comp: str, name: str, index=None) -> str:
    """The op that computes ``name`` (element ``index`` of a tuple), seen
    through tuples, fusions, while loops and bitcasts (views of the same
    bytes)."""
    instrs, _ = comps[comp]
    _type, op, operands, attrs = instrs[name]
    if op == "bitcast":
        return _producer(comps, comp, operands[0], index)
    if op == "get-tuple-element":
        return _producer(comps, comp, operands[0],
                         int(re.search(r"index=(\d+)", attrs).group(1)))
    if op == "tuple":
        return _producer(comps, comp, operands[index])
    if op in ("fusion", "while"):
        sub = re.search(r"(?:calls|body)=%([^,\s]+)", attrs).group(1)
        return _producer(comps, sub, comps[sub][1], index)
    return op


def _bs_scan_stage(which: str):
    """The Black–Scholes stage as the scan executor drives it at 2^27 on
    ``TPU_V5E``: the stage, its driver's arguments and the batch it picks
    (the §5.2 estimate, or the largest tuner candidate)."""
    n = 1 << 27
    ex = get_executor("scan")
    plan_cache.clear()
    with mozart.session(executor="scan", chip=hardware.TPU_V5E) as ctx:
        outs = w.black_scholes(**w.black_scholes_data(4096))
        (stage,), _entry = lookup_or_plan(ctx.graph.pending(), ctx.graph, ctx)
        concrete = {k: si.value for k, si in stage.inputs.items()}
        tile = executor._stage_tile(stage, concrete)
        est = ex.estimate_batch(stage, concrete, ctx, 4096)
        batch = {"estimate": executor._aligned(min(est, n), n, tile),
                 "tuner_max": max(ex.tuning_candidates(stage, concrete, ctx,
                                                       est, n))}[which]
    del outs
    return stage, concrete, n, batch, tile


@pytest.mark.parametrize("which", ["estimate", "tuner_max"])
def test_scan_driver_is_flat_at_real_size(one_chip, which):
    """Chunks are read in place from the flat inputs and written into flat
    outputs: the inputs keep their ``{0:T(1024)}`` layout, every chunk is
    whole tiles (``f32[batch]{0:T(1024)}``, or the same bytes as rows of
    128 lanes, ``f32[batch/128,128]{1,0:T(8,128)}``), never one-row
    ``T(1,128)`` slab rows; nothing input-sized is copied, and every
    whole-size output comes out of a ``dynamic-update-slice``."""
    stage, concrete, n, batch, tile = _bs_scan_stage(which)
    assert tile == 1024 and batch % tile == 0 and n // batch > 1
    split_keys = [k for k, si in stage.inputs.items()
                  if si.split_type.splittable]
    split_axes = {stage.ckey(k): 0 for k in split_keys}
    esc = tuple(stage.escape_positions())
    out_axes = {stage.pos[nid]: split_axis_of(stage.out_types[nid])
                for nid in stage.escaping}
    driver = executor._build_scan_driver(stage, esc, split_axes, out_axes,
                                         batch)
    args = {ck: jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
            for ck in split_axes}
    bcast = {stage.ckey(k): jax.ShapeDtypeStruct(
                 (), jnp.result_type(concrete[k]), sharding=one_chip)
             for k in stage.inputs if k not in split_keys}
    compiled = driver.lower(args, bcast).compile()
    hlo = compiled.as_text()

    whole = (f"f32[{n}]", f"f32[{n // 128},128]")
    chunk = (rf"f32\[{batch}\]\{{0:T\(1024\)",
             rf"f32\[{batch // 128},128\]\{{1,0:T\(8,128\)")
    slices = [l for l in hlo.splitlines() if " dynamic-slice(" in l]
    assert slices
    for line in slices:
        assert any(re.search("= " + c, line) for c in chunk), line
    assert "T(1,128)" not in hlo
    params = [t for t, op, _o, _a in _computations(hlo)["ENTRY"][0].values()
              if op == "parameter" and t.startswith("f32[") and "[]" not in t]
    assert params and all(t == f"f32[{n}]{{0:T(1024)}}" for t in params)
    copies = [l for l in hlo.splitlines() if " copy(" in l
              and any(w in l.split(" copy(")[0] for w in whole)]
    assert copies == []
    assert compiled.memory_analysis().temp_size_in_bytes < n * 4

    comps = _computations(hlo)
    entry, root = comps["ENTRY"]
    outputs = entry[root][2]
    assert len(outputs) == len(esc)
    for i in range(len(outputs)):
        assert _producer(comps, "ENTRY", root, i) == "dynamic-update-slice"
    loop = next(a for _t, op, _o, a in entry.values() if op == "while")
    body = re.search(r"body=%([^,\s]+)", loop).group(1)
    body_root = comps[body][0][comps[body][1]]
    written = [i for i, t in enumerate(re.findall(r"\w+\[[^\]]*\]",
                                                  body_root[0]))
               if t in whole and _producer(comps, body, comps[body][1], i)
               == "dynamic-update-slice"]
    assert len(written) == len(esc)


def test_sharded_driver_loops_each_shard_at_real_size(topo):
    """Black–Scholes at 2^29 on a described four-chip ``data`` mesh, so
    2^27 options a shard, at the executor's batch: each shard runs the
    scan driver's chunk loop (a ``while``), takes ``f32[2^27]`` shards,
    joins no chunk outputs with a ``concatenate`` and writes every
    whole-shard output with a ``dynamic-update-slice``.  It compiles in
    seconds, where the Python loop it replaced, unrolled over the shard's
    chunks, took over a minute."""
    stage, concrete, n_local, batch, _tile = _bs_scan_stage("estimate")
    assert n_local // batch > 1 and n_local % batch
    mesh = Mesh(np.array(topo.devices), ("data",))
    axes = ("data",)
    in_specs, out_specs = sharded._specs(stage, concrete, axes)
    esc = tuple(stage.pos[nid] for nid in sorted(stage.escaping))
    driver = sharded._build_sharded_driver(stage, mesh, axes, in_specs,
                                           out_specs, esc, batch, True)
    args = [jax.ShapeDtypeStruct(
                (4 * n_local,) if si.split_type.splittable else (),
                jnp.result_type(concrete[k]),
                sharding=NamedSharding(mesh, spec))
            for (k, si), spec in zip(stage.inputs.items(), in_specs)]
    t0 = time.perf_counter()
    compiled = driver.lower(*args).compile()
    assert time.perf_counter() - t0 < 30
    hlo = compiled.as_text()

    chunk = (f"f32[{batch}]", f"f32[{batch // 128},128]")
    joins = [l for l in hlo.splitlines()
             if " concatenate(" in l and any(c in l for c in chunk)]
    assert joins == []
    comps = _computations(hlo)
    entry, root = comps["ENTRY"]
    assert any(op == "while" for _t, op, _o, _a in entry.values())
    params = [t for t, op, _o, _a in entry.values()
              if op == "parameter" and t.startswith("f32[") and "[]" not in t]
    assert len(params) == sum(si.split_type.splittable
                              for si in stage.inputs.values())
    assert all(t == f"f32[{n_local}]{{0:T(1024)}}" for t in params)
    outputs = entry[root][2]
    assert len(outputs) == len(esc)
    for i in range(len(outputs)):
        assert _producer(comps, "ENTRY", root, i) == "dynamic-update-slice"


@pytest.mark.parametrize("shape,dtype,axis", [
    ((1 << 20,), jnp.float32, 0), ((1 << 20,), jnp.bfloat16, 0),
    ((4096, 256), jnp.float32, 0), ((4096, 256), jnp.float32, 1),
    ((4096, 256), jnp.bfloat16, 0), ((4096, 256), jnp.bfloat16, 1),
    ((64, 64, 256), jnp.float32, 0)])
def test_split_tile_matches_compiled_layout(one_chip, shape, dtype, axis):
    """``split_tile`` is the compiler's own tile along the split axis."""
    compiled = jax.jit(lambda x: jnp.exp(x) * 2).lower(
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)).compile()
    entry, _root = _computations(compiled.as_text())["ENTRY"]
    layout = next(t for t, op, _o, a in entry.values()
                  if op == "parameter" and ", " not in t)
    tiles = [int(x) for x in re.search(r":T\(([0-9,]+)\)", layout)
             .group(1).split(",")]
    along = dict(zip(range(len(shape) - len(tiles), len(shape)), tiles))
    assert executor.split_tile(shape, axis) == along.get(axis, 1), layout
