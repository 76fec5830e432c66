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
elementwise op of the annotated NumPy library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmarks import workloads as w
from repro import hardware
from repro.core import mozart, plan_cache
from repro.core import split_types as st
from repro.core import annotated_numpy as anp
from repro.core.pallas_exec import _block_cap, _effective_block, _make_chain_fn
from repro.core.plan_cache import lookup_or_plan
from repro.core.stage_exec import get_executor
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

