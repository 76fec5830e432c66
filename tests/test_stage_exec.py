"""StageExecutor subsystem tests: registry, cross-executor differential
parity vs the "eager" (un-annotated library) oracle, plan cache, auto-tuner,
cost-model executor auto-selection.  (The full executor × library-surface
differential matrix lives in tests/test_differential.py.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import hardware
from repro.core import cost_model, mozart, plan_cache, planner, splittable, Along
from repro.core import annotated_numpy as anp
from repro.core.stage_exec import (
    StageExecutor,
    available_executors,
    candidate_batches,
    get_executor,
    register_executor,
)

ALL_EXECUTORS = ("eager", "pipelined", "fused", "scan", "sharded", "pallas", "auto")


#: a tiny fast-memory tier so the §5.2 estimate lands well below our array
#: sizes and the tuner has a real candidate spread to measure.
TINY_CHIP = hardware.Chip(
    name="tiny_test_chip",
    peak_bf16_flops=1e11,
    hbm_bandwidth=2e10,
    ici_link_bandwidth=1e10,
    ici_links=1,
    hbm_bytes=2**30,
    vmem_bytes=64 * 1024,
    mozart_c=1.0,
)


@splittable(x=Along(0), y=Along(0), ret=Along(0), elementwise=True)
def saxpy(x, y):
    return 2.0 * x + y


def quickstart(x, y):
    """The examples/quickstart.py pipeline: saxpy -> exp -> scale -> sum."""
    a = saxpy(x, y)
    b = anp.exp(a)
    c = anp.multiply(b, 0.5)
    return c, anp.sum(c)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------


class TestRegistry:
    def test_all_strategies_registered(self):
        names = set(available_executors())
        assert set(ALL_EXECUTORS) <= names
        for n in names:
            assert isinstance(get_executor(n), StageExecutor)
            assert get_executor(n).name == n

    def test_unknown_executor_raises(self):
        with pytest.raises(ValueError, match="unknown executor"):
            get_executor("warp-drive")

    def test_get_executor_returns_singleton(self):
        assert get_executor("fused") is get_executor("fused")

    def test_custom_registration(self):
        @register_executor("test-noop")
        class NoopExecutor(StageExecutor):
            def execute(self, stage, concrete, ctx):
                for node in stage.nodes:
                    node.result = None
                    node.done = True

        try:
            assert "test-noop" in available_executors()
            assert isinstance(get_executor("test-noop"), NoopExecutor)
        finally:
            from repro.core import stage_exec
            stage_exec._REGISTRY.pop("test-noop", None)
            stage_exec._INSTANCES.pop("test-noop", None)


# ---------------------------------------------------------------------------
# Cross-executor differential: everyone must match the eager oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("executor", sorted(available_executors()))
def test_quickstart_differential_vs_eager(executor):
    n = 4096
    x = jnp.arange(n, dtype=jnp.float32) / n
    y = jnp.ones(n, jnp.float32)

    with mozart.session(executor="eager"):
        c0, s0 = quickstart(x, y)
        want_c, want_s = np.asarray(c0), float(s0)

    kwargs = {"batch_elements": 512}
    if executor == "sharded":
        kwargs["mesh"] = jax.make_mesh((1,), ("data",))
    with mozart.session(executor=executor, **kwargs) as ctx:
        c, s = quickstart(x, y)
        got_c, got_s = np.asarray(c), float(s)

    np.testing.assert_allclose(got_c, want_c, rtol=2e-5, atol=1e-6)
    assert np.isclose(got_s, want_s, rtol=1e-5), (executor, got_s, want_s)
    assert ctx.stats["stages"] >= 1


@pytest.mark.parametrize("executor", ["pipelined", "fused", "scan", "pallas"])
def test_differential_with_autotuned_batches(executor):
    """Parity must survive the tuner's candidate re-executions too."""
    n = 30_000
    x = jnp.linspace(0.0, 1.0, n, dtype=jnp.float32)
    y = jnp.ones(n, jnp.float32)

    with mozart.session(executor="eager"):
        _, s0 = quickstart(x, y)
        want = float(s0)

    plan_cache.clear()
    got = []
    for _ in range(3):   # miss -> tuning hit -> pinned hit
        with mozart.session(executor=executor, chip=TINY_CHIP):
            _, s = quickstart(x, y)
            got.append(float(s))
    assert all(np.isclose(g, want, rtol=1e-5) for g in got), (executor, got, want)
    assert plan_cache.tuned_batches(), "tuner pinned nothing"


# ---------------------------------------------------------------------------
# Plan cache
# ---------------------------------------------------------------------------


def _pipeline(x):
    return anp.sum(anp.multiply(anp.exp(x), 0.5))


class TestPlanCache:
    def test_second_run_performs_zero_planner_calls(self):
        x = jnp.linspace(0.0, 1.0, 2048, dtype=jnp.float32)

        with mozart.session(executor="fused") as ctx1:
            v1 = float(_pipeline(x))
        assert ctx1.stats["planner_calls"] == 1
        assert ctx1.stats["plan_cache_misses"] == 1

        before = planner.N_CALLS
        with mozart.session(executor="fused") as ctx2:
            v2 = float(_pipeline(x))
        assert planner.N_CALLS == before          # the planner never ran
        assert ctx2.stats["planner_calls"] == 0
        assert ctx2.stats["plan_cache_hits"] == 1
        assert np.isclose(v1, v2)

    def test_fresh_data_same_shape_hits(self):
        with mozart.session(executor="fused") as ctx1:
            _ = float(_pipeline(jnp.linspace(0.0, 1.0, 512)))
        with mozart.session(executor="fused") as ctx2:
            v = float(_pipeline(jnp.linspace(1.0, 2.0, 512)))
        assert ctx2.stats["plan_cache_hits"] == 1
        want = float(np.sum(np.exp(np.linspace(1.0, 2.0, 512)) * 0.5))
        assert np.isclose(v, want, rtol=1e-5)

    def test_shape_change_misses(self):
        with mozart.session(executor="fused") as ctx1:
            _ = float(_pipeline(jnp.linspace(0.0, 1.0, 128)))
        with mozart.session(executor="fused") as ctx2:
            _ = float(_pipeline(jnp.linspace(0.0, 1.0, 256)))
        assert ctx2.stats["plan_cache_hits"] == 0
        assert ctx2.stats["plan_cache_misses"] == 1

    def test_executor_is_part_of_the_key(self):
        x = jnp.linspace(0.0, 1.0, 256)
        with mozart.session(executor="fused"):
            _ = float(_pipeline(x))
        with mozart.session(executor="scan") as ctx:
            _ = float(_pipeline(x))
        assert ctx.stats["plan_cache_hits"] == 0

    def test_mesh_is_part_of_the_key(self):
        """A plan (and any pinned `sharded` choice) from a mesh session must
        never replay in a mesh-less session of the same pipeline."""
        x = jnp.arange(64.0)
        mesh = jax.make_mesh((1,), ("data",))
        with mozart.session(executor="auto", mesh=mesh, batch_elements=16):
            _ = float(_pipeline(x))
        with mozart.session(executor="auto", batch_elements=16) as ctx:
            _ = float(_pipeline(x))
        assert ctx.stats["plan_cache_hits"] == 0
        assert ctx.stats["plan_cache_misses"] == 1

    def test_aliased_arguments_key_differently(self):
        """add(x, x) and add(x, y) have different plans (one split vs two)."""
        x = jnp.arange(64.0)
        y = jnp.ones(64) * 2
        with mozart.session(executor="pipelined", batch_elements=16):
            np.testing.assert_allclose(np.asarray(anp.add(x, x)), np.arange(64.0) * 2)
        with mozart.session(executor="pipelined", batch_elements=16) as ctx:
            np.testing.assert_allclose(np.asarray(anp.add(x, y)), np.arange(64.0) + 2)
        assert ctx.stats["plan_cache_hits"] == 0

    def test_plan_cache_can_be_disabled(self):
        x = jnp.linspace(0.0, 1.0, 256)
        for _ in range(2):
            with mozart.session(executor="fused", plan_cache=False) as ctx:
                _ = float(_pipeline(x))
        assert ctx.stats["planner_calls"] == 1
        assert ctx.stats["plan_cache_hits"] == 0
        assert plan_cache.cache_info()["entries"] == 0

    def test_table_pipeline_hits_via_fingerprint_hook(self):
        from repro.core import annotated_table as tb
        r = np.random.RandomState(0)
        t = tb.Table({
            "pop": r.rand(100).astype(np.float64) * 1000,
            "crime": r.rand(100).astype(np.float64) * 10,
        })
        def run():
            with mozart.session(executor="pipelined", batch_elements=17) as ctx:
                idx = anp.divide(anp.multiply(tb.col(t, "crime"), 100.0),
                                 tb.col(t, "pop"))
                return float(anp.sum(idx)), ctx
        v1, c1 = run()
        v2, c2 = run()
        assert c1.stats["plan_cache_misses"] == 1
        assert c2.stats["plan_cache_hits"] == 1
        assert np.isclose(v1, v2)

    def test_consumed_done_future_replans_correctly(self):
        """NodeRefs to already-materialized nodes rebind across cache hits."""
        x = jnp.arange(16.0)
        for _ in range(2):
            with mozart.session(executor="fused") as ctx:
                a = anp.exp(x)
                _ = a.value                       # materialize
                b = anp.add(a, x)                 # consumes a DONE node
                np.testing.assert_allclose(
                    np.asarray(b), np.exp(np.arange(16.0)) + np.arange(16.0),
                    rtol=1e-5)


# ---------------------------------------------------------------------------
# Auto-tuner
# ---------------------------------------------------------------------------


class TestAutoTuner:
    def _run(self, x, **kw):
        with mozart.session(executor="fused", chip=TINY_CHIP, **kw) as ctx:
            v = float(_pipeline(x))
        return v, ctx

    def test_tunes_on_first_cached_execution_then_pins(self):
        x = jnp.linspace(0.0, 1.0, 100_000, dtype=jnp.float32)
        v1, c1 = self._run(x)       # miss: plan + §5.2 estimate
        assert c1.stats["autotuned_stages"] == 0
        v2, c2 = self._run(x)       # first hit: measure candidates
        assert c2.stats["autotuned_stages"] == 1
        tuned = plan_cache.tuned_batches()
        assert tuned, "no chunk size pinned"
        (entry,) = plan_cache.entries()
        assert all(len(t) >= 2 for t in entry.trials.values())   # 2-3 candidates
        v3, c3 = self._run(x)       # later hits: reuse the pinned size
        assert c3.stats["autotuned_stages"] == 0
        assert c3.stats["plan_cache_hits"] == 1
        pinned = list(tuned.values())[0]
        assert c3.stats["chunks"] == int(np.ceil(100_000 / pinned))
        assert np.isclose(v1, v2) and np.isclose(v2, v3)

    def test_explicit_batch_elements_disables_tuning(self):
        x = jnp.linspace(0.0, 1.0, 50_000, dtype=jnp.float32)
        for _ in range(3):
            _, ctx = self._run(x, batch_elements=7000)
        assert ctx.stats["autotuned_stages"] == 0
        assert not plan_cache.tuned_batches()
        assert ctx.stats["chunks"] == int(np.ceil(50_000 / 7000))

    def test_autotune_flag_off(self):
        x = jnp.linspace(0.0, 1.0, 50_000, dtype=jnp.float32)
        for _ in range(3):
            _, ctx = self._run(x, autotune=False)
        assert ctx.stats["autotuned_stages"] == 0
        assert not plan_cache.tuned_batches()

    def test_candidate_batches_bracket_the_estimate(self):
        assert candidate_batches(100, 1000) == [50, 100, 200]
        assert candidate_batches(100, 150) == [50, 100, 150]
        assert candidate_batches(100, 80) == [80]       # one chunk: no tuning
        assert candidate_batches(1, 1000) == [1, 2]
        assert candidate_batches(100, 0) == [1]         # empty split

    def test_tuning_cost_is_a_bounded_sample(self):
        """ROADMAP fix: the tuner times a bounded sample of chunks per
        candidate (extrapolating to full-stage seconds) instead of 2 full
        stage executions each.  Structural bound: the elements re-executed
        for measurement stay below ONE extra full stage execution."""
        n = 100_000
        x = jnp.linspace(0.0, 1.0, n, dtype=jnp.float32)
        _, c1 = self._run(x)        # miss: plan
        _, c2 = self._run(x)        # first hit: sampled tuning
        assert c2.stats["autotuned_stages"] == 1
        assert 0 < c2.stats["tuning_sample_elems"] < n
        assert plan_cache.tuned_batches(), "tuner pinned nothing"
        _, c3 = self._run(x)        # pinned: no further sampling
        assert c3.stats["tuning_sample_elems"] == 0


# ---------------------------------------------------------------------------
# Cost-model executor auto-selection
# ---------------------------------------------------------------------------


def _feats(**kw):
    base = dict(n=100_000, elem_bytes=12, n_nodes=3, flops_per_elem=24.0,
                dynamic=False, pallas_eligible=True, mesh_devices=0,
                on_tpu=False)
    base.update(kw)
    return cost_model.StageFeatures(**base)


class TestAutoSelection:
    def _run(self, x, **kw):
        with mozart.session(executor="auto", chip=TINY_CHIP, **kw) as ctx:
            v = float(_pipeline(x))
        return v, ctx

    def test_choice_is_deterministic_in_recorded_timings(self):
        """Same features + same recorded timings => same pick, regardless of
        dict insertion order or repetition."""
        ctx = mozart.MozartContext(chip=TINY_CHIP)
        f = _feats()
        t_fwd = {"fused": 0.010, "scan": 0.020, "pipelined": 0.030}
        t_rev = dict(reversed(list(t_fwd.items())))
        picks = {cost_model.choose(f, ctx, t) for t in (t_fwd, t_rev)}
        picks |= {cost_model.choose(f, ctx, t_fwd) for _ in range(5)}
        assert picks == {"fused"}

    def test_ties_break_by_fixed_preference_order(self):
        ctx = mozart.MozartContext(chip=TINY_CHIP)
        tie = {"fused": 0.01, "scan": 0.01, "eager": 0.01}
        assert cost_model.choose(_feats(), ctx, tie) == "scan"

    def test_analytic_prior_prefers_low_dispatch_strategies(self):
        ctx = mozart.MozartContext(chip=TINY_CHIP)
        f = _feats()
        scores = {n: cost_model.analytic_seconds(n, f, TINY_CHIP)
                  for n in ("scan", "fused", "pipelined", "eager")}
        assert scores["scan"] < scores["pipelined"]     # 1 dispatch vs many
        assert scores["fused"] < scores["pipelined"]    # 1/chunk vs nodes/chunk
        # interpret-mode pallas is effectively vetoed off-TPU
        assert cost_model.analytic_seconds("pallas", f, TINY_CHIP) > 100 * scores["scan"]
        # sharded needs a mesh
        assert cost_model.analytic_seconds("sharded", f, TINY_CHIP) == float("inf")
        assert "sharded" not in cost_model.candidates(f, ctx)

    def test_dynamic_stage_excludes_traced_strategies(self):
        """Dynamic-shape chains cannot be traced: only the raw-per-chunk
        driver (pipelined) and the whole-value baseline (eager) may run."""
        ctx = mozart.MozartContext(chip=TINY_CHIP)
        f = _feats(dynamic=True)
        assert set(cost_model.candidates(f, ctx)) == {"pipelined", "eager"}
        assert cost_model.choose(f, ctx) in ("pipelined", "eager")

    def test_same_pipeline_same_timings_same_per_stage_choice(self, tmp_path):
        """End-to-end determinism: measured timings persisted and replayed
        (with the pinned choice stripped) reproduce the identical pick."""
        x = jnp.linspace(0.0, 1.0, 60_000, dtype=jnp.float32)
        self._run(x)                          # miss
        self._run(x)                          # measurement pass
        (entry,) = plan_cache.entries()
        (sid,) = entry.chosen_exec
        first_pick = entry.chosen_exec[sid]
        assert entry.exec_timings[sid], "no timings recorded"

        path = str(tmp_path / "plans.json")
        plan_cache.save(path)
        for _ in range(3):
            plan_cache.clear()
            plan_cache.load(path)
            (e2,) = plan_cache.entries()
            del e2.chosen_exec[sid]           # force a re-choice from timings
            # autotune=False: no fresh measurement may perturb the inputs
            _, ctx = self._run(x, autotune=False)
            assert ctx.stats[f"auto_pick_{first_pick}"] == 1
            assert e2.chosen_exec == {}       # nothing pinned without tuning

    def test_poisoned_cost_entry_overridden_by_fresh_measurement(self):
        x = jnp.linspace(0.0, 1.0, 60_000, dtype=jnp.float32)
        v0, _ = self._run(x)                  # miss: entry exists, unmeasured
        (entry,) = plan_cache.entries()
        sid = 0                               # single-stage pipeline
        # poison: claim `eager` finishes in a femtosecond
        entry.exec_timings[sid] = {"eager": 1e-15}
        v1, ctx = self._run(x)                # first hit: measurement pass
        assert ctx.stats["auto_measured_stages"] == 1
        # the lie was overwritten by a real measurement...
        assert entry.exec_timings[sid]["eager"] > 1e-9
        # ...and the pin agrees with the fresh numbers, not the poison
        assert entry.chosen_exec[sid] == min(
            sorted(entry.exec_timings[sid]), key=entry.exec_timings[sid].get)
        assert np.isclose(v0, v1, rtol=1e-5)

    def test_auto_measures_then_replays_pinned(self):
        x = jnp.linspace(0.0, 1.0, 60_000, dtype=jnp.float32)
        _, c1 = self._run(x)
        assert c1.stats["auto_stages"] == 1
        assert c1.stats["auto_measured_stages"] == 0
        _, c2 = self._run(x)
        assert c2.stats["auto_measured_stages"] == 1
        _, c3 = self._run(x)
        assert c3.stats["auto_measured_stages"] == 0
        assert c3.stats["auto_pinned_replays"] == 1
        (entry,) = plan_cache.entries()
        assert entry.chosen_exec and entry.exec_timings

    def test_auto_respects_explicit_batch_elements(self):
        x = jnp.linspace(0.0, 1.0, 10_000, dtype=jnp.float32)
        want = float(np.sum(np.exp(np.linspace(0.0, 1.0, 10_000,
                                               dtype=np.float32)) * 0.5))
        for _ in range(3):
            v, ctx = self._run(x, batch_elements=1024)
        assert np.isclose(v, want, rtol=1e-5)
        assert not plan_cache.tuned_batches()   # explicit batch: no tuning


# ---------------------------------------------------------------------------
# Future inspection
# ---------------------------------------------------------------------------


def test_future_exposes_split_type():
    x = jnp.arange(8.0)
    with mozart.session(executor="fused"):
        f = saxpy(x, x)
        assert f.split_type.name == "ArraySplit"
        _ = f.value


# ---------------------------------------------------------------------------
# The scan driver over flat values
# ---------------------------------------------------------------------------


def _eager_and_scan(fn, *args, **session):
    with mozart.session(executor="eager"):
        want = [np.asarray(v, np.float32) for v in fn(*args)]
    with mozart.session(executor="scan", **session) as ctx:
        got = [np.asarray(v, np.float32) for v in fn(*args)]
    return want, got, ctx


class TestScanFlat:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["f32", "bf16"])
    @pytest.mark.parametrize("n", [4096, 5000, 4480],
                             ids=["even", "ragged", "ragged_rows"])
    def test_matches_eager_with_reduction_output(self, n, dtype):
        """A chain with an elementwise and a sum output: the sum folds in
        the loop carry, the tail runs in the same program.  4096 and 4480
        are whole rows of 128 lanes, sliced as rows; 5000 is not."""
        x = (jnp.arange(n, dtype=jnp.float32) / n).astype(dtype)
        y = jnp.ones(n, dtype)
        want, got, ctx = _eager_and_scan(quickstart, x, y,
                                         batch_elements=1024)
        tol = 2e-5 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(got[0], want[0], rtol=tol, atol=1e-6)
        np.testing.assert_allclose(got[1], want[1], rtol=tol)
        assert ctx.stats["chunks"] == -(-n // 1024)
        assert ctx.stats.get("scan_fallbacks", 0) == 0

    @pytest.mark.parametrize("rows", [96, 100], ids=["even", "ragged"])
    @pytest.mark.parametrize("split_axis", [0, 1])
    def test_matches_eager_split_along_either_axis_of_2d(self, split_axis,
                                                         rows):
        """normalize_axis(m, axis) splits along the other axis."""
        shape = (rows, 16) if split_axis == 0 else (16, rows)
        m = jnp.arange(rows * 16, dtype=jnp.float32).reshape(shape) % 7.0
        want, got, ctx = _eager_and_scan(
            lambda v: (anp.normalize_axis(v, axis=1 - split_axis),), m,
            batch_elements=8)
        np.testing.assert_allclose(got[0], want[0], rtol=1e-4, atol=1e-5)
        assert ctx.stats["chunks"] == -(-rows // 8)
        assert ctx.stats.get("layout_bytes", 0) == 0

    def test_plain_inputs_issue_no_layout_copy(self):
        x = jnp.linspace(0.0, 1.0, 5000, dtype=jnp.float32)
        y = jnp.ones(5000, jnp.float32)
        _, _, ctx = _eager_and_scan(quickstart, x, y, batch_elements=1000)
        assert ctx.stats["chunks"] == 5
        assert ctx.stats.get("layout_bytes", 0) == 0

    def test_estimated_batch_is_a_whole_number_of_tiles(self):
        """The §5.2 estimate and every tuner candidate round down to the
        1-D tile (1024 elements); an explicit batch is kept exactly."""
        from repro.core import executor
        ex = get_executor("scan")
        n = 30_000
        x = jnp.linspace(0.0, 1.0, n, dtype=jnp.float32)
        y = jnp.ones(n, jnp.float32)
        with mozart.session(executor="scan", chip=TINY_CHIP) as ctx:
            c, s = quickstart(x, y)
            (stage,) = ctx.last_plan()
            np.asarray(c)
        concrete = {k: si.value for k, si in stage.inputs.items()}
        est = ex.estimate_batch(stage, concrete, ctx, n)
        batch = ex.choose_batch(stage, concrete, ctx, n)
        assert executor._stage_tile(stage, concrete) == 1024
        assert est % 1024 and batch == est // 1024 * 1024
        cands = ex.tuning_candidates(stage, concrete, ctx, est, n)
        assert cands and all(c % 1024 == 0 for c in cands)
        with mozart.session(executor="scan", batch_elements=1000) as ctx2:
            assert ex.choose_batch(stage, concrete, ctx2, n) == 1000

    def test_warm_call_retraces_nothing(self):
        from repro.core import stage_exec
        x = jnp.linspace(0.0, 1.0, 5000, dtype=jnp.float32)
        y = jnp.ones(5000, jnp.float32)
        p = mozart.pipeline(quickstart, executor="scan", batch_elements=1000)
        p.lower(x, y).compile()
        p(x, y)
        t0 = stage_exec.trace_count()
        (c, s), delta = p.call_with_stats(x, y)
        np.asarray(c)
        assert stage_exec.trace_count() == t0
        assert delta.get("exec_builds", 0) == 0
        assert delta["chunks"] == 5

    def test_fallbacks_are_counted(self):
        """An empty split has no chunk to loop over: it goes to ``fused``,
        and the count says so."""
        x = jnp.zeros((0,), jnp.float32)
        with mozart.session(executor="scan") as ctx:
            out = np.asarray(anp.multiply(anp.exp(x), 0.5))
        assert out.shape == (0,)
        assert ctx.stats["scan_fallbacks"] == 1


# ---------------------------------------------------------------------------
# Pallas block-shape-aware tuning (ROADMAP satellite)
# ---------------------------------------------------------------------------


class TestPallasBlockShapeTuning:
    def test_candidates_round_to_hardware_blocks(self):
        """Raw element-count candidates resolving to the SAME 8x128 block are
        duplicates — the tuner must measure each compiled block shape once,
        and never one above the stage's VMEM cap."""
        from repro.core.stage_exec import get_executor
        from repro.kernels.split_pipeline import MIN_BLOCK
        ex = get_executor("pallas")
        n = 1 << 16
        x = jnp.linspace(0.0, 1.0, n, dtype=jnp.float32)
        with mozart.session(executor="pallas") as ctx:
            out = anp.sum(anp.multiply(anp.exp(x), 0.5))
            (stage,) = ctx.last_plan()
            float(out)
        # est=700 -> raw bracket {350, 700, 1400} all round to 1024/2048
        cands = ex.tuning_candidates(stage, {}, ctx, 700, n)
        assert cands == sorted(set(cands))
        assert all(c == n or c % MIN_BLOCK == 0 for c in cands)
        assert len(cands) <= 2
        # huge estimate clamps to n; empty split degenerates to [1]
        assert ex.tuning_candidates(stage, {}, ctx, 10 * n, n) == [n]
        assert ex.tuning_candidates(stage, {}, ctx, 512, 0) == [1]
        # a chip whose VMEM holds less than two blocks caps every candidate
        tiny = mozart.MozartContext(executor="pallas", chip=TINY_CHIP)
        assert ex.tuning_candidates(stage, {}, tiny, 10 * n, n) == [MIN_BLOCK]

    def test_chosen_block_shape_recorded_in_plan_entry(self):
        x = jnp.linspace(0.0, 1.0, 6000, dtype=jnp.float32)

        def run():
            with mozart.session(executor="pallas", chip=hardware.CPU_HOST) as c:
                out = float(anp.sum(anp.multiply(anp.exp(x), 0.5)))
            return out, c

        plan_cache.clear()
        run(); run(); _, ctx = run()
        (entry,) = plan_cache.entries()
        assert entry.block_shape, "pallas recorded no block shape"
        from repro.kernels.split_pipeline import LANES, SUBLANES, padded_layout
        for sid, (sub, lanes) in entry.block_shape.items():
            assert lanes == LANES and sub % SUBLANES == 0
            # the recorded shape is what the pinned batch compiles to (this
            # chain's VMEM cap on CPU_HOST is far above 6000 elements)
            if sid in entry.tuned_batch:
                block, _, _ = padded_layout(6000, entry.tuned_batch[sid])
                assert sub * lanes == block

    def test_block_shape_persists(self, tmp_path):
        x = jnp.linspace(0.0, 1.0, 6000, dtype=jnp.float32)
        plan_cache.clear()
        for _ in range(2):
            with mozart.session(executor="pallas", chip=hardware.CPU_HOST):
                float(anp.sum(anp.multiply(anp.exp(x), 0.5)))
        (entry,) = plan_cache.entries()
        want = dict(entry.block_shape)
        assert want
        path = str(tmp_path / "plans.json")
        plan_cache.save(path)
        plan_cache.clear()
        assert plan_cache.load(path) == 1
        (loaded,) = plan_cache.entries()
        assert dict(loaded.block_shape) == want


# ---------------------------------------------------------------------------
# Chip constants from the device, and the compile-cache location
# ---------------------------------------------------------------------------


class _Device:
    def __init__(self, kind):
        self.device_kind = kind


class TestChipConstants:
    def test_chip_for_reads_device_kind(self):
        assert hardware.chip_for(_Device("TPU v5 lite")) is hardware.TPU_V5E
        assert hardware.chip_for(jax.devices()[0]) is hardware.CPU_HOST
        with pytest.raises(ValueError, match="no chip constants"):
            hardware.chip_for(_Device("TPU v9 imaginary"))

    def test_kernel_vmem_limit_leaves_compiler_headroom(self):
        limit = hardware.TPU_V5E.kernel_vmem_limit_bytes
        assert 0 < limit < hardware.TPU_V5E.vmem_bytes

    def test_compile_cache_env_wins_and_nothing_else_is_set(self, monkeypatch):
        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert hardware.use_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before

    def test_compile_cache_defaults_to_checkout(self, monkeypatch):
        import os
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        before = jax.config.jax_compilation_cache_dir
        try:
            path = hardware.use_compile_cache()
            assert path == os.path.join(repo, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == path
            assert hardware.use_compile_cache() == path     # fixed path
        finally:
            jax.config.update("jax_compilation_cache_dir", before)
