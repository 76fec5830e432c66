"""Resilience layer (``repro.core.resilience``): deterministic fault
injection, the executor degradation ladder, chunk-granular OOM recovery,
plan-cache quarantine persistence, hardened persistence, and the serving
failure domains.

The spine is differential: every chaos run must produce EXACTLY the result
of the fault-free eager oracle — degradation is only allowed to cost time,
never correctness.
"""

import json
import subprocess
import sys
import textwrap
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro import hardware
from repro.core import mozart, plan_cache, resilience, splittable, Along
from repro.core import annotated_numpy as anp
from repro.core.resilience import (FaultConfig, FaultPlan, FaultSpec,
                                   InjectedFault, InjectedResourceExhausted,
                                   StepFailure, StepTimer, with_retries,
                                   run_with_restarts)


@pytest.fixture(autouse=True)
def _fresh_resilience():
    """Disarm fault plans and zero the process counters around every test."""
    resilience.clear_faults()
    resilience.clear_events()
    yield
    resilience.clear_faults()
    resilience.clear_events()


@splittable(x=Along(0), y=Along(0), ret=Along(0), elementwise=True)
def saxpy(x, y):
    return 2.0 * x + y


def quickstart(x, y):
    a = saxpy(x, y)
    b = anp.exp(a)
    c = anp.multiply(b, 0.5)
    return c, anp.sum(c)


def chain3(x, y):
    """A multi-stage pipeline: the scalar reduction forces a stage break,
    so downstream stages INGEST upstream results (handoff boundary)."""
    a = saxpy(x, y)
    s = anp.sum(a)                      # stage break: scalar out
    b = anp.multiply(x, 0.5)
    c = anp.subtract(b, s)              # consumes the scalar + a fresh chain
    return anp.sum(anp.exp(anp.multiply(c, 0.01)))


N = 4096
X = jnp.arange(N, dtype=jnp.float32) / N
Y = jnp.ones(N, jnp.float32)


@pytest.fixture(scope="module")
def oracle():
    """Fault-free eager results for both pipelines."""
    with mozart.session(executor="eager"):
        c, s = quickstart(X, Y)
        q = (np.asarray(c), float(s))
        t = float(chain3(X, Y))
    return q, t


# ---------------------------------------------------------------------------
# Fault plans: parsing, firing, arming
# ---------------------------------------------------------------------------


class TestFaultPlan:
    def test_parse_defaults_and_fields(self):
        p = FaultPlan.parse("compile")
        assert p.specs == [FaultSpec("compile", "fail", 1, "", 0)]
        p = FaultPlan.parse("chunk:oom:2, merge:fail:1:stage 0")
        assert p.specs[0] == FaultSpec("chunk", "oom", 2, "", 0)
        assert p.specs[1] == FaultSpec("merge", "fail", 1, "stage 0", 0)

    def test_parse_after_skip(self):
        (spec,) = FaultPlan.parse("chunk:fail:1+3").specs
        assert (spec.count, spec.after) == (1, 3)

    def test_unknown_boundary_and_kind_rejected(self):
        with pytest.raises(ValueError, match="unknown fault boundary"):
            FaultPlan.parse("warp-drive:fail:1")
        with pytest.raises(ValueError, match="unknown fault kind"):
            FaultPlan.parse("chunk:frobnicate:1")

    def test_fires_count_times_then_disarms(self):
        p = FaultPlan.parse("merge:fail:2")
        for _ in range(2):
            with pytest.raises(InjectedFault):
                p.check("merge", "stage 0")
        p.check("merge", "stage 0")          # spent: silent
        assert p.fired == [("merge", "stage 0")] * 2

    def test_match_filters_crossings(self):
        p = FaultPlan.parse("merge:fail:1:stage 2")
        p.check("merge", "stage 0")          # no match: skipped, not consumed
        with pytest.raises(InjectedFault):
            p.check("merge", "stage 2")

    def test_after_skips_crossings(self):
        p = FaultPlan.parse("chunk:fail:1+2")
        p.check("chunk", "a")
        p.check("chunk", "b")
        with pytest.raises(InjectedFault):
            p.check("chunk", "c")

    def test_oom_kind_raises_resource_exhausted(self):
        p = FaultPlan.parse("chunk:oom:1")
        with pytest.raises(InjectedResourceExhausted) as ei:
            p.check("chunk", "x")
        assert resilience.is_resource_exhausted(ei.value)

    def test_inject_faults_nests_and_restores(self):
        with mozart.inject_faults("merge:fail:1") as outer:
            with mozart.inject_faults("split:fail:1") as inner:
                resilience.maybe_fail("merge")          # outer masked: silent
                with pytest.raises(InjectedFault):
                    resilience.maybe_fail("split")
            assert inner.fired and not outer.fired
            with pytest.raises(InjectedFault):
                resilience.maybe_fail("merge")          # outer restored
        resilience.maybe_fail("merge")                   # all disarmed

    def test_env_plan_fires_once_and_stays_spent(self, monkeypatch):
        monkeypatch.setenv("MOZART_FAULTS", "merge:fail:1")
        with pytest.raises(InjectedFault):
            resilience.maybe_fail("merge", "env")
        # Re-reading the same env value must NOT re-arm the plan.
        resilience.maybe_fail("merge", "env")
        assert resilience.stats["MZ401"] == 1

    def test_is_resource_exhausted_matches_xla_strings(self):
        assert resilience.is_resource_exhausted(MemoryError())
        assert resilience.is_resource_exhausted(
            RuntimeError("RESOURCE_EXHAUSTED: out of memory on device"))
        assert not resilience.is_resource_exhausted(RuntimeError("boom"))


# ---------------------------------------------------------------------------
# Chaos sweep: injected boundary faults, exact differential parity
# ---------------------------------------------------------------------------

SWEEP_EXECUTORS = ("pipelined", "fused", "scan", "pallas", "auto")
SWEEP_BOUNDARIES = ("split", "chunk", "compile", "ingest")


@pytest.mark.parametrize("executor", SWEEP_EXECUTORS)
@pytest.mark.parametrize("boundary", SWEEP_BOUNDARIES)
def test_boundary_fault_parity(executor, boundary, oracle):
    """A fault at the FIRST crossing of each boundary: the run completes
    bit-identically to the fault-free oracle (ladder demotion, probe
    swallow, or the boundary simply not being exercised — all are fine,
    a wrong answer is not)."""
    (want_c, want_s), _ = oracle
    with mozart.inject_faults(f"{boundary}:fail:1") as plan:
        with mozart.session(executor=executor, batch_elements=512) as ctx:
            c, s = quickstart(X, Y)
            got_c, got_s = np.asarray(c), float(s)
    np.testing.assert_allclose(got_c, want_c, rtol=2e-5, atol=1e-6)
    assert np.isclose(got_s, want_s, rtol=1e-5)
    if plan.fired:
        # The fault really happened and was recovered from — and the
        # recovery is observable (MZ401 fire record at minimum).
        assert resilience.stats["MZ401"] >= 1


@pytest.mark.parametrize("executor", ("pipelined", "scan"))
def test_merge_fault_parity(executor, oracle):
    """Merge faults recover for non-donating drives (donating attempts are
    deliberately NOT re-driven: freed buffers must never be re-read)."""
    (want_c, want_s), _ = oracle
    with mozart.inject_faults("merge:fail:1") as plan:
        with mozart.session(executor=executor, batch_elements=512) as ctx:
            c, s = quickstart(X, Y)
            got_c, got_s = np.asarray(c), float(s)
    np.testing.assert_allclose(got_c, want_c, rtol=2e-5, atol=1e-6)
    assert np.isclose(got_s, want_s, rtol=1e-5)
    assert plan.fired
    assert ctx.stats["exec_demotions"] >= 1


def test_handoff_chain_fault_parity(oracle):
    """The 3-stage handoff chain survives an ingest fault mid-chain."""
    _, want = oracle
    with mozart.inject_faults("ingest:fail:1") as plan:
        with mozart.session(executor="fused", batch_elements=512) as ctx:
            got = float(chain3(X, Y))
    assert np.isclose(got, want, rtol=1e-5)
    assert ctx.stats["stages"] >= 2 or ctx.stats["evaluations"] >= 1


def test_compile_fault_demotes_and_quarantines(oracle):
    """A compile-time failure walks the ladder (fused -> pipelined), records
    MZ402/MZ404, and quarantines the broken choice in the plan entry so the
    NEXT call skips it outright."""
    (want_c, want_s), _ = oracle
    with mozart.session(executor="fused", batch_elements=512) as ctx:
        with mozart.inject_faults("compile:fail:1") as plan:
            c, s = quickstart(X, Y)
            got = (np.asarray(c), float(s))
        assert plan.fired
        assert ctx.stats["exec_demotions"] >= 1
        assert resilience.stats["MZ402"] >= 1
        assert resilience.stats["MZ404"] >= 1
        skips_before = ctx.stats["exec_quarantine_skips"]
        # Warm call, no fault armed: the quarantined executor is skipped.
        c2, s2 = quickstart(X, Y)
        got2 = (np.asarray(c2), float(s2))
        assert ctx.stats["exec_quarantine_skips"] > skips_before
    np.testing.assert_allclose(got[0], want_c, rtol=2e-5, atol=1e-6)
    np.testing.assert_allclose(got2[0], want_c, rtol=2e-5, atol=1e-6)
    assert np.isclose(got[1], want_s, rtol=1e-5)
    assert np.isclose(got2[1], want_s, rtol=1e-5)
    # The quarantine is persisted state on the entry.
    assert any(e.quarantined for e in plan_cache.entries())


def test_chunk_oom_halves_batch_and_repins(oracle):
    """Injected RESOURCE_EXHAUSTED on the first chunk drive: the batch is
    halved below the ladder, the run completes exactly, and the surviving
    size is re-pinned into the tuner state."""
    (want_c, want_s), _ = oracle
    with mozart.inject_faults("chunk:oom:1") as plan:
        with mozart.session(executor="fused", batch_elements=512) as ctx:
            c, s = quickstart(X, Y)
            got_c, got_s = np.asarray(c), float(s)
    np.testing.assert_allclose(got_c, want_c, rtol=2e-5, atol=1e-6)
    assert np.isclose(got_s, want_s, rtol=1e-5)
    assert plan.fired
    assert ctx.stats["chunk_oom_halvings"] >= 1
    assert resilience.stats["MZ403"] >= 1
    # No executor demotion needed: recovery happened below the ladder.
    assert ctx.stats["exec_demotions"] == 0
    assert 256 in set(plan_cache.tuned_batches().values())


def test_sustained_oom_bounded_then_ladder_finishes_on_eager(oracle):
    """OOM on EVERY chunk drive: each chunked executor halves at most
    MAX_OOM_HALVINGS times before the failure escalates to the ladder,
    which lands on eager — the unchunked baseline that cannot OOM-inject —
    and still produces the exact answer.  No unbounded retry loop."""
    (want_c, want_s), _ = oracle
    with mozart.inject_faults("chunk:oom:999"):
        with mozart.session(executor="fused", batch_elements=512) as ctx:
            c, s = quickstart(X, Y)
            got_c, got_s = np.asarray(c), float(s)
    np.testing.assert_allclose(got_c, want_c, rtol=2e-5, atol=1e-6)
    assert np.isclose(got_s, want_s, rtol=1e-5)
    assert ctx.stats["exec_demoted_to_eager"] >= 1
    # Halvings are bounded PER ATTEMPT; the ladder tried two chunked rungs.
    assert ctx.stats["chunk_oom_halvings"] <= 2 * resilience.MAX_OOM_HALVINGS


class _Ctx:
    def __init__(self, **stats):
        self.stats = dict(stats)


#: fast memory sized so the tuner brackets several kernel blocks below N.
_TUNING_CHIP = hardware.Chip(
    name="resilience_tuning_chip", peak_bf16_flops=1e11, hbm_bandwidth=2e10,
    ici_link_bandwidth=1e10, ici_links=1, hbm_bytes=2**30,
    vmem_bytes=256 * 1024, mozart_c=0.1)


@pytest.mark.parametrize("when", ["cold", "tuning"])
def test_refused_kernel_compile_propagates_without_demotion(monkeypatch, when):
    """A kernel the compiler refuses is a deterministic fault: the error
    propagates (as KernelRefused, caused by the compiler's error) from the
    first launch and from the tuner's samples alike — never demoted down
    the ladder, never halved, never swallowed."""
    from repro.kernels import split_pipeline as sp

    sessions = []

    def run():
        with mozart.session(executor="pallas", chip=_TUNING_CHIP) as ctx:
            sessions.append(ctx)
            c, s = quickstart(X, Y)
            float(s)
        return ctx

    def refuse(*args, **kwargs):
        raise ValueError("The Pallas TPU lowering currently requires that the "
                         "last two dimensions of your block shape are "
                         "divisible by 8 and 128 respectively")

    plan_cache.clear()
    if when == "tuning":
        assert run().stats["pallas_stages"] == 1     # plan (miss); works
    monkeypatch.setattr(sp, "split_pipeline_call_2d", refuse)
    with pytest.raises(resilience.KernelRefused) as info:
        run()
    assert isinstance(info.value.__cause__, ValueError)
    stats = sessions[-1].stats
    assert stats["exec_demotions"] == 0
    assert stats["chunk_oom_halvings"] == 0
    assert stats["swallowed_errors"] == 0
    for code in ("MZ402", "MZ403", "MZ404", "MZ406"):
        assert resilience.stats[code] == 0, code
    (entry,) = plan_cache.entries()
    assert not entry.quarantined


def test_sanitizer_errors_are_never_demoted_around():
    from repro.core.stage_exec import SanitizerError
    assert not resilience._recoverable(SanitizerError("bad merge"), _Ctx(), 0)


def test_donating_attempt_is_not_redriven():
    ctx = _Ctx(donated_chunks=3)
    assert not resilience._recoverable(RuntimeError("x"), ctx, 0)
    assert resilience._recoverable(RuntimeError("x"), ctx, 3)


def test_demotion_ladder_order():
    assert resilience.demotion_ladder("pallas") == [
        "sharded", "scan", "fused", "pipelined", "eager"]
    assert resilience.demotion_ladder("eager") == []
    # Unknown / meta names restart from the top, minus themselves.
    assert resilience.demotion_ladder("auto") == list(resilience.DEGRADE_ORDER)


# ---------------------------------------------------------------------------
# Quarantine aging
# ---------------------------------------------------------------------------


def test_quarantine_ages_out(oracle):
    """After TTL warm dispatches the quarantined executor is retried."""
    (want_c, want_s), _ = oracle
    with mozart.session(executor="fused", batch_elements=512) as ctx:
        with mozart.inject_faults("compile:fail:1"):
            _, s = quickstart(X, Y)
            float(s)
        entry = next(e for e in plan_cache.entries() if e.quarantined)
        (sid,) = [k for k, v in entry.quarantined.items() if "fused" in v]
        assert entry.quarantined_execs(sid) == {"fused"}
        # Unit-level aging: each tick ages by one, TTL drops the ban.
        assert entry.tick_quarantine(sid, ttl=2) == {"fused"}   # age 1 of 2
        assert entry.tick_quarantine(sid, ttl=2) == set()       # age 2: out
        assert entry.quarantined_execs(sid) == set()
        # Post-quarantine the executor runs again (fault long spent).
        c, s = quickstart(X, Y)
        np.testing.assert_allclose(np.asarray(c), want_c, rtol=2e-5,
                                   atol=1e-6)


def test_tick_quarantine_multiple_names():
    with mozart.session(executor="fused", batch_elements=512):
        _, s = quickstart(X, Y)
        float(s)
    entry = plan_cache.entries()[0]
    entry.quarantine_exec(7, "pallas")
    entry.quarantine_exec(7, "scan")
    assert entry.quarantined_execs(7) == {"pallas", "scan"}
    assert entry.tick_quarantine(7, ttl=2) == {"pallas", "scan"}
    assert entry.tick_quarantine(7, ttl=2) == set()
    assert 7 not in entry.quarantined


# ---------------------------------------------------------------------------
# Plan-cache persistence hardening
# ---------------------------------------------------------------------------


def _warm_cache():
    with mozart.session(executor="fused", batch_elements=512):
        _, s = quickstart(X, Y)
        float(s)


class TestPersistence:
    def test_persist_fault_leaves_existing_file_intact(self, tmp_path):
        path = str(tmp_path / "plans.json")
        _warm_cache()
        assert plan_cache.save(path, force=True) >= 1
        before = json.loads(open(path).read())
        with mozart.inject_faults("persist:fail:1"):
            with pytest.raises(InjectedFault):
                plan_cache.save(path, force=True)
        # The fault fired before the tmp-write + atomic rename: the
        # previous payload is untouched and still loads.
        assert json.loads(open(path).read()) == before
        plan_cache.clear()
        assert plan_cache.load(path) >= 1

    def test_quarantine_round_trips_through_persistence(self, tmp_path):
        path = str(tmp_path / "plans.json")
        _warm_cache()
        entry = plan_cache.entries()[0]
        entry.quarantine_exec(0, "pallas")
        assert plan_cache.save(path, force=True) >= 1
        plan_cache.clear()
        assert plan_cache.load(path) >= 1
        loaded = plan_cache.entries()[0]
        assert loaded.quarantined_execs(0) == {"pallas"}

    def test_v5_file_forward_migrates(self, tmp_path):
        path = str(tmp_path / "plans.json")
        _warm_cache()
        assert plan_cache.save(path, force=True) >= 1
        payload = json.loads(open(path).read())
        assert payload["schema"] == plan_cache.SCHEMA_VERSION
        payload["schema"] = 5
        for e in payload["entries"]:
            e.pop("quarantined", None)       # v5 files predate the field
        open(path, "w").write(json.dumps(payload))
        plan_cache.clear()
        assert plan_cache.load(path) >= 1
        assert plan_cache.stats["persist_migrated_v5"] >= 1
        assert plan_cache.entries()[0].quarantined == {}

    def test_cross_process_saves_merge_not_clobber(self, tmp_path):
        """Two processes sharing one MOZART_PLAN_CACHE path: the second
        save must MERGE the first process's entries (read-merge-write under
        the advisory lock), not overwrite them."""
        path = str(tmp_path / "shared.json")
        script = textwrap.dedent("""\
            import sys
            import jax.numpy as jnp
            from repro.core import mozart, plan_cache
            from repro.core import annotated_numpy as anp
            n = int(sys.argv[1])
            x = jnp.linspace(0.0, 1.0, n, dtype=jnp.float32)
            with mozart.session(executor="fused", batch_elements=512):
                s = anp.sum(anp.multiply(anp.exp(x), 0.5))
                float(s)
            print(plan_cache.save(sys.argv[2], force=True))
        """)
        for n in (1024, 2048):               # distinct shapes: distinct keys
            r = subprocess.run([sys.executable, "-c", script, str(n), path],
                               capture_output=True, text=True, timeout=300)
            assert r.returncode == 0, r.stderr
        payload = json.loads(open(path).read())
        assert len(payload["entries"]) == 2
        plan_cache.clear()
        assert plan_cache.load(path) == 2


# ---------------------------------------------------------------------------
# Observability: MZ4xx vocabulary + counted swallows
# ---------------------------------------------------------------------------


def test_mz4xx_codes_registered():
    from repro.core.analysis import CODES
    for code in ("MZ401", "MZ402", "MZ403", "MZ404", "MZ405", "MZ406"):
        assert code in CODES


def test_note_swallowed_is_counted_and_evented():
    resilience.note_swallowed("unit_test", ValueError("nope"))
    assert resilience.stats["swallowed_errors"] == 1
    assert resilience.stats["swallowed:unit_test"] == 1
    diags = resilience.events()
    assert any(d.code == "MZ406" and "unit_test" in d.subject for d in diags)


# ---------------------------------------------------------------------------
# Absorbed seed-era fault helpers (runtime/fault.py shim)
# ---------------------------------------------------------------------------


def test_fault_shim_reexports_same_objects():
    from repro.runtime import fault
    assert fault.with_retries is resilience.with_retries
    assert fault.StepTimer is resilience.StepTimer
    assert fault.FaultConfig is resilience.FaultConfig
    assert fault.run_with_restarts is resilience.run_with_restarts
    assert fault.TRANSIENT_ERRORS is resilience.TRANSIENT_ERRORS


class TestWithRetries:
    def test_transient_retried_then_succeeds(self):
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise RuntimeError("transient")
            return 42

        assert with_retries(flaky, retries=4) == 42
        assert len(calls) == 3
        assert resilience.stats["step_retries"] == 2

    def test_non_transient_propagates_immediately(self):
        calls = []

        def buggy():
            calls.append(1)
            raise KeyError("programming error")

        with pytest.raises(KeyError):
            with_retries(buggy, retries=5)
        assert len(calls) == 1

    def test_exhaustion_raises_step_failure_with_cause(self):
        boom = RuntimeError("always")

        def always():
            raise boom

        with pytest.raises(StepFailure) as ei:
            with_retries(always, retries=2)
        assert ei.value.__cause__ is boom

    def test_backoff_sleeps_exponentially(self, monkeypatch):
        slept = []
        monkeypatch.setattr(time, "sleep", slept.append)

        def always():
            raise RuntimeError("x")

        with pytest.raises(StepFailure):
            with_retries(always, retries=3, backoff_s=0.1)
        assert slept == [0.1, 0.2, 0.4]      # no sleep after the last try


class TestStepTimer:
    def test_straggler_flagged_and_hook_called(self):
        hits = []
        cfg = FaultConfig(min_steps_for_baseline=3, straggler_factor=2.0)
        t = StepTimer(cfg, on_straggler=lambda s, sec, med: hits.append((s, sec, med)))
        for i in range(3):
            assert not t.record(i, 0.01)
        assert t.record(3, 0.05)
        assert t.stragglers == [3]
        assert hits and hits[0][0] == 3 and hits[0][1] == 0.05
        assert resilience.stats["stragglers"] == 1

    def test_no_flag_before_baseline(self):
        t = StepTimer(FaultConfig(min_steps_for_baseline=5))
        assert not t.record(0, 100.0)        # no baseline yet: never flagged


def test_run_with_restarts_restarts_from_checkpoint():
    calls = {"n": 0}
    ckpts = [None, 3, 7]

    def make_state(step):
        return ({"from": step}, step or 0)

    def run_from(state, start):
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError(f"crash {calls['n']}")
        return ("done", start)

    result = run_with_restarts(
        make_state, run_from,
        fault_cfg=FaultConfig(max_restarts=3, backoff_s=0.0),
        latest_step=lambda: ckpts[min(calls["n"], 2)])
    assert result == ("done", 7)             # resumed from the NEWEST ckpt
    assert resilience.stats["restarts"] == 2


def test_run_with_restarts_gives_up_after_max():
    def run_from(state, start):
        raise RuntimeError("always down")

    with pytest.raises(RuntimeError, match="always down"):
        run_with_restarts(
            lambda step: (None, 0), run_from,
            fault_cfg=FaultConfig(max_restarts=1, backoff_s=0.0),
            latest_step=lambda: None)
