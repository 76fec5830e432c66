"""Cross-stage chunk handoff: the merge→re-split eliminator.

Covers: the SplitType ``can_handoff``/``rechunk`` protocol (including the
misaligned-grid property test and the ConcatSplit→ArraySplit rule);
differential parity (handoff on vs off) across every registered executor
and across ElementSplit/ReduceSplit/broadcast/axis-mismatch edges with
empty and odd-size inputs; ``scan``/``pallas`` stream ingest (flat
values read whole, padded-launch-buffer stacking, zero interior bytes, zero
warm retraces); interior-vs-terminal boundary-byte accounting; zero-chunk
stream hardening; chunk-buffer donation safety (plan-time veto of
observable producers + the pinned runtime backstop); and
``MOZART_PLAN_CACHE`` round trips asserting recorded decisions — including
ConcatSplit conversions and migrated v2/v3 files — replay in a fresh
process with zero planner calls.  Also: per-context counter scoping
(``ctx.counters`` sees only its own session's traffic), the
ConcatSplit→PytreeSplit per-leaf conversion rule, and donation-veto aging
(stale plan-time vetoes re-analyze after ``handoff.STALE_THRESHOLD``
consecutive disagreements with observed liveness).
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import mozart, plan_cache, stage_exec
from repro.core import annotated_numpy as anp
from repro.core import split_types as st
from repro.core.stage_exec import ChunkStream, available_executors


def _ranges(n, b):
    return [(s, min(s + b, n)) for s in range(0, n, b)]


# ---------------------------------------------------------------------------
# The SplitType handoff protocol
# ---------------------------------------------------------------------------


class TestCanHandoff:
    def test_array_split_same_grid(self):
        a = st.ArraySplit((100,), 0)
        assert a.can_handoff(st.ArraySplit((100,), 0))

    def test_array_split_axis_mismatch(self):
        assert not st.ArraySplit((8, 8), 0).can_handoff(st.ArraySplit((8, 8), 1))

    def test_array_split_shape_mismatch(self):
        assert not st.ArraySplit((100,), 0).can_handoff(st.ArraySplit((99,), 0))

    def test_non_splittable_consumers_refuse(self):
        a = st.ArraySplit((100,), 0)
        assert not a.can_handoff(st.BROADCAST)
        assert not a.can_handoff(st.ReduceSplit("add"))
        assert not a.can_handoff(st.ConcatSplit("t", 0))

    def test_non_array_producers_refuse(self):
        c = st.ArraySplit((100,), 0)
        assert not st.BROADCAST.can_handoff(c)
        assert not st.ReduceSplit("add").can_handoff(c)
        assert not st.UnknownSplit().can_handoff(c)

    def test_pytree_split(self):
        p = st.PytreeSplit("td", 10, 0)
        assert p.can_handoff(st.PytreeSplit("td", 10, 0))
        assert not p.can_handoff(st.PytreeSplit("td", 11, 0))
        assert not p.can_handoff(st.ArraySplit((10,), 0))


class TestRechunk:
    def _chunks(self, t, x, grid):
        return [t.split(x, s, e) for s, e in grid]

    @pytest.mark.parametrize("src_b,dst_b", [(4, 4), (4, 8), (8, 4), (10, 4), (4, 10)])
    def test_round_trips_any_aligned_grids(self, src_b, dst_b):
        n = 20
        t = st.ArraySplit((n,), 0)
        x = jnp.arange(n, dtype=jnp.float32)
        out, copied = t.rechunk(self._chunks(t, x, _ranges(n, src_b)),
                                _ranges(n, src_b), _ranges(n, dst_b))
        assert len(out) == len(_ranges(n, dst_b))
        np.testing.assert_array_equal(np.asarray(t.merge(out)), np.asarray(x))
        if src_b == dst_b:
            assert copied == 0          # identical grids: pure pass-through
        else:
            assert copied > 0

    def test_identity_passthrough_by_reference(self):
        n, b = 16, 4
        t = st.ArraySplit((n,), 0)
        chunks = self._chunks(t, jnp.arange(n, dtype=jnp.float32), _ranges(n, b))
        out, copied = t.rechunk(chunks, _ranges(n, b), _ranges(n, b))
        assert copied == 0
        assert all(o is c for o, c in zip(out, chunks))

    def test_coarsen_costs_at_most_one_copy(self):
        n, src_b, dst_b = 64, 8, 16
        t = st.ArraySplit((n,), 0)
        x = jnp.arange(n, dtype=jnp.float32)
        out, copied = t.rechunk(self._chunks(t, x, _ranges(n, src_b)),
                                _ranges(n, src_b), _ranges(n, dst_b))
        assert copied == int(x.nbytes)  # one copy — merge+re-split pays two
        np.testing.assert_array_equal(np.asarray(t.merge(out)), np.asarray(x))

    def test_pytree_split_rechunk(self):
        n = 12
        leaves = {"a": jnp.arange(n, dtype=jnp.float32),
                  "b": jnp.ones((n, 2), jnp.float32)}
        t = st.PytreeSplit("td", n, 0)
        out, copied = t.rechunk([t.split(leaves, s, e) for s, e in _ranges(n, 3)],
                                _ranges(n, 3), _ranges(n, 6))
        merged = t.merge(out)
        np.testing.assert_array_equal(np.asarray(merged["a"]),
                                      np.asarray(leaves["a"]))
        assert copied > 0


# ---------------------------------------------------------------------------
# Differential: handoff on == handoff off, everywhere
# ---------------------------------------------------------------------------


def _eval_chain(x, evals=3):
    """Multi-evaluation elementwise chain: every evaluation boundary is a
    producer→consumer edge with identical ArraySplit grids (the serve-decode
    shape — exactly where the merge→re-split round trip used to live)."""
    cur = x
    for _ in range(evals):
        cur = anp.multiply(anp.add(cur, 1.0), 0.5)
        mozart.evaluate()
    return cur


def _reduce_edge(x):
    """ElementSplit stage → ReduceSplit output → broadcast into the next
    evaluation: the boundary must merge (partials), never stream."""
    s = anp.sum(anp.exp(x))
    mozart.evaluate()
    return anp.multiply(x, s)


def _axis_mismatch(m):
    """Row-split then column-split: boundary with INCOMPATIBLE grids."""
    a = anp.normalize_axis(m, axis=1)
    mozart.evaluate()
    return anp.normalize_axis(a, axis=0)


SURFACES = {
    "element_chain": (lambda: jnp.linspace(0., 1., 10_000, dtype=jnp.float32),
                      _eval_chain),
    "reduce_edge": (lambda: jnp.linspace(0., 1., 10_000, dtype=jnp.float32),
                    _reduce_edge),
    "axis_mismatch": (lambda: jnp.arange(64 * 32, dtype=jnp.float32).reshape(64, 32),
                      _axis_mismatch),
    "empty": (lambda: jnp.zeros((0,), jnp.float32), _eval_chain),
    "odd_size": (lambda: jnp.linspace(0., 1., 17, dtype=jnp.float32),
                 lambda x: _eval_chain(x, evals=2)),
}


@pytest.mark.parametrize("surface", sorted(SURFACES))
@pytest.mark.parametrize("executor", sorted(available_executors()))
def test_differential_handoff_on_off(executor, surface):
    make, fn = SURFACES[surface]
    if executor == "sharded" and surface in ("empty", "odd_size", "axis_mismatch"):
        pytest.skip("sharded requires mesh-divisible element counts")
    kwargs = {"batch_elements": 2048 if surface != "odd_size" else 4}
    if executor == "sharded":
        kwargs["mesh"] = jax.make_mesh((1,), ("data",))
    outs = {}
    for handoff in (True, False):
        plan_cache.clear()
        with mozart.session(executor=executor, handoff=handoff, **kwargs) as ctx:
            out = np.asarray(fn(make()))
        outs[handoff] = (out, dict(ctx.stats))
    np.testing.assert_allclose(outs[True][0], outs[False][0],
                               rtol=2e-5, atol=1e-6)
    # handoff=False must never stream or ingest
    assert outs[False][1].get("streamed_outputs", 0) == 0
    assert outs[False][1].get("stream_ingests", 0) == 0


def test_pytree_split_streams_end_to_end():
    """PytreeSplit outputs hand off like arrays: a chained pytree pipeline
    (optimizer-state shape) streams across evaluation boundaries, and batch
    sizing reads the stream's AVAL (the stream object is not a pytree)."""
    from repro.core import splittable
    from repro.core import split_types as _st

    @splittable(s=_st.Pytree(0), ret=_st.Pytree(0))
    def tree_step(s):
        return {"p": s["p"] * 0.5 + 1.0, "m": s["m"] + s["p"][:, None]}

    n = 4096
    state = {"p": jnp.arange(n, dtype=jnp.float32),
             "m": jnp.ones((n, 2), jnp.float32)}
    outs = {}
    for handoff in (True, False):
        plan_cache.clear()
        with mozart.session(executor="fused", batch_elements=512,
                            handoff=handoff) as ctx:
            cur = state
            for _ in range(3):
                cur = tree_step(cur)
                mozart.evaluate()
            outs[handoff] = (jax.tree_util.tree_map(np.asarray, cur.value),
                             dict(ctx.stats))
    assert outs[True][1].get("streamed_outputs", 0) == 3
    assert outs[True][1].get("stream_ingests", 0) == 2
    for k in ("p", "m"):
        np.testing.assert_allclose(outs[True][0][k], outs[False][0][k],
                                   rtol=1e-6)


def test_auto_executor_stream_stats_not_double_counted():
    """AutoExecutor resolves once for scoring and the delegate resolves
    again for execution — only the delegate's resolve may tally.  Delegates
    are pinned to the stream-capable `fused` so the streams actually exist
    (auto's own measured pick on this host is `eager`, which never chunks)."""
    n = 20_000
    x = jnp.linspace(0., 1., n, dtype=jnp.float32)
    plan_cache.clear()

    def once():
        with mozart.session(executor="auto", batch_elements=4096) as ctx:
            out = np.asarray(_eval_chain(x))
        return out, ctx

    out1, _ = once()
    for e in plan_cache.entries():      # pin every stage to the fused driver
        for tm_id in range(len(e.stage_templates)):
            e.pin_exec(tm_id, "fused")
    out2, ctx = once()                  # warm: auto replays the pins
    np.testing.assert_allclose(out1, out2, rtol=1e-6)
    assert ctx.stats["auto_pinned_replays"] == 3
    assert ctx.stats["streamed_outputs"] == 3
    # 2 interior edges: exactly ONE ingest event per edge, no double tally
    assert ctx.stats.get("stream_ingests", 0) == 2
    assert ctx.stats.get("stream_materialized", 0) == 0


# ---------------------------------------------------------------------------
# Boundary traffic: interior boundaries drop to zero
# ---------------------------------------------------------------------------


class TestBoundaryTraffic:
    N, BATCH = 50_000, 8192

    def _run(self, handoff, observe=True):
        def once():
            with mozart.session(executor="fused", batch_elements=self.BATCH,
                                handoff=handoff) as ctx:
                cur = _eval_chain(jnp.linspace(0., 1., self.N, dtype=jnp.float32))
                out = np.asarray(cur) if observe else None
            return out, ctx
        plan_cache.clear()
        once(); once()                   # plan, then warm the cache
        before = stage_exec.bytes_materialized()
        out, ctx = once()
        return out, ctx, stage_exec.bytes_materialized() - before

    def test_interior_boundaries_zero_bytes(self):
        final_bytes = self.N * 4
        _, ctx, on_bytes = self._run(handoff=True)
        assert on_bytes == final_bytes   # ONLY the observed output merged
        assert ctx.stats["streamed_outputs"] == 3
        assert ctx.stats["stream_ingests"] == 2
        _, _, off_bytes = self._run(handoff=False)
        # merge-everything pays ≥ (3 merges + 2 re-splits) x n bytes
        assert off_bytes >= 5 * final_bytes

    def test_unobserved_output_never_materializes(self):
        _, ctx, on_bytes = self._run(handoff=True, observe=False)
        assert on_bytes == 0             # nothing observed: zero merges total

    def test_zero_planner_calls_on_warm_handoff(self):
        _, ctx, _ = self._run(handoff=True)
        assert ctx.stats["planner_calls"] == 0
        assert ctx.stats.get("plan_cache_hits", 0) >= 3

    def test_pipe_ablation_streams_interior(self):
        """pipeline=False (Table-4 "-pipe") makes every op its own stage;
        handoff then removes the per-boundary round trips the ablation used
        to pay INSIDE one evaluation."""
        x = jnp.linspace(0., 1., self.N, dtype=jnp.float32)

        def once(handoff):
            with mozart.session(executor="fused", batch_elements=self.BATCH,
                                pipeline=False, handoff=handoff) as ctx:
                out = np.asarray(anp.multiply(anp.exp(anp.add(x, 1.0)), 0.5))
            return out, ctx
        plan_cache.clear()
        once(True); once(True)
        before = stage_exec.bytes_materialized()
        on_out, ctx = once(True)
        on_bytes = stage_exec.bytes_materialized() - before
        assert ctx.stats["streamed_outputs"] >= 2
        assert on_bytes == self.N * 4
        plan_cache.clear()
        once(False); once(False)
        before = stage_exec.bytes_materialized()
        off_out, _ = once(False)
        assert stage_exec.bytes_materialized() - before >= 5 * self.N * 4
        np.testing.assert_allclose(on_out, off_out, rtol=2e-5)

    def test_incapable_executor_materializes_on_ingest(self):
        """A stream handed to a whole-value executor merges on ingest —
        correct, merely the old cost.  (`eager` is the remaining
        stream-incapable chunking-free strategy; `scan` and `pallas` became
        stream ingesters in the handoff-completion pass.)"""
        x = jnp.linspace(0., 1., self.N, dtype=jnp.float32)
        plan_cache.clear()
        with mozart.session(executor="fused", batch_elements=self.BATCH) as ctx:
            a = anp.multiply(anp.add(x, 1.0), 0.5)
            mozart.evaluate()            # `a` streams (pure output, fused)
            assert isinstance(ctx.graph.nodes[a._node.id].result, ChunkStream)
            mozart.configure(executor="eager")
            out = np.asarray(anp.exp(a))
        assert ctx.stats["stream_materialized"] >= 1
        want = np.exp((np.linspace(0., 1., self.N, dtype=np.float32) + 1) * 0.5)
        np.testing.assert_allclose(out, want, rtol=2e-5)

    def test_scan_ingests_fused_stream(self):
        """`scan` is a stream ingester now: a chunk-list stream from the
        fused driver is concatenated once into the flat value the driver
        reads (a layout copy) — no materialize on the boundary."""
        x = jnp.linspace(0., 1., self.N, dtype=jnp.float32)
        plan_cache.clear()
        with mozart.session(executor="fused", batch_elements=self.BATCH) as ctx:
            a = anp.multiply(anp.add(x, 1.0), 0.5)
            mozart.evaluate()            # `a` streams (pure output, fused)
            assert isinstance(ctx.graph.nodes[a._node.id].result, ChunkStream)
            mozart.configure(executor="scan")
            laid = ctx.stats.get("layout_bytes", 0)
            out = np.asarray(anp.exp(a))
        assert ctx.stats.get("stream_materialized", 0) == 0
        assert ctx.stats["stream_ingests"] >= 1
        assert ctx.stats["layout_bytes"] - laid == self.N * 4
        want = np.exp((np.linspace(0., 1., self.N, dtype=np.float32) + 1) * 0.5)
        np.testing.assert_allclose(out, want, rtol=2e-5)


# ---------------------------------------------------------------------------
# Donation safety
# ---------------------------------------------------------------------------


class TestDonation:
    def test_alive_future_donates_copies_only(self):
        """A stream whose producer Future is still observable must keep its
        own buffers — the driver gets defensive COPIES to donate, and
        observing the producer after consumption still works."""
        n, b = 20_000, 4096
        x = jnp.linspace(0., 1., n, dtype=jnp.float32)
        plan_cache.clear()
        for _ in range(3):
            with mozart.session(executor="fused", batch_elements=b) as ctx:
                a = anp.multiply(anp.add(x, 1.0), 0.5)
                mozart.evaluate()
                out = np.asarray(anp.exp(a))     # consumes a's stream
                a_val = np.asarray(a)            # a observed AFTER consumption
            if ctx.stats.get("donated_chunks", 0):
                assert ctx.stats["donation_copies"] == ctx.stats["donated_chunks"]
        want_a = (np.linspace(0., 1., n, dtype=np.float32) + 1) * 0.5
        np.testing.assert_allclose(a_val, want_a, rtol=2e-5)
        np.testing.assert_allclose(out, np.exp(want_a), rtol=2e-5)

    def test_liveness_flap_does_not_retrace(self):
        """The donate key set is structural: whether the producer's Future
        happens to be alive on a given call must not change the pinned
        driver variant (zero retraces on warm calls either way)."""
        n, b = 20_000, 4096
        x = jnp.linspace(0., 1., n, dtype=jnp.float32)
        plan_cache.clear()

        def once(hold):
            with mozart.session(executor="fused", batch_elements=b) as ctx:
                a = anp.multiply(anp.add(x, 1.0), 0.5)
                mozart.evaluate()
                e = anp.exp(a)                   # registered; holds a NodeRef
                if not hold:
                    del a                        # Future dies pre-consumption
                out = np.asarray(e)
                if hold:
                    _ = np.asarray(a)            # observe AFTER consumption
            return out, ctx

        once(True); once(True)                   # plan + warm the cache
        before = stage_exec.trace_count()
        o1, c1 = once(True)                      # producer observable: copies
        o2, c2 = once(False)                     # producer dead: real donation
        o3, _ = once(True)
        assert stage_exec.trace_count() == before
        assert c1.stats["exec_builds"] == 0 and c2.stats["exec_builds"] == 0
        assert c1.stats.get("donation_copies", 0) > 0
        assert c2.stats.get("donation_copies", 0) == 0
        assert c2.stats.get("donated_chunks", 0) > 0
        np.testing.assert_allclose(o1, o2, rtol=1e-6)
        np.testing.assert_allclose(o1, o3, rtol=1e-6)

    def test_dead_future_donates_and_stays_correct(self):
        n, b = 20_000, 4096
        x = jnp.linspace(0., 1., n, dtype=jnp.float32)
        plan_cache.clear()

        def once():
            with mozart.session(executor="fused", batch_elements=b) as ctx:
                cur = _eval_chain(x)
                out = np.asarray(cur)
            return out, ctx
        once(); once()
        out, ctx = once()
        assert ctx.stats["donated_chunks"] > 0
        want = np.asarray(x)
        for _ in range(3):
            want = (want + 1.0) * 0.5
        np.testing.assert_allclose(out, want, rtol=2e-5)


# ---------------------------------------------------------------------------
# Handoff decisions replay from MOZART_PLAN_CACHE with zero planner calls
# ---------------------------------------------------------------------------

_PRELUDE = """
import json, sys
import jax.numpy as jnp
import numpy as np
from repro.core import mozart, plan_cache, stage_exec
from repro.core import annotated_numpy as anp

x = jnp.linspace(0.0, 1.0, 30_000, dtype=jnp.float32)

def run():
    with mozart.session(executor="fused", batch_elements=4096) as ctx:
        cur = x
        for _ in range(3):
            cur = anp.multiply(anp.add(cur, 1.0), 0.5)
            mozart.evaluate()
        out = np.asarray(cur)
    return out, ctx
"""

_PROC_A = _PRELUDE + """
run(); run()
out, ctx = run()
print(json.dumps({"sum": float(out.sum()),
                  "streamed": ctx.stats["streamed_outputs"],
                  "ingests": ctx.stats["stream_ingests"]}))
"""

_PROC_B = _PRELUDE + """
b0 = stage_exec.bytes_materialized()
out, ctx = run()
print(json.dumps({"sum": float(out.sum()),
                  "streamed": ctx.stats["streamed_outputs"],
                  "ingests": ctx.stats["stream_ingests"],
                  "planner_calls": ctx.stats["planner_calls"],
                  "bytes": stage_exec.bytes_materialized() - b0,
                  "pc": dict(plan_cache.stats)}))
"""


def _run_subprocess(code, path):
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    existing = env.get("PYTHONPATH")
    env["PYTHONPATH"] = src + (os.pathsep + existing if existing else "")
    env["MOZART_PLAN_CACHE"] = path
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_handoff_decisions_replay_from_persisted_cache(tmp_path):
    """Process A records handoff decisions in its persisted plans; a FRESH
    process B replays them — zero planner calls, streams from call one, and
    interior boundary bytes already zero."""
    path = str(tmp_path / "plans.json")
    a = _run_subprocess(_PROC_A, path)
    assert a["streamed"] == 3 and a["ingests"] == 2
    assert os.path.exists(path)

    b = _run_subprocess(_PROC_B, path)
    assert b["pc"].get("persist_loaded", 0) >= 1
    assert b["planner_calls"] == 0            # decisions replayed, not re-derived
    assert b["streamed"] == 3 and b["ingests"] == 2
    assert b["bytes"] == 30_000 * 4           # final observed output only
    assert np.isclose(a["sum"], b["sum"], rtol=1e-6)


# ---------------------------------------------------------------------------
# scan / pallas stream ingest (the handoff-completion pass)
# ---------------------------------------------------------------------------


class TestScanPallasIngest:
    """Every executor's interior boundary hits zero, not just the chunk
    loops: `scan` reads a scan producer's flat output whole and
    concatenates a chunk list once, `pallas` stacks streams into the padded
    launch buffer."""

    N, BATCH = 50_000, 8192

    def _chain(self, executor, handoff=True):
        x = jnp.linspace(0., 1., self.N, dtype=jnp.float32)

        def once():
            with mozart.session(executor=executor, batch_elements=self.BATCH,
                                handoff=handoff) as ctx:
                out = np.asarray(_eval_chain(x))
            return out, ctx

        plan_cache.clear()
        once(); once()                   # plan, then warm (tune + pin)
        stage_exec.reset_materialized()
        t0 = stage_exec.trace_count()
        out, ctx = once()
        return out, ctx, stage_exec.trace_count() - t0

    @pytest.mark.parametrize("executor", ["scan", "pallas"])
    def test_interior_zero_and_zero_retrace(self, executor):
        out, ctx, traces = self._chain(executor)
        assert stage_exec.bytes_interior() == 0
        assert traces == 0               # warm calls: zero jit retraces
        assert ctx.stats["planner_calls"] == 0
        off_out, _, _ = self._chain(executor, handoff=False)
        np.testing.assert_allclose(out, off_out, rtol=2e-5)

    def test_scan_streams_and_donates_carry(self):
        _, ctx, _ = self._chain("scan")
        assert ctx.stats["streamed_outputs"] == 3
        assert ctx.stats["stream_ingests"] == 2
        # dead carries donate for real — no defensive copies on this chain
        assert ctx.stats["donated_chunks"] > 0
        assert ctx.stats.get("donation_copies", 0) == 0
        # the driver's outputs are already whole: observing the final one
        # merges nothing, terminal or interior
        assert stage_exec.bytes_terminal() == 0

    def test_scan_carry_passthrough_is_stacked(self):
        """A scan stage's streamed output is the driver's flat output with
        its merge already done (ChunkStream.from_merged): a scan consumer
        reads it whole — no chunk list, no layout copy, no boundary bytes."""
        x = jnp.linspace(0., 1., self.N, dtype=jnp.float32)
        plan_cache.clear()
        with mozart.session(executor="scan", batch_elements=self.BATCH) as ctx:
            a = anp.multiply(anp.add(x, 1.0), 0.5)
            mozart.evaluate()
            res = ctx.graph.nodes[a._node.id].result
            assert isinstance(res, ChunkStream)
            assert res.merged is not None and res._chunks is None
            assert res.merged.shape == (self.N,)
            laid = ctx.stats.get("layout_bytes", 0)
            stage_exec.reset_materialized()
            out = np.asarray(anp.exp(a))
            assert ctx.stats.get("layout_bytes", 0) == laid
            assert stage_exec.bytes_interior() == 0
            assert res._chunks is None       # never sliced into chunks
            assert ctx.stats["stream_ingests"] == 1
        want = np.exp((np.asarray(x) + 1) * 0.5)
        np.testing.assert_allclose(out, want, rtol=2e-5)

    def test_pallas_ingests_fused_stream(self):
        """A chunk-list stream stacks straight into the pallas launch
        buffer — no materialize on the boundary."""
        x = jnp.linspace(0., 1., self.N, dtype=jnp.float32)

        def once():
            with mozart.session(executor="fused",
                                batch_elements=self.BATCH) as ctx:
                a = anp.multiply(anp.add(x, 1.0), 0.5)
                mozart.evaluate()
                mozart.configure(executor="pallas")
                out = np.asarray(anp.exp(a))
            return out, ctx

        plan_cache.clear()
        once(); once()
        stage_exec.reset_materialized()
        out, ctx = once()
        assert stage_exec.bytes_interior() == 0
        assert ctx.stats["stream_ingests"] >= 1
        assert ctx.stats.get("stream_materialized", 0) == 0
        assert ctx.stats["pallas_stages"] == 1
        want = np.exp((np.asarray(x) + 1) * 0.5)
        np.testing.assert_allclose(out, want, rtol=2e-5)

    def test_misaligned_grid_rechunks_once(self):
        """A producer grid beyond the consumer's slack re-grids through
        SplitType.rechunk — at most one copy, tallied and counted."""
        x = jnp.linspace(0., 1., 20_000, dtype=jnp.float32)
        plan_cache.clear()
        with mozart.session(executor="fused", batch_elements=6000) as ctx:
            a = anp.multiply(anp.add(x, 1.0), 0.5)
            mozart.evaluate()
            mozart.configure(batch_elements=1024)   # 6000 > 2x1024: re-grid
            stage_exec.reset_materialized()
            out = np.asarray(anp.exp(a))
        assert ctx.stats["handoff_rechunks"] == 1
        # the rechunk pays at most ONE copy of the data (merge+re-split = 2)
        rechunk_bytes = sum(nb for kind, _, nb in stage_exec.materialize_events()
                            if kind == "interior:rechunk")
        assert 0 < rechunk_bytes <= x.nbytes
        want = np.exp((np.asarray(x) + 1) * 0.5)
        np.testing.assert_allclose(out, want, rtol=2e-5)


# ---------------------------------------------------------------------------
# ConcatSplit→ArraySplit handoff (fresh-output producers)
# ---------------------------------------------------------------------------


_REPEAT2 = None


def _make_repeat2():
    # One AnnotatedFn for the whole module: the plan cache matches entries
    # on function identity, so a fresh wrapper per run would always miss.
    global _REPEAT2
    if _REPEAT2 is None:
        from repro.core import splittable

        @splittable(x=st.Along(0), ret=st.Concat("rep2", 0))
        def repeat2(x):
            return jnp.repeat(x, 2)

        _REPEAT2 = repeat2
    return _REPEAT2


_TREE_REPEAT2 = None
_TREE_SCALE = None


def _make_tree_repeat2():
    # Fresh-output producer whose pieces are PYTREES with mixed leaf ranks
    # (the optimizer-state shape) — exercises the per-leaf conversion rule.
    global _TREE_REPEAT2
    if _TREE_REPEAT2 is None:
        from repro.core import splittable

        @splittable(x=st.Along(0), ret=st.Concat("trep2", 0))
        def tree_repeat2(x):
            y = jnp.repeat(x, 2)
            return {"p": y, "m": jnp.stack([y, y * 2.0], axis=1)}

        _TREE_REPEAT2 = tree_repeat2
    return _TREE_REPEAT2


def _make_tree_scale():
    global _TREE_SCALE
    if _TREE_SCALE is None:
        from repro.core import splittable

        @splittable(s=st.Pytree(0), ret=st.Pytree(0))
        def tree_scale(s):
            return {"p": (s["p"] + 1.0) * 0.5, "m": s["m"] * 2.0}

        _TREE_SCALE = tree_scale
    return _TREE_SCALE


class TestConcatHandoff:
    N, BATCH = 10_000, 2048

    def _run(self, handoff):
        repeat2 = _make_repeat2()
        x = jnp.linspace(0., 1., self.N, dtype=jnp.float32)
        with mozart.session(executor="fused", batch_elements=self.BATCH,
                            handoff=handoff) as ctx:
            y = repeat2(x)               # fresh output: ConcatSplit
            out = np.asarray(anp.multiply(anp.add(y, 1.0), 0.5))
        return out, ctx

    def test_concat_producer_hands_off_to_array_consumer(self):
        plan_cache.clear()
        self._run(True); self._run(True)
        stage_exec.reset_materialized()
        out, ctx = self._run(True)
        assert ctx.stats["stream_converted"] == 1
        assert ctx.stats["stream_ingests"] == 1
        assert ctx.stats["planner_calls"] == 0
        assert stage_exec.bytes_interior() == 0
        off, _ = self._run(False)
        np.testing.assert_allclose(out, off, rtol=1e-6)
        want = (np.repeat(np.asarray(x := np.linspace(0., 1., self.N,
                                                      dtype=np.float32)), 2)
                + 1) * 0.5
        np.testing.assert_allclose(out, want, rtol=2e-5)

    def test_conversion_recorded_in_plan_entry(self):
        plan_cache.clear()
        self._run(True)
        recs = [ho for e in plan_cache.entries()
                if e.handoff
                for ho in e.handoff.values() if ho.convert_in]
        assert recs, "ConcatSplit→ArraySplit conversion not recorded"
        ho = recs[0]
        assert ho.convert_in <= ho.stream_in
        # round-trips through the persisted JSON form
        assert (type(ho).from_json(ho.to_json()).convert_in == ho.convert_in)

    def test_protocol_rules(self):
        c = st.ConcatSplit("t", 0)
        assert c.can_handoff(st.ArraySplit((64,), 0))
        assert not c.can_handoff(st.ArraySplit((8, 8), 1))   # axis mismatch
        assert not c.can_handoff(st.ArraySplit((), 0))       # scalar geometry
        assert not c.can_handoff(st.ConcatSplit("t", 0))     # not splittable
        assert not st.ConcatSplit("t", 1).can_handoff(st.ArraySplit((64,), 0))

    def test_total_mismatch_materializes(self):
        """Pieces that do not tile the consumer's geometry fall back to the
        merge — adapt_stream returns None, never a wrong grid."""
        t = st.ConcatSplit("t", 0)
        chunks = [jnp.ones((3,), jnp.float32), jnp.ones((4,), jnp.float32)]
        s = ChunkStream(chunks, [(0, 2), (2, 4)], t,
                        jax.ShapeDtypeStruct((7,), jnp.float32))
        from repro.core.stage_exec import adapt_stream
        good = adapt_stream(s, st.ArraySplit((7,), 0))
        assert good is not None and good.ranges == [(0, 3), (3, 7)]
        assert adapt_stream(s, st.ArraySplit((8,), 0)) is None

    def test_concat_producer_hands_off_to_pytree_consumer(self):
        """Fresh-output producers that emit PYTREES hand off to PytreeSplit
        consumers: the conversion decides per LEAF (mixed ranks/trailing
        dims are fine as long as every leaf of a chunk agrees on its
        split-axis extent) — previously this edge always merged."""
        tree_rep2 = _make_tree_repeat2()
        tree_scale = _make_tree_scale()
        x = jnp.linspace(0., 1., self.N, dtype=jnp.float32)

        def run(handoff):
            plan_cache.clear()
            for _ in range(2):               # plan, then warm
                with mozart.session(executor="fused",
                                    batch_elements=self.BATCH,
                                    handoff=handoff) as ctx:
                    out = jax.tree_util.tree_map(
                        np.asarray, tree_scale(tree_rep2(x)).value)
            return out, ctx

        out, ctx = run(True)
        assert ctx.stats["stream_converted"] == 1
        assert ctx.counters.bytes_interior() == 0
        assert ctx.stats["planner_calls"] == 0
        off, _ = run(False)
        for k in ("p", "m"):
            np.testing.assert_allclose(out[k], off[k], rtol=1e-6)
        want_p = (np.repeat(np.linspace(0., 1., self.N, dtype=np.float32), 2)
                  + 1.0) * 0.5
        np.testing.assert_allclose(out["p"], want_p, rtol=2e-5)

    def test_pytree_protocol_rule(self):
        c = st.ConcatSplit("t", 0)
        assert c.can_handoff(st.PytreeSplit("t", 64, 0))
        assert not c.can_handoff(st.PytreeSplit("t", 64, 1))  # axis mismatch
        assert not st.ConcatSplit("t", 1).can_handoff(st.PytreeSplit("t", 64, 0))

    def test_pytree_leaf_extent_mismatch_materializes(self):
        """Per-leaf rule: every leaf of a chunk must agree on its split-axis
        extent — a disagreeing chunk cannot define one grid range, so
        adapt_stream falls back to the merge (returns None)."""
        from repro.core.stage_exec import adapt_stream
        t = st.ConcatSplit("t", 0)
        aval = {"a": jax.ShapeDtypeStruct((7,), jnp.float32),
                "b": jax.ShapeDtypeStruct((7, 2), jnp.float32)}
        good = [{"a": jnp.ones((3,), jnp.float32),
                 "b": jnp.ones((3, 2), jnp.float32)},
                {"a": jnp.ones((4,), jnp.float32),
                 "b": jnp.ones((4, 2), jnp.float32)}]
        s = ChunkStream(good, [(0, 2), (2, 4)], t, aval)
        ok = adapt_stream(s, st.PytreeSplit("t", 7, 0))
        assert ok is not None and ok.ranges == [(0, 3), (3, 7)]
        # same buffers re-wrapped: zero copies
        assert ok._chunks is s._chunks or ok._chunks == s._chunks

        bad = [{"a": jnp.ones((3,), jnp.float32),
                "b": jnp.ones((4, 2), jnp.float32)}]   # leaves disagree
        s2 = ChunkStream(bad, [(0, 1)], t,
                         {"a": jax.ShapeDtypeStruct((3,), jnp.float32),
                          "b": jax.ShapeDtypeStruct((4, 2), jnp.float32)})
        assert adapt_stream(s2, st.PytreeSplit("t", 3, 0)) is None
        # total mismatch still falls back too
        assert adapt_stream(s, st.PytreeSplit("t", 8, 0)) is None

    def test_empty_concat_pieces_stream(self):
        """Zero-size fresh pieces (filter-to-nothing) hand off as an empty
        grid instead of crashing merge([]) — the zero-chunk hardening."""
        from repro.core import splittable

        @splittable(x=st.Along(0), ret=st.Concat("nil", 0))
        def drop_all(x):
            return x[:0]

        x = jnp.linspace(0., 1., self.N, dtype=jnp.float32)
        plan_cache.clear()
        with mozart.session(executor="fused", batch_elements=self.BATCH) as ctx:
            y = drop_all(x)
            out = np.asarray(anp.add(y, 1.0))
        assert out.shape == (0,)


# ---------------------------------------------------------------------------
# Zero-chunk / empty-stream hardening (regression: PR 4 stream paths)
# ---------------------------------------------------------------------------


class TestZeroChunkStreams:
    AVAL = jax.ShapeDtypeStruct((0,), jnp.float32)

    def test_materialize_zero_chunk_stream(self):
        s = ChunkStream([], [(0, 0)], st.ArraySplit((0,), 0), self.AVAL)
        out = s.materialize()
        assert out.shape == (0,) and out.dtype == jnp.float32

    def test_chunk_accessor_zero_chunk_stream(self):
        s = ChunkStream([], [(0, 0)], st.ArraySplit((0,), 0), self.AVAL)
        assert s.chunk(0).shape == (0,)

    def test_rechunk_degenerate_grids(self):
        """Zero-size destination ranges carve empty slices instead of
        crashing merge([])."""
        t = st.ArraySplit((0,), 0)
        chunks = [jnp.zeros((0,), jnp.float32)] * 3
        out, copied = t.rechunk(chunks, [(0, 0)] * 3, [(0, 0)])
        assert len(out) == 1 and out[0].shape == (0,)
        assert copied == 0

    @pytest.mark.parametrize("executor",
                             [e for e in sorted(available_executors())
                              if e != "sharded"])
    def test_empty_chain_streams_safely(self, executor):
        """n == 0 through a multi-evaluation chain with handoff on: every
        executor's stream ingest/materialize path must survive the
        degenerate single-zero-size-chunk grid."""
        plan_cache.clear()
        with mozart.session(executor=executor, batch_elements=64) as ctx:
            out = np.asarray(_eval_chain(jnp.zeros((0,), jnp.float32)))
        assert out.shape == (0,)


# ---------------------------------------------------------------------------
# Donation: plan-time veto + the pinned runtime backstop
# ---------------------------------------------------------------------------


class TestDonationVeto:
    def test_observable_producer_vetoed_at_plan_time(self):
        """An in-plan producer whose Future is alive at analysis time never
        becomes a donation point: no donated chunks AND no defensive copies
        (before the veto, the runtime burned one copy per chunk)."""
        n, b = 20_000, 4096
        x = jnp.linspace(0., 1., n, dtype=jnp.float32)
        plan_cache.clear()

        def once():
            with mozart.session(executor="fused", batch_elements=b,
                                pipeline=False) as ctx:
                a = anp.add(x, 1.0)          # own stage (pipeline=False)
                out = np.asarray(anp.multiply(a, 0.5))  # a's Future held
                a_val = np.asarray(a)        # observed after consumption
            return out, a_val, ctx

        for _ in range(3):
            out, a_val, ctx = once()
        assert ctx.stats.get("donated_chunks", 0) == 0
        assert ctx.stats.get("donation_copies", 0) == 0
        np.testing.assert_allclose(a_val, np.asarray(x) + 1, rtol=1e-6)
        np.testing.assert_allclose(out, (np.asarray(x) + 1) * 0.5, rtol=1e-6)

    def test_dead_producer_still_donates(self):
        """The veto is scoped: a producer with no live Future at analysis
        time keeps its donation point."""
        n, b = 20_000, 4096
        x = jnp.linspace(0., 1., n, dtype=jnp.float32)
        plan_cache.clear()

        def once():
            with mozart.session(executor="fused", batch_elements=b,
                                pipeline=False) as ctx:
                out = np.asarray(anp.multiply(anp.add(x, 1.0), 0.5))
            return out, ctx

        once(); once()
        out, ctx = once()
        assert ctx.stats.get("donated_chunks", 0) > 0
        np.testing.assert_allclose(out, (np.asarray(x) + 1) * 0.5, rtol=1e-6)

    def test_runtime_backstop_message_pinned(self):
        """The donated-stream late-merge raise stays as the backstop; its
        message is pinned and carries the MZ301 lint code plus the donating
        stage/edge (``ChunkStream.donor``, set by mark_stream_consumed)."""
        t = st.ArraySplit((8,), 0)
        s = ChunkStream([jnp.arange(4, dtype=jnp.float32),
                         jnp.arange(4, dtype=jnp.float32)],
                        [(0, 4), (4, 8)], t,
                        jax.ShapeDtypeStruct((8,), jnp.float32))
        s.consumed = True
        s.donor = "stage 7 input ('in', 0)"
        with pytest.raises(RuntimeError,
                           match="donated to a driver and can no longer be "
                                 "merged") as ei:
            s.materialize()
        assert "[MZ301]" in str(ei.value)
        assert "stage 7 input ('in', 0)" in str(ei.value)
        assert stage_exec.DONATED_MERGE_ERROR.startswith("[MZ301]")
        assert "handoff analysis bug" in stage_exec.DONATED_MERGE_ERROR


# ---------------------------------------------------------------------------
# Donation-veto aging: stale vetoes re-analyze instead of persisting forever
# ---------------------------------------------------------------------------


class TestVetoAging:
    """A plan-time donation decision is a snapshot of Future liveness.  When
    observed liveness disagrees with the recorded ``vetoed``/``last_use``
    sets for ``handoff.STALE_THRESHOLD`` consecutive calls, the entry
    re-analyzes against current liveness — so a producer that stops being
    observed regains its donation point, and one that STARTS being observed
    stops paying per-chunk defensive copies."""

    N, B = 20_000, 4096

    def _once(self, hold):
        x = jnp.linspace(0., 1., self.N, dtype=jnp.float32)
        with mozart.session(executor="fused", batch_elements=self.B,
                            pipeline=False) as ctx:
            a = anp.add(x, 1.0)              # own stage (pipeline=False)
            e = anp.multiply(a, 0.5)
            if not hold:
                del a                        # producer dies pre-analysis
            out = np.asarray(e)
            if hold:
                _ = np.asarray(a)            # observed after consumption
        return out, ctx

    def test_stale_veto_ages_into_donation(self):
        """Producer observable at plan time → vetoed (no donation).  After
        it stops being observed, two stale calls age the veto out and the
        donation point comes back copy-free."""
        from repro.core import handoff as ho_mod
        plan_cache.clear()
        out0, c0 = self._once(hold=True)     # analysis: Future alive → veto
        assert c0.stats.get("donated_chunks", 0) == 0
        assert c0.stats.get("donation_copies", 0) == 0
        _, c1 = self._once(hold=False)       # stale ×1: hysteresis holds
        assert c1.stats.get("handoff_reanalyzed", 0) == 0
        assert c1.stats.get("donated_chunks", 0) == 0
        _, c2 = self._once(hold=False)       # stale ×2 == STALE_THRESHOLD
        assert ho_mod.STALE_THRESHOLD == 2
        assert c2.stats.get("handoff_reanalyzed", 0) == 1
        out3, c3 = self._once(hold=False)    # re-analyzed plan replays
        assert c3.stats.get("handoff_reanalyzed", 0) == 0
        assert c3.stats.get("donated_chunks", 0) > 0
        assert c3.stats.get("donation_copies", 0) == 0   # real donation, no copies
        assert c3.stats.get("planner_calls", 0) == 0     # aging ≠ replanning
        np.testing.assert_allclose(out0, out3, rtol=1e-6)

    def test_fresh_observation_ages_out_donation_copies(self):
        """The reverse direction: a donation point recorded against a dead
        producer ships per-chunk defensive copies once the producer IS
        observed — until aging re-vetoes it and the copies drop to zero."""
        plan_cache.clear()
        out0, c0 = self._once(hold=False)    # analysis: dead → donation point
        assert c0.stats.get("donated_chunks", 0) > 0
        _, c1 = self._once(hold=True)        # runtime backstop: copies
        assert c1.stats.get("donation_copies", 0) > 0
        assert c1.stats.get("handoff_reanalyzed", 0) == 0
        _, c2 = self._once(hold=True)        # stale ×2 → re-analyze → veto
        assert c2.stats.get("handoff_reanalyzed", 0) == 1
        out3, c3 = self._once(hold=True)
        assert c3.stats.get("donation_copies", 0) == 0   # copy count dropped
        assert c3.stats.get("donated_chunks", 0) == 0
        np.testing.assert_allclose(out0, out3, rtol=1e-6)

    def test_single_flap_never_reanalyzes(self):
        """One disagreeing call is noise (liveness legitimately varies);
        the age resets on the next agreeing call."""
        plan_cache.clear()
        self._once(hold=True)                # veto recorded
        _, c1 = self._once(hold=False)       # stale ×1
        assert c1.stats.get("handoff_reanalyzed", 0) == 0
        _, c2 = self._once(hold=True)        # agrees again: age resets
        assert c2.stats.get("handoff_reanalyzed", 0) == 0
        _, c3 = self._once(hold=False)       # stale ×1 again, not ×2
        assert c3.stats.get("handoff_reanalyzed", 0) == 0


# ---------------------------------------------------------------------------
# Per-context counter scoping
# ---------------------------------------------------------------------------


class TestScopedCounters:
    """Boundary traffic and trace counts attribute to the owning session's
    ``ctx.counters`` (plus the process-global aggregate): one session's
    merge round trips can never leak into another session's gate."""

    N, BATCH = 30_000, 4096

    def _once(self, handoff):
        with mozart.session(executor="fused", batch_elements=self.BATCH,
                            handoff=handoff) as ctx:
            out = np.asarray(_eval_chain(
                jnp.linspace(0., 1., self.N, dtype=jnp.float32)))
        return out, ctx

    def test_sessions_see_only_their_own_traffic(self):
        plan_cache.clear()
        self._once(True); self._once(True)   # plan + warm both configs
        self._once(False)
        g_int = stage_exec.bytes_interior()
        g_term = stage_exec.bytes_terminal()
        on_out, on_ctx = self._once(True)
        off_out, off_ctx = self._once(False)
        # Disjoint scoped views: the handoff session's gate reads zero even
        # though a merge-everything session ran in the same process.
        assert on_ctx.counters.bytes_interior() == 0
        assert on_ctx.counters.bytes_terminal() == self.N * 4
        assert off_ctx.counters.bytes_interior() >= 5 * self.N * 4
        assert off_ctx.counters.bytes_terminal() == 0
        # The process-global aggregate is exactly the sum of the scopes.
        assert (stage_exec.bytes_interior() - g_int
                == off_ctx.counters.bytes_interior())
        assert (stage_exec.bytes_terminal() - g_term
                == on_ctx.counters.bytes_terminal())
        np.testing.assert_allclose(on_out, off_out, rtol=2e-5)

    def test_scoped_event_trail_and_traces(self):
        plan_cache.clear()
        self._once(True); self._once(True)
        _, ctx = self._once(True)            # warm: zero scoped retraces
        assert ctx.counters.trace_count() == 0
        kinds = {k.split(":")[0] for k, _, _ in ctx.counters.materialize_events()}
        assert kinds == {"terminal"}         # only the observed output
        _, off_ctx = self._once(False)
        off_kinds = {k.split(":")[0]
                     for k, _, _ in off_ctx.counters.materialize_events()}
        assert off_kinds == {"interior"}

    def test_global_reset_does_not_touch_scoped_views(self):
        plan_cache.clear()
        self._once(True); self._once(True)
        _, ctx = self._once(True)
        before = ctx.counters.bytes_terminal()
        assert before == self.N * 4
        stage_exec.reset_materialized()      # resets the GLOBAL aggregate
        assert stage_exec.bytes_terminal() == 0
        assert ctx.counters.bytes_terminal() == before


# ---------------------------------------------------------------------------
# Interior vs terminal accounting
# ---------------------------------------------------------------------------


class TestByteAccounting:
    N, BATCH = 30_000, 4096

    def test_observed_terminal_output_not_interior(self):
        x = jnp.linspace(0., 1., self.N, dtype=jnp.float32)
        plan_cache.clear()

        def once():
            with mozart.session(executor="fused",
                                batch_elements=self.BATCH) as ctx:
                out = np.asarray(_eval_chain(x))
            return out, ctx

        once(); once()
        stage_exec.reset_materialized()
        once()
        assert stage_exec.bytes_interior() == 0
        assert stage_exec.bytes_terminal() == self.N * 4
        # total stays the back-compat sum
        assert stage_exec.bytes_materialized() == self.N * 4

    def test_merge_everything_is_interior(self):
        x = jnp.linspace(0., 1., self.N, dtype=jnp.float32)
        plan_cache.clear()
        with mozart.session(executor="fused", batch_elements=self.BATCH,
                            handoff=False):
            stage_exec.reset_materialized()
            np.asarray(_eval_chain(x))
            assert stage_exec.bytes_terminal() == 0
            assert stage_exec.bytes_interior() >= 5 * self.N * 4

    def test_reset_clears_counters_and_events(self):
        stage_exec.note_materialized(128, kind="merge", where="test")
        stage_exec.note_materialized(64, terminal=True, kind="materialize",
                                     where="test")
        assert stage_exec.bytes_materialized() >= 192
        assert stage_exec.materialize_events()
        stage_exec.reset_materialized()
        assert stage_exec.bytes_materialized() == 0
        assert stage_exec.bytes_interior() == 0
        assert stage_exec.bytes_terminal() == 0
        assert not stage_exec.materialize_events()

    def test_event_trail_names_the_boundary(self):
        x = jnp.linspace(0., 1., self.N, dtype=jnp.float32)
        plan_cache.clear()
        with mozart.session(executor="fused", batch_elements=self.BATCH,
                            handoff=False):
            stage_exec.reset_materialized()
            np.asarray(_eval_chain(x))
        kinds = {k.split(":")[1] for k, _, _ in stage_exec.materialize_events()}
        assert "merge" in kinds           # producer-side merges
        assert "resplit" in kinds         # consumer-side re-splits
        assert all(w for _, w, _ in stage_exec.materialize_events())


# ---------------------------------------------------------------------------
# rechunk property test (hypothesis-optional)
# ---------------------------------------------------------------------------


from repro.testing import given, settings, hst  # noqa: E402


class TestRechunkProperty:
    @given(n=hst.integers(1, 96), src_b=hst.integers(1, 96),
           dst_b=hst.integers(1, 96))
    @settings(max_examples=60, deadline=None)
    def test_any_grid_pair_at_most_one_copy(self, n, src_b, dst_b):
        """Misaligned grids (src not an integer multiple of dst or vice
        versa) still convert with at most ONE copy of the data; exactly
        aligned grids pass through by reference with zero copies."""
        t = st.ArraySplit((n,), 0)
        x = jnp.arange(n, dtype=jnp.float32)
        src, dst = _ranges(n, src_b), _ranges(n, dst_b)
        chunks = [t.split(x, s, e) for s, e in src]
        out, copied = t.rechunk(chunks, src, dst)
        assert len(out) == len(dst)
        assert copied <= int(x.nbytes)      # merge+re-split always pays two
        if src == dst:
            assert copied == 0
        multiple = (src_b % dst_b == 0 or dst_b % src_b == 0)
        if not multiple and src != dst and n > max(src_b, dst_b):
            # genuinely misaligned grids: some copying is unavoidable
            assert copied > 0
        np.testing.assert_array_equal(np.asarray(t.merge(out)), np.asarray(x))


# ---------------------------------------------------------------------------
# Persistence: ConcatSplit conversions replay; v2 files migrate
# ---------------------------------------------------------------------------

_CONCAT_PRELUDE = """
import json, sys
import jax.numpy as jnp
import numpy as np
from repro.core import mozart, plan_cache, stage_exec, splittable
from repro.core import annotated_numpy as anp
from repro.core import split_types as st

@splittable(x=st.Along(0), ret=st.Concat("rep2", 0))
def repeat2(x):
    return jnp.repeat(x, 2)

x = jnp.linspace(0.0, 1.0, 10_000, dtype=jnp.float32)

def run():
    with mozart.session(executor="fused", batch_elements=2048) as ctx:
        y = repeat2(x)
        out = np.asarray(anp.multiply(anp.add(y, 1.0), 0.5))
    return out, ctx
"""

_CONCAT_A = _CONCAT_PRELUDE + """
run(); run()
out, ctx = run()
print(json.dumps({"sum": float(out.sum()),
                  "converted": ctx.stats["stream_converted"],
                  "ingests": ctx.stats["stream_ingests"]}))
"""

_CONCAT_B = _CONCAT_PRELUDE + """
i0 = stage_exec.bytes_interior()
out, ctx = run()
recorded = [sorted(ho.convert_in)
            for e in plan_cache.entries() if e.handoff
            for ho in e.handoff.values() if ho.convert_in]
print(json.dumps({"sum": float(out.sum()),
                  "converted": ctx.stats["stream_converted"],
                  "planner_calls": ctx.stats["planner_calls"],
                  "interior": stage_exec.bytes_interior() - i0,
                  "recorded": recorded,
                  "pc": dict(plan_cache.stats)}))
"""


def test_concat_handoff_replays_from_persisted_cache(tmp_path):
    """Process A records a ConcatSplit→ArraySplit conversion in its
    persisted plans; a FRESH process B replays it — zero planner calls
    (zero analysis), conversion applied from call one, interior bytes 0."""
    path = str(tmp_path / "plans.json")
    a = _run_subprocess(_CONCAT_A, path)
    assert a["converted"] == 1 and a["ingests"] == 1
    assert os.path.exists(path)

    b = _run_subprocess(_CONCAT_B, path)
    assert b["pc"].get("persist_loaded", 0) >= 1
    assert b["planner_calls"] == 0
    assert b["converted"] == 1
    assert b["interior"] == 0
    assert b["recorded"], "convert_in not rehydrated from disk"
    assert np.isclose(a["sum"], b["sum"], rtol=1e-6)


def test_v2_plan_file_migrates_forward(tmp_path):
    """A schema-v2 cache file (pre ``convert_in``) loads under v3: handoff
    records default the new field to empty instead of rejecting the file."""
    path = str(tmp_path / "plans.json")
    plan_cache.clear()
    x = jnp.linspace(0., 1., 30_000, dtype=jnp.float32)
    with mozart.session(executor="fused", batch_elements=4096):
        np.asarray(_eval_chain(x))
    assert plan_cache.save(path) >= 1

    with open(path) as f:
        payload = json.load(f)
    assert payload["schema"] == plan_cache.SCHEMA_VERSION
    payload["schema"] = 2                 # rewrite as a v2-era file
    for e in payload["entries"]:
        if e.get("handoff"):
            for ho in e["handoff"].values():
                ho.pop("convert_in", None)
    with open(path, "w") as f:
        json.dump(payload, f)

    plan_cache.clear()
    loaded = plan_cache.load(path)
    assert loaded >= 1
    assert plan_cache.stats.get("persist_migrated_v2", 0) == 1
    for e in plan_cache.entries():
        if e.handoff:
            for ho in e.handoff.values():
                assert ho.convert_in == frozenset()

    # and the migrated plans actually replay
    with mozart.session(executor="fused", batch_elements=4096) as ctx:
        out = np.asarray(_eval_chain(x))
    assert ctx.stats["planner_calls"] == 0
    assert ctx.stats["streamed_outputs"] == 3
    want = np.asarray(x)
    for _ in range(3):
        want = (want + 1.0) * 0.5
    np.testing.assert_allclose(out, want, rtol=2e-5)


def test_v3_plan_file_migrates_forward(tmp_path):
    """A schema-v3 cache file (pre ``shard_in``/``vetoed``) loads under v4:
    handoff records default the new fields to empty — correct for every
    pre-bump plan, since the rules they gate did not exist — and the
    migrated plans replay with zero planner calls."""
    path = str(tmp_path / "plans.json")
    plan_cache.clear()
    x = jnp.linspace(0., 1., 30_000, dtype=jnp.float32)
    with mozart.session(executor="fused", batch_elements=4096):
        np.asarray(_eval_chain(x))
    assert plan_cache.save(path) >= 1

    with open(path) as f:
        payload = json.load(f)
    assert payload["schema"] == plan_cache.SCHEMA_VERSION
    payload["schema"] = 3                 # rewrite as a v3-era file
    for e in payload["entries"]:
        if e.get("handoff"):
            for ho in e["handoff"].values():
                ho.pop("shard_in", None)
                ho.pop("vetoed", None)
    with open(path, "w") as f:
        json.dump(payload, f)

    plan_cache.clear()
    before = plan_cache.stats.get("persist_migrated_v3", 0)
    loaded = plan_cache.load(path)
    assert loaded >= 1
    assert plan_cache.stats.get("persist_migrated_v3", 0) == before + 1
    for e in plan_cache.entries():
        if e.handoff:
            for ho in e.handoff.values():
                assert ho.shard_in == frozenset()
                assert ho.vetoed == frozenset()

    # and the migrated plans actually replay
    with mozart.session(executor="fused", batch_elements=4096) as ctx:
        out = np.asarray(_eval_chain(x))
    assert ctx.stats["planner_calls"] == 0
    assert ctx.stats["streamed_outputs"] == 3
    want = np.asarray(x)
    for _ in range(3):
        want = (want + 1.0) * 0.5
    np.testing.assert_allclose(out, want, rtol=2e-5)


def test_unsupported_schema_still_rejected(tmp_path):
    path = str(tmp_path / "plans.json")
    plan_cache.clear()
    x = jnp.linspace(0., 1., 10_000, dtype=jnp.float32)
    with mozart.session(executor="fused", batch_elements=4096):
        np.asarray(_eval_chain(x, evals=1))
    assert plan_cache.save(path) >= 1
    with open(path) as f:
        payload = json.load(f)
    payload["schema"] = 1                 # pre-handoff layouts never migrate
    with open(path, "w") as f:
        json.dump(payload, f)
    plan_cache.clear()
    before = plan_cache.stats.get("persist_rejected_schema", 0)
    assert plan_cache.load(path) == 0
    assert plan_cache.stats.get("persist_rejected_schema", 0) == before + 1
