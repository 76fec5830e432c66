"""Sharded (mesh) executor tests — run in a subprocess so the forced
device count never leaks into other tests."""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(body: str, n_devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    code = textwrap.dedent(body)
    proc = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True,
        timeout=600,
    )
    assert proc.returncode == 0, f"stdout={proc.stdout}\nstderr={proc.stderr}"
    return proc.stdout


@pytest.mark.parametrize("batch", [1500, 3072], ids=["cols", "rows"])
def test_sharded_matches_the_benchmark_references(batch):
    """Differential: Black–Scholes and data cleaning (the benchmark's user
    code) through ``executor="sharded"`` on a 4-device mesh, 2^12 elements
    a shard, against the benchmark's plain references to their limits.
    Each shard runs several chunks and a ragged tail (1500: sliced along
    the axis; 3072: sliced as rows of 128 lanes); data cleaning's two sums
    fold in the loop's carry and then add up across shards."""
    out = run_with_devices(f"""
        import warnings; warnings.filterwarnings("ignore")
        import sys; sys.path.insert(0, {REPO!r})
        from pathlib import Path
        import jax
        from bench.cells import load_module
        from repro.core import mozart

        configs = Path({REPO!r}) / "bench" / "configs"
        mesh = jax.make_mesh((4,), ("data",))
        n, batch = 1 << 14, {batch}
        limits = {{"black-scholes-f32": ("price_abs_err", 1e-3),
                   "data-cleaning-f32": ("sum_rel_err", 3e-5)}}
        for name, (reading, limit) in limits.items():
            user = load_module(configs / f"{{name}}.workload.py")
            ref = load_module(configs / f"{{name}}.reference.py")
            args = ref.make_batch(jax.random.key(7), n)
            p = mozart.pipeline(user.workload, executor="sharded", mesh=mesh,
                                batch_elements=batch)
            outs, delta = p.call_with_stats(**args)
            got = ref.compare(outs, ref.reference(**args))[reading]
            assert got <= limit, (name, got)
            chunks = -(-(n // 4) // batch)
            assert delta["sharded_stages"] == 1, dict(delta)
            assert delta["chunks"] == 4 * chunks, dict(delta)
        print("OK")
    """, n_devices=4)
    assert "OK" in out


def test_only_one_device_executors_gather_a_mesh_input():
    """A user input laid out over an Explicit mesh axis: ``fused`` runs on
    it as laid out, with no gather; ``scan`` and ``pallas``, whose programs
    refuse the layout, gather it onto the first device of its own mesh,
    counted once as ``interior:gather`` of its bytes."""
    out = run_with_devices("""
        import warnings; warnings.filterwarnings("ignore")
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
        from repro.core import mozart, plan_cache, stage_exec
        from repro.core import annotated_numpy as anp

        mesh = jax.make_mesh((2,), ("data",), (AxisType.Explicit,),
                             devices=jax.devices()[2:])
        n = 4096
        x = jax.device_put(jnp.linspace(0.0, 1.0, n, dtype=jnp.float32),
                           NamedSharding(mesh, P("data")))
        want = (np.linspace(0.0, 1.0, n, dtype=np.float32) + 1.0) * 0.5
        put = []
        real_put = jax.device_put
        def spy(v, device=None, *a, **k):
            put.append(device)
            return real_put(v, device, *a, **k)
        stage_exec.jax.device_put = spy
        for executor, gathers in (("fused", 0), ("scan", 1), ("pallas", 1)):
            plan_cache.clear()
            put.clear()
            with mozart.session(executor=executor,
                                batch_elements=1024) as ctx:
                got = np.asarray(anp.multiply(anp.add(x, 1.0), 0.5))
            assert np.allclose(got, want, rtol=1e-6), executor
            events = [e for e in ctx.counters.materialize_events()
                      if e[0] == "interior:gather"]
            assert len(events) == gathers, (executor, events)
            assert all(e[2] == 4 * n for e in events), events
            assert len(put) == gathers, (executor, put)
            assert all(d.device_set == {jax.devices()[2]} for d in put), put
        print("OK")
    """, n_devices=4)
    assert "OK" in out


@pytest.mark.slow
def test_sharded_elementwise_and_reduce():
    out = run_with_devices("""
        import warnings; warnings.filterwarnings("ignore")
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import mozart
        from repro.core import annotated_numpy as anp

        mesh = jax.make_mesh((8,), ("data",))
        x = jnp.arange(4096.0, dtype=jnp.float32)
        with mozart.session(executor="sharded", mesh=mesh, batch_elements=64) as ctx:
            b = anp.multiply(anp.log1p(x), 3.0)
            s = anp.sum(b)
            got = np.asarray(b); sgot = float(s)
        want = np.log1p(np.arange(4096.0)) * 3
        assert np.allclose(got, want, rtol=1e-5)
        assert np.isclose(sgot, want.sum(), rtol=1e-5), (sgot, want.sum())
        assert ctx.stats["sharded_stages"] == 1
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_sharded_output_sharding_matches_split_axis():
    out = run_with_devices("""
        import warnings; warnings.filterwarnings("ignore")
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import mozart
        from repro.core import annotated_numpy as anp

        mesh = jax.make_mesh((4,), ("data",))
        m = jnp.asarray(np.random.RandomState(0).randn(64, 8), jnp.float32)
        v = jnp.ones(8, jnp.float32)
        with mozart.session(executor="sharded", mesh=mesh) as ctx:
            y = anp.matvec(m, v)     # Along(0): rows sharded, v broadcast
            z = anp.exp(y)
            res = z.value
        shard_shapes = {s.data.shape for s in res.addressable_shards}
        assert shard_shapes == {(16,)}, shard_shapes
        assert np.allclose(np.asarray(res), np.exp(np.asarray(m) @ np.ones(8)), rtol=1e-4)
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_sharded_multipod_axes():
    """Splits spread over BOTH the pod and data axes (multi-pod DP)."""
    out = run_with_devices("""
        import warnings; warnings.filterwarnings("ignore")
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import mozart
        from repro.core import annotated_numpy as anp

        mesh = jax.make_mesh((2, 4), ("pod", "data"))
        x = jnp.arange(1024.0, dtype=jnp.float32) / 128.0
        with mozart.session(executor="sharded", mesh=mesh,
                            data_axes=("pod", "data")) as ctx:
            y = anp.add(anp.exp(x), 1.0)
            s = anp.sum(y)
            got = np.asarray(y); sg = float(s)
        want = np.exp(np.arange(1024.0) / 128.0) + 1
        assert np.allclose(got, want, rtol=1e-5)
        assert np.isclose(sg, want.sum(), rtol=1e-6)
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_sharded_stream_to_each_consumer():
    """Differential: a sharded producer's device-resident stream crosses
    into EVERY consumer executor (equal and misaligned consumer grids),
    handoff on vs off — values identical to numpy either way.  The
    sharded→sharded edge must move zero interior bytes and never
    all-gather; non-shard-capable consumers gather honestly (counted)."""
    out = run_with_devices("""
        import warnings; warnings.filterwarnings("ignore")
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import mozart, plan_cache
        from repro.core import annotated_numpy as anp

        mesh = jax.make_mesh((2,), ("data",))
        n = 4096
        x = jnp.linspace(0.0, 1.0, n, dtype=jnp.float32)
        want = np.linspace(0.0, 1.0, n, dtype=np.float32)
        for _ in range(2):
            want = (want + 1.0) * 0.5

        for consumer in ("fused", "scan", "pallas", "sharded"):
            # 2048 matches the 2-shard grid exactly; 1000 leaves an
            # odd-length 96-element tail chunk in the consumer's grid.
            for batch in (2048, 1000):
                if consumer == "sharded" and batch != 2048:
                    continue        # sharded grids come from the mesh
                for handoff in (True, False):
                    plan_cache.clear()
                    with mozart.session(executor="sharded", mesh=mesh,
                                        batch_elements=2048,
                                        handoff=handoff) as ctx:
                        cur = anp.multiply(anp.add(x, 1.0), 0.5)
                        mozart.evaluate()       # sharded producer stage
                        mozart.configure(executor=consumer,
                                         batch_elements=batch)
                        cur = anp.multiply(anp.add(cur, 1.0), 0.5)
                        got = np.asarray(cur)
                    tag = (consumer, batch, handoff)
                    assert np.allclose(got, want, rtol=2e-5), tag
                    if handoff and consumer == "sharded":
                        # zero interior bytes, and no all-gather anywhere
                        # in the scoped event trail
                        assert ctx.counters.bytes_interior() == 0, tag
                        gathers = [e for e in
                                   ctx.counters.materialize_events()
                                   if e[0].startswith("interior:gather")]
                        assert not gathers, (tag, gathers)
                        assert ctx.stats.get("shard_passthrough", 0) >= 1
                    if handoff and consumer != "sharded":
                        # honest fallback: the gather is counted, not hidden
                        assert ctx.stats.get("stream_materialized", 0) >= 1
        print("OK")
    """, n_devices=2)
    assert "OK" in out


@pytest.mark.slow
def test_sharded_chunk_list_ingest_grids():
    """Chunk-list → sharded ingest: a grid equal to the shard layout is
    device_put per shard with zero rechunks; a misaligned grid converts
    through ``rechunk`` exactly once (at most one copy, not merge+re-split
    two)."""
    out = run_with_devices("""
        import warnings; warnings.filterwarnings("ignore")
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import mozart, plan_cache
        from repro.core import annotated_numpy as anp

        mesh = jax.make_mesh((2,), ("data",))
        n = 4096
        x = jnp.linspace(0.0, 1.0, n, dtype=jnp.float32)
        want = np.linspace(0.0, 1.0, n, dtype=np.float32)
        for _ in range(2):
            want = (want + 1.0) * 0.5

        for batch, rechunks in ((2048, 0), (1000, 1)):
            plan_cache.clear()
            with mozart.session(executor="fused", mesh=mesh,
                                batch_elements=batch) as ctx:
                cur = anp.multiply(anp.add(x, 1.0), 0.5)
                mozart.evaluate()           # fused producer: chunk list
                mozart.configure(executor="sharded")
                cur = anp.multiply(anp.add(cur, 1.0), 0.5)
                got = np.asarray(cur)
            assert np.allclose(got, want, rtol=2e-5), batch
            assert ctx.stats.get("shard_ingests", 0) == 1, dict(ctx.stats)
            assert ctx.stats.get("handoff_rechunks", 0) == rechunks, \\
                dict(ctx.stats)
            assert ctx.stats.get("stream_materialized", 0) == 0, \\
                dict(ctx.stats)
        print("OK")
    """, n_devices=2)
    assert "OK" in out


@pytest.mark.slow
def test_sharded_stream_empty_inputs():
    """n == 0 through the new sharded stream paths, both directions — the
    degenerate zero-length grid must survive ingest and egress fallbacks."""
    out = run_with_devices("""
        import warnings; warnings.filterwarnings("ignore")
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import mozart, plan_cache
        from repro.core import annotated_numpy as anp

        mesh = jax.make_mesh((2,), ("data",))
        z = jnp.zeros((0,), jnp.float32)
        for first, second in (("sharded", "fused"), ("fused", "sharded")):
            plan_cache.clear()
            kw = {"mesh": mesh, "batch_elements": 64}
            with mozart.session(executor=first, **kw) as ctx:
                cur = anp.add(z, 1.0)
                mozart.evaluate()
                mozart.configure(executor=second)
                got = np.asarray(anp.multiply(cur, 2.0))
            assert got.shape == (0,), (first, second, got.shape)
        print("OK")
    """, n_devices=2)
    assert "OK" in out


@pytest.mark.slow
def test_elastic_checkpoint_restore_across_meshes(tmp_path):
    """Elastic restart: save on a 1-device layout, restore sharded onto a
    4-device mesh (different topology) — values identical."""
    out = run_with_devices(f"""
        import warnings; warnings.filterwarnings("ignore")
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.ckpt import checkpoint as ckpt

        tree = {{"w": jnp.arange(64.0).reshape(8, 8),
                "b": jnp.arange(8.0)}}
        ckpt.save(r"{str(tmp_path)}", 3, tree)

        mesh = jax.make_mesh((4,), ("data",))
        sh = {{"w": NamedSharding(mesh, P("data", None)),
              "b": NamedSharding(mesh, P())}}
        avals = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
        r = ckpt.restore(r"{str(tmp_path)}", 3, avals, sh)
        assert len(r["w"].addressable_shards) == 4
        assert r["w"].addressable_shards[0].data.shape == (2, 8)
        np.testing.assert_array_equal(np.asarray(r["w"]), np.asarray(tree["w"]))
        print("OK")
    """, n_devices=4)
    assert "OK" in out
