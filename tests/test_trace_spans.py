"""Spans and the ``layout_bytes`` counter of Mozart's call path
(``repro.core.trace``), read back from a real CPU profile.

Each warm call of a one-stage pipeline opens ``mozart.call`` with its
entry spans, and each stage ``mozart.stage`` with its children, nested as
``repro.core.trace`` documents; ``mozart.drive`` carries the call's chunk
count and ``mozart.stage`` the executor that ran and the stage's
``layout_bytes``; a sharded stage's ``mozart.drive`` also carries its
shards and tile.  ``layout_bytes`` is checked against a formula from
shapes.
"""

import collections
import dataclasses
import glob
import inspect
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import hardware
from repro.core import mozart, pallas_exec, trace
from repro.core import annotated_numpy as anp
from repro.core import split_types as st
from repro.core.runtime import MozartContext
from repro.core.stage_exec import ChunkStream, batch_ranges
from repro.kernels import split_pipeline as sp

SRC = Path(__file__).resolve().parents[1] / "src" / "repro"

#: the span each span may open inside (None: outermost).
PARENTS = {
    "mozart.call": {None},
    "mozart.capture": {"mozart.call"},
    "mozart.plan": {"mozart.call", "mozart.evaluate"},
    "mozart.rewrite": {"mozart.plan"},
    "mozart.fingerprint": {"mozart.plan"},
    "mozart.evaluate": {"mozart.call"},
    "mozart.stage": {"mozart.evaluate", "mozart.call"},
    "mozart.inputs": {"mozart.stage"},
    "mozart.layout": {"mozart.stage"},
    "mozart.drive": {"mozart.stage"},
    "mozart.merge": {"mozart.stage"},
    "mozart.force": {"mozart.call"},
}


def chain(x, y):
    a = anp.add(x, y)
    b = anp.multiply(a, 2.0)
    return anp.exp(b)


def _data(n):
    x = jnp.arange(n, dtype=jnp.float32) / n
    return x, jnp.ones(n, jnp.float32) * 0.25


def _profiled_calls(p, args, calls, tmp_path):
    """Run ``calls`` warm calls under the profiler: (spans, stats deltas)."""
    deltas = []
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(calls):
            out, delta = p.call_with_stats(*args)
            jax.block_until_ready(out)
            deltas.append(delta)
    finally:
        jax.profiler.stop_trace()
    path = max(glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb")))
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("mozart.")]
    return sorted(spans, key=lambda s: (s[1], -s[2])), deltas


def _with_parents(spans):
    """``[(span, parent name or None)]`` for properly nested spans."""
    out, stack = [], []
    for sp_ in spans:
        while stack and stack[-1][2] <= sp_[1]:
            stack.pop()
        out.append((sp_, stack[-1][0] if stack else None))
        stack.append(sp_)
    return out


def _per_call(spans):
    """The spans of each ``mozart.call``, in call order."""
    calls = [s for s in spans if s[0] == "mozart.call"]
    return [(c, [s for s in spans if c[1] <= s[1] and s[2] <= c[2]])
            for c in calls]


@pytest.mark.parametrize("executor", ["fused", "scan", "pallas"])
def test_warm_calls_open_the_documented_spans(executor, tmp_path):
    n = 5000                                     # ragged against 1024
    args = _data(n)
    p = mozart.pipeline(chain, executor=executor, batch_elements=1024)
    p.lower(*args).compile()
    spans, deltas = _profiled_calls(p, args, 3, tmp_path)
    per_call = _per_call(spans)
    assert len(per_call) == 3
    for (call, inside), delta in zip(per_call, deltas):
        assert call[3] == {"fast": 0}
        assert len(inside) <= 12                 # 7 entry + 5 for one stage
        for (name, _, _, _), parent in _with_parents(inside):
            assert parent in PARENTS[name], (name, parent)
        names = collections.Counter(s[0] for s in inside)
        stage_spans = {"mozart.stage", "mozart.inputs", "mozart.drive",
                       "mozart.merge"}
        if executor != "fused":
            stage_spans.add("mozart.layout")
        assert set(names) == set(PARENTS) - {"mozart.layout"} | stage_spans
        assert all(v == 1 for v in names.values())
        by_name = {s[0]: s[3] for s in inside}
        assert by_name["mozart.stage"] == {
            "stage": 0, "executor": executor,
            "layout_bytes": delta.get("layout_bytes", 0)}
        assert by_name["mozart.drive"]["chunks"] == delta["chunks"]
        assert by_name["mozart.plan"] == {"hit": 1}
        assert by_name["mozart.evaluate"] == {"stages": 1}
        assert by_name["mozart.capture"] == {"nodes": 3}
    assert all(d["chunks"] == 5 for d in deltas)  # ceil(5000 / 1024)


#: a chip whose fast memory takes 3276 elements of ``chain``'s 20 bytes, so
#: a stage of 20000 runs 7 chunks of 3072, the estimate rounded to a tile.
SMALL_VMEM = dataclasses.replace(hardware.TPU_V5E, vmem_bytes=64 * 1024,
                                 mozart_c=1.0)


def test_sharded_stage_opens_layout_drive_and_merge(tmp_path):
    """On a one-device mesh, each warm call's sharded stage opens
    ``mozart.layout``, ``mozart.drive`` and ``mozart.merge`` inside
    ``mozart.stage``; ``mozart.drive`` carries the chunks of a shard, the
    shards and the layout tile its batch was rounded to."""
    n = 20000
    args = _data(n)
    p = mozart.pipeline(chain, executor="sharded", chip=SMALL_VMEM,
                        mesh=jax.make_mesh((1,), ("data",)), autotune=False)
    p.lower(*args).compile()
    spans, deltas = _profiled_calls(p, args, 2, tmp_path)
    per_call = _per_call(spans)
    assert len(per_call) == 2
    for (_call, inside), delta in zip(per_call, deltas):
        for (name, _, _, _), parent in _with_parents(inside):
            assert parent in PARENTS[name], (name, parent)
        names = collections.Counter(s[0] for s in inside)
        assert all(names[s] == 1 for s in ("mozart.stage", "mozart.layout",
                                           "mozart.drive", "mozart.merge"))
        by_name = {s[0]: s[3] for s in inside}
        assert by_name["mozart.stage"] == {"stage": 0, "executor": "sharded",
                                           "layout_bytes": 0}
        assert by_name["mozart.drive"] == {"chunks": 7, "shards": 1,
                                           "tile": 1024}
        assert delta["chunks"] == 7 and delta["sharded_stages"] == 1
    out, _ = p.call_with_stats(*args)
    np.testing.assert_allclose(np.asarray(out),
                               np.exp(2 * (np.asarray(args[0]) + 0.25)),
                               rtol=1e-6)


def test_sharded_ingest_counts_its_rechunk_as_layout_bytes():
    """A chunk list from a fused stage, on a grid of 1000 against the
    mesh's one shard of 4096, is regrouped onto the shard grid: one copy of
    the whole value, counted in ``layout_bytes``."""
    n = 4096
    x = jnp.linspace(0.0, 1.0, n, dtype=jnp.float32)
    with mozart.session(executor="fused", mesh=jax.make_mesh((1,), ("data",)),
                        batch_elements=1000) as ctx:
        cur = anp.multiply(anp.add(x, 1.0), 0.5)
        mozart.evaluate()                   # fused producer: 5 chunks
        assert ctx.stats.get("layout_bytes", 0) == 0
        mozart.configure(executor="sharded")
        got = np.asarray(anp.multiply(anp.add(cur, 1.0), 0.5))
    np.testing.assert_allclose(got, (np.asarray(x) + 3.0) / 4.0, rtol=1e-6)
    assert ctx.stats["handoff_rechunks"] == 1
    assert ctx.stats["shard_ingests"] == 1
    assert ctx.stats["layout_bytes"] == n * 4


def test_fast_path_call_opens_no_capture_or_plan(tmp_path):
    args = _data(4096)
    p = mozart.pipeline(chain, executor="fused", batch_elements=1024,
                        arg_transparent=True)
    p.lower(*args).compile()
    p(*args)                                      # builds the replay
    spans, deltas = _profiled_calls(p, args, 2, tmp_path)
    for (call, inside), delta in zip(_per_call(spans), deltas):
        assert delta["fast_path_calls"] == 1
        assert call[3] == {"fast": 1}
        parents = dict((s[0], parent) for s, parent in _with_parents(inside))
        assert set(parents) == {"mozart.call", "mozart.stage", "mozart.inputs",
                                "mozart.drive", "mozart.merge", "mozart.force"}
        assert parents["mozart.stage"] == "mozart.call"


@pytest.mark.parametrize("n", [5000, 4096], ids=["ragged", "even"])
def test_scan_layout_bytes_from_shapes(n):
    """Scan reads its 2 plain inputs in place and writes its output whole
    inside the driver (the ragged tail too): no layout copy at all."""
    args = _data(n)
    p = mozart.pipeline(chain, executor="scan", batch_elements=1024,
                        handoff=False)
    p.lower(*args).compile()
    out, delta = p.call_with_stats(*args)
    assert delta.get("layout_bytes", 0) == 0
    assert delta["chunks"] == len(batch_ranges(n, 1024))
    np.testing.assert_allclose(np.asarray(out),
                               np.exp(2 * (np.asarray(args[0]) + 0.25)),
                               rtol=1e-6)


def test_fused_issues_no_layout_copies():
    args = _data(5000)
    p = mozart.pipeline(chain, executor="fused", batch_elements=1024)
    p.lower(*args).compile()
    _, delta = p.call_with_stats(*args)
    assert delta.get("layout_bytes", 0) == 0


def test_pallas_layout_bytes_from_shapes():
    """Pallas pads each input to whole blocks and views it as rows of 128
    lanes (two copies of 5120 elements); the output is reshaped back and
    sliced to 5000."""
    args = _data(5000)
    p = mozart.pipeline(chain, executor="pallas", batch_elements=1024)
    p.lower(*args).compile()
    out, delta = p.call_with_stats(*args)
    assert delta["pallas_stages"] == 1
    assert delta["layout_bytes"] == 4 * (2 * 2 * 5120 + 5120 + 5000)
    np.testing.assert_allclose(np.asarray(out),
                               np.exp(2 * (np.asarray(args[0]) + 0.25)),
                               rtol=1e-6)


class _Ctx:
    def __init__(self):
        self.stats = collections.Counter()


def test_launch_layout_of_a_whole_array():
    x = jnp.arange(5000, dtype=jnp.float32)
    ctx = _Ctx()
    buf, fresh = pallas_exec._to_launch_layout(x, 5000, 1024, None, ("in", 0),
                                               ctx)
    assert fresh and buf.shape == (40, 128)
    np.testing.assert_array_equal(np.asarray(buf),
                                  np.asarray(sp.pad_to_layout(x, 5000, 1024)))
    assert ctx.stats["layout_bytes"] == 2 * 5120 * 4      # pad, reshape


def test_launch_layout_of_a_chunk_stream():
    x = jnp.arange(5000, dtype=jnp.float32)
    ranges = batch_ranges(5000, 1024)
    stream = ChunkStream([x[s:e] for s, e in ranges], ranges,
                         st.ArraySplit((5000,), 0),
                         jax.ShapeDtypeStruct((5000,), jnp.float32))
    ctx = _Ctx()
    buf, fresh = pallas_exec._to_launch_layout(stream, 5000, 1024, None,
                                               ("in", 0), ctx)
    assert fresh
    np.testing.assert_array_equal(np.asarray(buf),
                                  np.asarray(sp.pad_to_layout(x, 5000, 1024)))
    # stack + reshape of 4 full chunks, pad + reshape of the tail, concat
    assert ctx.stats["layout_bytes"] == 4 * (2 * 4096 + 2 * 1024 + 5120)
    assert ctx.stats["handoff_rechunks"] == 0


def test_every_span_opened_in_the_source_is_declared():
    opened = set()
    for path in SRC.rglob("*.py"):
        opened |= set(re.findall(r'span\("(mozart\.[a-z]+)"', path.read_text()))
    assert opened == set(trace.SPANS)


def test_profiler_api_only_through_the_trace_module():
    users = sorted(p.relative_to(SRC).as_posix() for p in SRC.rglob("*.py")
                   if "jax.profiler" in p.read_text())
    assert users == ["core/trace.py"]


def test_log_option_is_gone():
    assert "log" not in inspect.signature(MozartContext).parameters
    with pytest.raises(TypeError):
        mozart.pipeline(chain, log=True)
