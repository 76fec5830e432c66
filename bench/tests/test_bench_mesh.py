"""A cell on a mesh of its chips: Black–Scholes under the ``sharded``
executor on four virtual CPU devices, built here as a ``cells.Cell`` (it is
no cell of ``BENCHMARK.json``) and driven through ``run_cell`` with the
chip check skipped.  The forced device count must not leak into other
tests, so the runs are made in one subprocess and their results read
here."""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from bench import cells

TESTS = Path(__file__).resolve().parent

BODY = """
    import json, sys
    from pathlib import Path
    sys.path[:0] = [{root!r}, {src!r}, {tests!r}]
    import jax
    from bench import run as bench_run, spans
    from test_bench_correct import SEED, half_left_out, tiny

    # traces go to this test's own directory
    bench_run.OUT = Path({out!r})
    spans.TRACES = bench_run.OUT / "trace"

    def cell():
        c = tiny("black-scholes.bulk")
        c.name, c.chips = "black-scholes.mesh-test", 4
        c.traffic["executor"] = "sharded"
        return c

    fed = []

    def watched(call):
        def inner(b):
            fed.append([[len(x.addressable_shards),
                         len({{s.device for s in x.addressable_shards}}),
                         max(s.data.size for s in x.addressable_shards),
                         x.size] for x in jax.tree.leaves(b)])
            return call(b)
        return inner

    lines = []
    out = {{"sound": bench_run.run_cell(
        cell(), SEED, 0.0, trace=True, calls=6, require_tpu=False,
        wrap_call=watched, log=lambda *a: lines.append(" ".join(map(str, a))))}}
    out["fed"] = fed
    out["log"] = lines
    for name, kw in (("control", {{"control": True}}),
                     ("half_left_out", {{"wrap_call": half_left_out}})):
        out[name] = bench_run.run_cell(cell(), SEED, 0.0, calls=6,
                                       require_tpu=False,
                                       log=lambda *_: None, **kw)
    print(json.dumps(out, default=str))
"""


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = textwrap.dedent(BODY.format(
        root=str(cells.ROOT), src=str(cells.ROOT / "src"), tests=str(TESTS),
        out=str(tmp_path_factory.mktemp("out"))))
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_sound_run_is_correct_on_the_mesh(runs):
    res = runs["sound"]
    assert res["correct"], res["check"]
    assert res["failed"] == 0
    assert res["device"]["count"] == 4
    pipeline = next(x for x in runs["log"] if x.startswith("[bench] Pipeline"))
    assert "'sharded'" in pipeline and "(0, 1, 2, 3)" in pipeline
    # the traced window is read on four planes, and the program's spans
    # are found again from the reading
    trace = next(x for x in runs["log"] if x.startswith("[bench] trace:"))
    assert "busy per chip [0.0, 0.0, 0.0, 0.0]" in trace
    assert "capture_ms" in res["metrics"]


def test_every_batch_is_split_over_the_four_chips(runs):
    assert len(runs["fed"]) > 7     # a warm-up call, six timed, the traced
    for leaves in runs["fed"]:
        assert len(leaves) == 5
        for shards, devices, largest, size in leaves:
            assert shards == devices == 4
            assert largest == size // 4 < size


@pytest.mark.parametrize("run", ["control", "half_left_out"])
def test_control_and_fault_are_not_correct_on_the_mesh(runs, run):
    assert not runs[run]["correct"], runs[run]["check"]
