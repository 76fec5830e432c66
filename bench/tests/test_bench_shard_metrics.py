"""The four-chip cell ``black-scholes.sharded-4chip`` and its per-layer
metrics: each new reader on hand-made readings, and the cell's own
files run through ``run_cell`` at a size the CPU holds, on four virtual
CPU devices in a subprocess (the forced device count must not leak into
other tests)."""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from bench import cells, spans
from bench.reduction import Reading

CELL = "black-scholes.sharded-4chip"
TESTS = Path(__file__).resolve().parent


def reading(**kw):
    fields = dict(window_s=1.0, busy_s=0.8, calls=10, idle_in_calls_s=0.0,
                  op_totals=[], gaps=[])
    fields.update(kw)
    return Reading(**fields)


def test_the_cell_is_black_scholes_at_2p29_on_four_chips():
    cell = cells.load_cell(CELL)
    assert cell.chips == 4
    assert cell.config["name"] == "black-scholes-f32-4chip"
    assert cell.traffic["executor"] == "sharded"
    assert cell.traffic["elements_per_call"] == 2**29
    assert cell.config["options_per_call"] == cell.traffic["elements_per_call"]
    assert cell.config["chips"] == cell.chips
    assert [m["name"] for m in cell.per_layer][-2:] == [
        "chip_busy_spread", "shard_place_ms"]


def test_the_deployment_runs_the_one_chip_user_code_and_reference():
    """The four-chip configuration brings its own copies of the Black
    Scholes user code and plain reference; they are the one-chip ones."""
    configs = TESTS.parent / "configs"
    for kind in ("workload", "reference"):
        assert (configs / f"black-scholes-f32-4chip.{kind}.py").read_text() \
            == (configs / f"black-scholes-f32.{kind}.py").read_text()


def test_chip_busy_spread_is_the_straggler_over_the_mean():
    read = cells.metric_reader("chip_busy_spread")
    assert read(reading(chips=4, chip_busy_s=(0.9, 1.0, 1.1, 1.0))) == \
        pytest.approx(20.0)
    assert read(reading(chips=1, chip_busy_s=(1.0,))) is None
    assert read(reading(chips=2, chip_busy_s=(0.0, 1.0))) is None


def test_shard_place_ms_is_the_layout_span_self_time_per_call(monkeypatch):
    read = cells.metric_reader("shard_place_ms")
    ms = 1_000_000
    t = spans.totals([("mozart.stage", 0, 10 * ms, {}),
                      ("mozart.layout", 1 * ms, 5 * ms, {}),
                      ("mozart.drive", 5 * ms, 9 * ms, {})], [])
    monkeypatch.setattr(spans, "of_reading", lambda _r: t)
    assert read(reading(calls=2)) == pytest.approx(2.0)
    monkeypatch.setattr(spans, "of_reading", lambda _r: None)
    assert read(reading(calls=2)) is None


BODY = """
    import json, sys
    from pathlib import Path
    sys.path[:0] = [{root!r}, {src!r}, {tests!r}]
    from bench import run as bench_run, spans
    from test_bench_correct import SEED, tiny

    bench_run.OUT = Path({out!r})
    spans.TRACES = bench_run.OUT / "trace"
    out = {{}}
    for name, kw in (("sound", {{"trace": True}}),
                     ("control", {{"control": True}})):
        out[name] = bench_run.run_cell(tiny({cell!r}), SEED, 0.0, calls=6,
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
        out=str(tmp_path_factory.mktemp("out")), cell=CELL))
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_the_cell_runs_correct_and_reads_its_layout_span(runs):
    res = runs["sound"]
    assert res["correct"], res["check"]
    assert res["failed"] == 0 and res["device"]["count"] == 4
    # no device plane on the CPU: only the host-clock, span and counter
    # metrics have a number
    assert set(res["metrics"]) == {"call_p50_ms", "capture_ms", "plan_ms",
                                   "layout_bytes_share", "shard_place_ms"}
    assert res["metrics"]["shard_place_ms"]["value"] > 0


def test_the_cell_reads_the_accepted_entry_and_layout_metrics(runs):
    """The accepted per-layer metrics read the four-chip call's entry spans
    and its stage's ``layout_bytes`` counter: the batches are made on the
    mesh already, so placing them copies nothing."""
    got = runs["sound"]["metrics"]
    assert got["capture_ms"]["value"] > 0 and got["plan_ms"]["value"] > 0
    assert got["call_p50_ms"]["value"] > 0
    assert got["layout_bytes_share"]["value"] == 0.0


def test_the_cell_control_is_not_correct(runs):
    assert not runs["control"]["correct"], runs["control"]["check"]
