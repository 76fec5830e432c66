"""The program's ``mozart.*`` spans in a traced window: self times, the
split of the idle time inside ``bench.call`` by the innermost open span,
the attributes' sums, the per-layer metrics that read them, and how a
reader finds the trace its reading came from.  Hand-made events, times in
ns:

    host:   call [0,1000]  wait [1000,1200]  call [1300,2000]  wait [2000,2100]
    device: [500,700]  [1000,1150]  [1400,1500]

    call 1: mozart.call [10,990]
              mozart.capture [20,200]
              mozart.evaluate [210,900]
                mozart.plan [220,400]
                  mozart.rewrite [230,300]  mozart.fingerprint [310,380]
                mozart.stage [420,880]
                  inputs [430,450]  layout [460,600]  drive [610,800]
                  merge [810,870]
              mozart.force [920,980]
    call 2: mozart.call [1310,1990]
              mozart.force [1900,1950]
"""

import glob
from pathlib import Path

import jax
import pytest
from jax.profiler import ProfileData

from bench import reduction, spans
from bench.cells import metric_reader

FIXTURES = Path(__file__).parent / "fixtures"
OLD = ("host_idle_ms", "idle_share", "device_roofline", "call_mfu",
       "call_p50_ms")
NEW = ("capture_ms", "plan_ms", "entry_idle_ms", "stage_idle_ms",
       "layout_bytes_share")

PROGRAM = [
    ("mozart.call", 10, 990, {"fast": 0}),
    ("mozart.capture", 20, 200, {"nodes": 32}),
    ("mozart.evaluate", 210, 900, {"stages": 1}),
    ("mozart.plan", 220, 400, {"hit": 1}),
    ("mozart.rewrite", 230, 300, {}),
    ("mozart.fingerprint", 310, 380, {}),
    ("mozart.stage", 420, 880, {"stage": 0, "executor": "scan",
                                "layout_bytes": 3000}),
    ("mozart.inputs", 430, 450, {}),
    ("mozart.layout", 460, 600, {}),
    ("mozart.drive", 610, 800, {"chunks": 4}),
    ("mozart.merge", 810, 870, {}),
    ("mozart.force", 920, 980, {}),
    ("mozart.call", 1310, 1990, {"fast": 1}),
    ("mozart.force", 1900, 1950, {}),
]


def events():
    return reduction.Events(
        ops=[("fusion.1", 500, 700), ("copy.2", 1000, 1150),
             ("fusion.1", 1400, 1500)],
        spans=[("bench.call", 0, 1000), ("bench.wait", 1000, 1200),
               ("bench.call", 1300, 2000), ("bench.wait", 2000, 2100)])


@pytest.fixture(autouse=True)
def no_traces(tmp_path, monkeypatch):
    """Readers look for traces in an empty directory unless a test writes
    some there, and remember no earlier reading."""
    monkeypatch.setattr(spans, "TRACES", tmp_path / "trace")
    monkeypatch.setattr(spans, "_last", (None, None))


def reduced(ev, program):
    """The window's reading, with ``program`` as the spans its trace holds."""
    r = reduction.reduce(ev)
    spans._last = (r, spans.in_window(ev, program))
    return r


@pytest.fixture
def reading():
    return reduced(events(), PROGRAM)


def test_self_times(reading):
    by = spans.of_reading(reading).by_name
    ns = {n: round(t.self_s * 1e9) for n, t in by.items()}
    assert ns == {
        "mozart.call": 50 + 630, "mozart.capture": 180,
        "mozart.evaluate": 690 - 180 - 460, "mozart.plan": 180 - 70 - 70,
        "mozart.rewrite": 70, "mozart.fingerprint": 70,
        "mozart.stage": 460 - 20 - 140 - 190 - 60, "mozart.inputs": 20,
        "mozart.layout": 140, "mozart.drive": 190, "mozart.merge": 60,
        "mozart.force": 60 + 50}
    assert by["mozart.call"].count == 2
    assert round(by["mozart.plan"].total_s * 1e9) == 180


def test_idle_goes_to_the_innermost_span(reading):
    t = spans.of_reading(reading)
    ns = {n: round(v.idle_s * 1e9) for n, v in t.by_name.items()}
    assert ns == {
        "mozart.call": 20 + 30 + 90 + 400 + 40, "mozart.capture": 180,
        "mozart.evaluate": 30 + 20, "mozart.plan": 40, "mozart.rewrite": 70,
        "mozart.fingerprint": 70, "mozart.stage": 20 + 20,
        "mozart.inputs": 20, "mozart.layout": 40, "mozart.drive": 100,
        "mozart.merge": 60, "mozart.force": 60 + 50}
    assert round(t.unattributed_idle_s * 1e9) == 40


def test_entry_stage_and_unattributed_make_up_host_idle(reading):
    read = {n: metric_reader(n)(reading) for n in NEW + OLD}
    rest_ms = (spans.of_reading(reading).unattributed_idle_s
               / reading.calls * 1e3)
    assert read["entry_idle_ms"] == pytest.approx(1100e-9 / 2 * 1e3)
    assert read["stage_idle_ms"] == pytest.approx(260e-9 / 2 * 1e3)
    assert read["host_idle_ms"] == pytest.approx(1400e-9 / 2 * 1e3)
    assert (read["entry_idle_ms"] + read["stage_idle_ms"] + rest_ms
            == pytest.approx(read["host_idle_ms"]))
    assert read["capture_ms"] == pytest.approx(180e-9 / 2 * 1e3)
    assert read["plan_ms"] == pytest.approx(180e-9 / 2 * 1e3)
    assert read["layout_bytes_share"] is None     # no compulsory bytes


def test_attributes_are_summed_and_gaps_keep_their_names(reading):
    t = spans.of_reading(reading)
    assert t.by_name["mozart.stage"].attrs == {"stage": 0,
                                               "layout_bytes": 3000}
    assert t.by_name["mozart.drive"].attrs == {"chunks": 4}
    assert t.by_name["mozart.call"].attrs == {"fast": 1}
    # The breakdown's idle gaps are named by the bench spans, as before.
    assert [(n, round(s * 1e9)) for n, s in reading.gaps] == [
        ("bench.call", 600), ("bench.call", 500), ("bench.call", 300),
        ("loop", 250)]


def test_nesting_is_counted(reading):
    assert spans.of_reading(reading).parents == {
        ("mozart.call", None): 2, ("mozart.capture", "mozart.call"): 1,
        ("mozart.evaluate", "mozart.call"): 1,
        ("mozart.plan", "mozart.evaluate"): 1,
        ("mozart.rewrite", "mozart.plan"): 1,
        ("mozart.fingerprint", "mozart.plan"): 1,
        ("mozart.stage", "mozart.evaluate"): 1,
        ("mozart.inputs", "mozart.stage"): 1,
        ("mozart.layout", "mozart.stage"): 1,
        ("mozart.drive", "mozart.stage"): 1,
        ("mozart.merge", "mozart.stage"): 1,
        ("mozart.force", "mozart.call"): 2}


def test_innermost_pieces_are_disjoint_and_cover_the_spans():
    pieces = spans.innermost(PROGRAM)
    assert all(a[1] <= b[0] for a, b in zip(pieces, pieces[1:]))
    assert sum(e - s for s, e, _ in pieces) == 980 + 680
    assert spans.name_at(pieces, 5) is None
    assert spans.name_at(pieces, 250) == "mozart.rewrite"
    assert spans.name_at(pieces, 905) == "mozart.call"
    assert spans.name_at(pieces, 1925) == "mozart.force"


def test_layout_share_reads_the_stage_spans_count(reading):
    reading.bytes = 1500.0
    assert metric_reader("layout_bytes_share")(reading) == pytest.approx(200.0)
    program = [(n, s, e, {**a, "layout_bytes": 0} if n == "mozart.stage"
                else a) for n, s, e, a in PROGRAM]
    r = reduced(events(), program)
    r.bytes = 1500.0
    assert metric_reader("layout_bytes_share")(r) == 0.0
    # A program whose stage spans carry no count reads nothing.
    r = reduced(events(), [(n, s, e, {}) for n, s, e, _ in PROGRAM])
    r.bytes = 1500.0
    assert metric_reader("layout_bytes_share")(r) is None


def test_spans_outside_the_window_are_left_out():
    t = spans.in_window(events(), PROGRAM + [("mozart.call", 2200, 2300, {})])
    assert t.by_name["mozart.call"].count == 2


def test_trace_without_program_spans_reads_nothing():
    r = reduced(events(), [])
    assert spans.of_reading(r) is None
    for name in NEW:
        assert metric_reader(name)(r) is None


# Readings of the committed fixtures with the reduction as it was before
# the program had spans; the readers must still give exactly these.
BEFORE = {
    "two_calls.pbtxt": {
        "host_idle_ms": 5e-05, "idle_share": 45.00000000000001,
        "device_roofline": 593850.5938505939, "call_mfu": 326617.8266178266,
        "call_p50_ms": 15.6},
    "dc_two_calls.pbtxt": {
        "host_idle_ms": 143.787607, "idle_share": 95.5971245164879,
        "device_roofline": 19.72091249030339, "call_mfu": 0.8682872211604443,
        "call_p50_ms": 15.6},
}


@pytest.mark.parametrize("fixture", sorted(BEFORE))
def test_fixtures_read_as_before(fixture):
    profile = ProfileData.from_text_proto((FIXTURES / fixture).read_text())
    r = reduction.reduce(reduction.events_from_profile(profile))
    r.flops, r.bytes = 2 * 32.0, 2 * 1.07e9
    r.peak_flops_per_s, r.peak_bytes_per_s = 197e12, 819e9
    r.call_median_s = 0.0156
    assert {n: metric_reader(n)(r) for n in OLD} == pytest.approx(
        BEFORE[fixture], rel=1e-12)
    assert spans.of_reading(r) is None
    assert all(metric_reader(n)(r) is None for n in NEW)


def test_spans_and_attributes_read_from_a_profile(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("mozart.call", fast=False):
        with jax.profiler.TraceAnnotation("mozart.drive", chunks=3,
                                          executor="scan"):
            pass
    with jax.profiler.TraceAnnotation("other"):
        pass
    jax.profiler.stop_trace()
    path = max(glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb")))
    got = spans.from_profile(ProfileData.from_file(path))
    assert [(n, a) for n, _, _, a in sorted(got, key=lambda sp: sp[1])] == [
        ("mozart.call", {"fast": 0}),
        ("mozart.drive", {"chunks": 3, "executor": "scan"})]
    assert spans.parents(got) in ([None, 0], [1, None])


def _write_trace(trace_dir, calls):
    """A profile as ``run.py`` writes one: ``calls`` bench calls, each with
    a ``mozart.call`` and a ``mozart.stage`` inside; its reading."""
    jax.profiler.start_trace(str(trace_dir))
    for _ in range(calls):
        with jax.profiler.TraceAnnotation("bench.call"):
            with jax.profiler.TraceAnnotation("mozart.call", fast=False):
                with jax.profiler.TraceAnnotation("mozart.stage", stage=0,
                                                  layout_bytes=64):
                    pass
        with jax.profiler.TraceAnnotation("bench.wait"):
            pass
    jax.profiler.stop_trace()
    path = max(glob.glob(str(trace_dir / "plugins/profile/*/*.xplane.pb")))
    return reduction.reduce(reduction.load(path))


def test_a_reading_finds_the_trace_it_came_from(capsys):
    a = _write_trace(spans.TRACES / "cell-a", 3)
    b = _write_trace(spans.TRACES / "cell-b", 2)
    for r, calls in ((a, 3), (b, 2), (a, 3)):
        t = spans.of_reading(r)
        assert t.by_name["mozart.call"].count == calls
        assert t.by_name["mozart.stage"].attrs["layout_bytes"] == 64 * calls
    err = capsys.readouterr().err
    assert err.count("[bench] spans per call: ") == 3
    assert "mozart.stage.layout_bytes 64" in err
    assert "traced calls median" in err
    other = reduction.reduce(events())            # read from no trace here
    assert spans.of_reading(other) is None
    assert metric_reader("capture_ms")(other) is None
