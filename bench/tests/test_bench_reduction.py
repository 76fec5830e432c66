"""The reduction from a profiler trace to per-layer numbers, on a trace
written by hand (``fixtures/two_calls.pbtxt``, times in ns):

    host:   call [0,100]  wait [100,400]  loop  call [450,500]  wait [500,800]
    device: fusion.1 [50,150], copy.2 [120,300], fusion.1 [520,700],
            mozart_split_pipeline [790,900] (past the window's end)
"""

import dataclasses
from pathlib import Path

import pytest
from jax.profiler import ProfileData

from bench import reduction
from bench.cells import metric_reader

FIXTURES = Path(__file__).parent / "fixtures"


@pytest.fixture(scope="module")
def reading():
    profile = ProfileData.from_text_proto(
        (FIXTURES / "two_calls.pbtxt").read_text())
    return reduction.reduce(reduction.events_from_profile(profile))


def test_events_from_profile_keeps_ops_and_bench_spans():
    profile = ProfileData.from_text_proto(
        (FIXTURES / "two_calls.pbtxt").read_text())
    ev = reduction.events_from_profile(profile)
    assert sorted(n for n, _, _ in ev.ops) == [
        "copy.2", "fusion.1", "fusion.1", "mozart_split_pipeline"]
    assert sorted(n for n, _, _ in ev.spans) == [
        "bench.call", "bench.call", "bench.wait", "bench.wait"]


def test_window_busy_and_idle(reading):
    assert reading.calls == 2
    assert reading.window_s == pytest.approx(800e-9)
    # [50,300] + [520,700] + [790,800] (clipped at the window's end)
    assert reading.busy_s == pytest.approx(440e-9)
    # idle [0,50] inside call 1, idle [450,500] inside call 2
    assert reading.idle_in_calls_s == pytest.approx(100e-9)


def test_gaps_are_named_by_the_open_span(reading):
    assert [(n, pytest.approx(s)) for n, s in reading.gaps] == [
        ("loop", 220e-9), ("bench.wait", 90e-9), ("bench.call", 50e-9)]


def test_op_totals_inside_the_window(reading):
    assert dict(reading.op_totals) == {
        "jit_driver/fusion.1": pytest.approx(280e-9),
        "jit_driver/copy.2": pytest.approx(180e-9),
        "jit_driver/mozart_split_pipeline": pytest.approx(10e-9)}


def test_nested_ops_are_charged_self_time():
    ops = [("%while.1 = (f32[8]) while(...)", 0, 100),
           ("%fusion.3 = f32[8] fusion(...)", 10, 40),
           ("%fusion.4 = f32[8] fusion(...)", 50, 90),
           ("%copy = f32[8] copy(...)", 120, 130)]
    named = reduction.label_ops(ops, [("jit_scan(77)", 0, 140)])
    assert [n for n, _, _ in named] == [
        "jit_scan/while.1", "jit_scan/fusion.3", "jit_scan/fusion.4",
        "jit_scan/copy"]
    assert reduction.self_times(named) == [
        ("jit_scan/while.1", 30), ("jit_scan/fusion.3", 30),
        ("jit_scan/fusion.4", 40), ("jit_scan/copy", 10)]


def test_readers_and_roofline_arithmetic(reading):
    reading.flops, reading.bytes = 2 * 32.0, 2 * 819.0 * 100
    reading.peak_flops_per_s, reading.peak_bytes_per_s = 197e12, 819e9
    least = 2 * 100e-9                   # bytes bound: 2 x 100 ns at peak
    assert reading.min_time_s() == pytest.approx(least)
    reading.call_median_s = 0.0156
    read = {n: metric_reader(n)(reading) for n in (
        "host_idle_ms", "idle_share", "device_roofline", "call_mfu",
        "call_p50_ms")}
    assert read["call_p50_ms"] == pytest.approx(15.6)
    assert read["host_idle_ms"] == pytest.approx(50e-9 * 1e3)
    assert read["idle_share"] == pytest.approx(45.0)
    assert read["device_roofline"] == pytest.approx(least / 440e-9 * 100)
    assert read["call_mfu"] == pytest.approx(least / 800e-9 * 100)


def test_nothing_to_read_gives_nothing():
    assert reduction.reduce(reduction.Events(ops=[], spans=[])) is None
    idle = reduction.reduce(reduction.Events(
        ops=[], spans=[("bench.call", 0, 10), ("bench.wait", 10, 20)]))
    for name in ("host_idle_ms", "idle_share", "device_roofline", "call_mfu",
                 "call_p50_ms"):
        assert metric_reader(name)(idle) is None


def test_merge_and_overlap():
    assert reduction.merge([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
    assert reduction._overlap([(0, 3), (5, 9)], [(2, 6), (8, 20)]) == 3


def test_recorded_trace_adds_up():
    """A trace recorded on the chip (two data-cleaning calls): every op's
    self time adds up to the busy union, and the idle time inside the call
    spans matches a plain pairwise count."""
    profile = ProfileData.from_text_proto(
        (FIXTURES / "dc_two_calls.pbtxt").read_text())
    ev = reduction.events_from_profile(profile)
    r = reduction.reduce(ev, top=10**6)
    assert r.calls == 2
    assert 0 < r.busy_s < r.window_s
    assert sum(t for _, t in r.op_totals) == pytest.approx(r.busy_s)
    assert all(n.startswith("jit_") for n, _ in r.op_totals)
    lo = min(s for n, s, _ in ev.spans if n == "bench.call")
    hi = max(e for _, _, e in ev.spans)
    busy = reduction.merge([(max(s, lo), min(e, hi)) for _, s, e in ev.ops
                            if e > lo and s < hi])
    idle = reduction._complement(busy, lo, hi)
    calls = [(s, e) for n, s, e in ev.spans if n == "bench.call"]
    pairwise = sum(max(0, min(e, ce) - max(s, cs))
                   for s, e in idle for cs, ce in calls)
    assert r.idle_in_calls_s == pytest.approx(pairwise * 1e-9)
    assert sum(t for _, t in r.gaps) == pytest.approx(r.window_s - r.busy_s)


# What ``reduce`` gave for the recorded one-chip traces before it read a
# mesh's planes: a one-chip reading must stay the same to the bit.
ONE_CHIP = {
    "two_calls.pbtxt": dict(
        window_s=8.000000000000001e-07, busy_s=4.4e-07, calls=2,
        idle_in_calls_s=1.0000000000000001e-07,
        op_totals=[("jit_driver/fusion.1", 2.8e-07),
                   ("jit_driver/copy.2", 1.8000000000000002e-07),
                   ("jit_driver/mozart_split_pipeline", 1e-08)],
        gaps=[("loop", 2.2e-07), ("bench.wait", 9.000000000000001e-08),
              ("bench.call", 5.0000000000000004e-08)],
        flops=0.0, bytes=0.0, peak_flops_per_s=0.0, peak_bytes_per_s=0.0,
        call_median_s=0.0),
    "dc_two_calls.pbtxt": dict(
        window_s=0.30093067700000004, busy_s=0.013249603, calls=2,
        idle_in_calls_s=0.287575214,
        op_totals=[("jit_slice/slice.1", 0.006707186),
                   ("jit_fused_driver/fusion.1", 0.006542407),
                   ("jit_fused_driver/copy-start", 5e-09),
                   ("jit_fused_driver/copy-done", 5e-09)],
        gaps=[("bench.call", 0.041429277), ("bench.call", 0.039490417),
              ("bench.call", 0.002287584), ("bench.call", 0.001670416),
              ("bench.call", 0.001486991),
              ("bench.call", 0.0014652620000000002),
              ("bench.call", 0.001427389),
              ("bench.call", 0.0014212950000000002),
              ("bench.call", 0.001410957), ("bench.call", 0.001367327)],
        flops=0.0, bytes=0.0, peak_flops_per_s=0.0, peak_bytes_per_s=0.0,
        call_median_s=0.0),
}


@pytest.mark.parametrize("fixture", sorted(ONE_CHIP))
def test_one_chip_fixture_reads_to_the_bit_as_before(fixture):
    profile = ProfileData.from_text_proto((FIXTURES / fixture).read_text())
    r = reduction.reduce(reduction.events_from_profile(profile))
    got = dataclasses.asdict(r)
    assert {k: got[k] for k in ONE_CHIP[fixture]} == ONE_CHIP[fixture]
    assert r.chips == 1 and r.chip_busy_s == (r.busy_s,)
    r.flops, r.bytes = 3.0e12, 2 * 1.07e9
    r.peak_flops_per_s, r.peak_bytes_per_s = 197e12, 819e9
    assert r.min_time_s() == max(3.0e12 / 197e12, 2 * 1.07e9 / 819e9)


def four_chips(durations, start=100):
    """One call over [0, 1000] ns; chip ``i`` runs one ``fusion.1`` of
    ``durations[i]`` ns from ``start`` on."""
    planes = [([("%fusion.1 = f32[8] fusion(...)", start, start + d)],
               [("jit_driver(1)", 0, 1000)]) for d in durations]
    (ops, modules), *others = planes
    return reduction.Events(
        ops=ops, modules=modules, others=others,
        spans=[("bench.call", 0, 900), ("bench.wait", 900, 1000)])


def test_per_chip_peaks_bound_a_mesh_at_100_percent():
    """Each of four chips moves its quarter of a call's bytes at one chip's
    peak over the same 800 ns: the mesh is at its roofline."""
    r = reduction.reduce(four_chips([800] * 4))
    assert r.chips == 4
    assert r.busy_s == pytest.approx(800e-9)
    r.peak_flops_per_s, r.peak_bytes_per_s = 197e12, 819e9
    r.bytes = 4 * 819e9 * 800e-9
    assert metric_reader("device_roofline")(r) == pytest.approx(100.0)
    assert metric_reader("call_mfu")(r) == pytest.approx(80.0)


def test_mesh_busy_is_the_union_over_chips():
    """A chip busy twice as long as the others sets the mesh's busy time;
    each op's total is per chip."""
    r = reduction.reduce(four_chips([300, 300, 300, 600]))
    assert r.busy_s == pytest.approx(600e-9)
    assert r.chip_busy_s == pytest.approx((300e-9, 300e-9, 300e-9, 600e-9))
    assert dict(r.op_totals) == {
        "jit_driver/fusion.1": pytest.approx((3 * 300 + 600) / 4 * 1e-9)}
    # idle [0,100] and [700,1000], of which [700,900] lies in the call
    assert r.idle_in_calls_s == pytest.approx(300e-9)
    assert metric_reader("idle_share")(r) == pytest.approx(40.0)
    assert sorted(r.gaps) == [("bench.call", pytest.approx(100e-9)),
                              ("bench.call", pytest.approx(300e-9))]
