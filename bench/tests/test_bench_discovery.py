"""A cell, a configuration, a traffic mix and a per-layer metric are found
by name: a file dropped in is picked up with no edit to the harness."""

import json
import shutil

from bench import cells
from bench.reduction import Reading


def _copy(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(cells.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(cells.ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    return root


def test_every_declared_cell_loads():
    spec = json.loads((cells.ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = cells.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.workload_module().workload
        ref = cell.reference_module()
        assert ref.make_batch and ref.reference and ref.compare
        assert set(cell.config["limits"])
        for m in cell.per_layer:
            assert callable(cells.metric_reader(m["name"]))


def test_dropped_in_metric_is_read(tmp_path):
    root = _copy(tmp_path)
    (root / "bench" / "metrics" / "busy_ms.py").write_text(
        "def read(r):\n    return r.busy_s * 1e3 if r.busy_s > 0 else None\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["per_layer"].append({
        "name": "busy_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "device", "moves": "elems_per_s"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = cells.load_cell("black-scholes.bulk", root=root,
                           bench=root / "bench")
    assert "busy_ms" in [m["name"] for m in cell.per_layer]
    read = cells.metric_reader("busy_ms", root / "bench")
    assert read(Reading(window_s=1.0, busy_s=0.25, calls=1,
                        idle_in_calls_s=0.0, op_totals=[], gaps=[])) == 250.0


def test_dropped_in_cell_is_found(tmp_path):
    root = _copy(tmp_path)
    (root / "bench" / "traffic" / "bulk_2p26_scan.json").write_text(json.dumps(
        {"elements_per_call": 1 << 26, "batches": 2, "check_calls": 2,
         "warmup_calls": 1, "trace_seconds": 1}))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "data-cleaning.half", "chips": 1,
                              "config": "data-cleaning-f32",
                              "traffic": "bulk_2p26_scan", "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = cells.load_cell("data-cleaning.half", root=root,
                           bench=root / "bench")
    assert cell.traffic["elements_per_call"] == 1 << 26
    assert cell.config["name"] == "data-cleaning-f32"
