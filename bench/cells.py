"""Find what ``BENCHMARK.json`` names, each in a file of its own.

A cell names a configuration and a traffic mix; a per-layer metric names a
reader.  Each is found by its name alone:

    configs/<config>.json            sizes, the numbers compared and limits
    configs/<config>.workload.py     the user code under test (``workload``)
    configs/<config>.reference.py    input generator, plain reference, compare
    traffic/<traffic>.json           call size, batches in rotation, ...
    metrics/<metric>.py              ``read(reading) -> float | None``

So a new cell, configuration or metric needs new files and no edit here.

A cell's ``chips`` is the number of chips it runs on.  Above 1, ``run.py``
builds a ``data`` mesh over exactly that many chips and hands it to the
pipeline; the batches are made on that mesh, split along their first axis;
the trace is read on each of those chips' device planes, busy being the
union over them, op totals per chip, and the least time for the work
counted against every chip's peaks.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_module(path: Path) -> ModuleType:
    name = "bench_" + path.stem.replace("-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict            # configs/<config>.json
    traffic: dict           # traffic/<traffic>.json
    end_to_end: list        # BENCHMARK.json entries this cell reports
    per_layer: list
    bench: Path             # directory the files above came from

    def workload_module(self) -> ModuleType:
        return load_module(self.bench / "configs"
                           / f"{self.config['name']}.workload.py")

    def reference_module(self) -> ModuleType:
        return load_module(self.bench / "configs"
                           / f"{self.config['name']}.reference.py")


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT, bench: Path = BENCH) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    try:
        w = next(w for w in spec["workloads"] if w["name"] == name)
    except StopIteration:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{[w['name'] for w in spec['workloads']]}") from None
    config = json.loads((bench / "configs" / f"{w['config']}.json").read_text())
    traffic = json.loads((bench / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=[m for m in spec["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in spec["per_layer"] if _reports(m, name)],
        bench=bench)


def metric_reader(name: str, bench: Path = BENCH):
    """The ``read`` function of ``metrics/<name>.py``."""
    return load_module(bench / "metrics" / f"{name}.py").read
