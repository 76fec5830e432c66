"""The comparison that decides ``correct``, driven through a whole run at a
size the CPU holds (the chip check skipped, Pallas in interpret mode).

A sound run is correct.  The control (the plain reference in bfloat16 in
the pipeline's place) and each fault a cell can have, planted under the
timed call, come out not correct.  The faults of a training step or of a
multi-chip exchange do not exist in these one-chip library cells."""

import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from bench import cells
from bench import run as bench_run

CELLS = ["black-scholes.bulk", "data-cleaning.bulk",
         "black-scholes.small-batch"]
SEED = 2**33 + 17            # wider than 32 bits, as the driver's are


def tiny(name):
    cell = cells.load_cell(name)
    cell.traffic.update(elements_per_call=(1 << 12) + 40, batches=3,
                        check_calls=min(cell.traffic["check_calls"], 4),
                        warmup_calls=1, trace_seconds=0.2)
    return cell


def run(cell, **kw):
    return bench_run.run_cell(cell, SEED, 0.0, calls=6, require_tpu=False,
                              log=lambda *_: None, **kw)


def half_left_out(call):
    """Half of each batch replaced by the other half: the pipeline sees
    only half of the rows."""
    def broken(b):
        half = {k: jnp.concatenate([v[: v.shape[0] // 2],
                                    v[: v.shape[0] - v.shape[0] // 2]])
                for k, v in b.items()}
        return call(half)
    return broken


def answer_altered(call):
    """One answer of each call altered where it is produced."""
    def broken(b):
        outs, stats = call(b)
        first = outs[0]
        first = first.at[0].add(1.0) if first.ndim else first + 1.0
        return (first, *outs[1:]), stats
    return broken


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    res = run(tiny(name))
    assert res["correct"], res["check"]
    assert res["failed"] == 0 and res["attempted"] == 6
    assert list(res)[-1] == "check"
    assert set(res["metrics"]) == {"elems_per_s", "call_p95_ms", "setup_s"}


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    res = run(tiny(name), control=True)
    assert not res["correct"], res["check"]


@pytest.mark.parametrize("fault", [half_left_out, answer_altered],
                         ids=["half_left_out", "answer_altered"])
@pytest.mark.parametrize("name", CELLS)
def test_fault_is_not_correct(name, fault):
    res = run(tiny(name), wrap_call=fault)
    assert not res["correct"], res["check"]


def test_call_that_raises_is_failed_and_not_correct():
    def raising(call):
        def broken(b):
            raise RuntimeError("planted")
        return broken
    res = run(tiny("data-cleaning.bulk"), wrap_call=raising)
    assert res["failed"] == res["attempted"] == 6
    assert not res["correct"]


def test_traced_run_reports_per_layer_keys():
    res = bench_run.run_cell(tiny("black-scholes.small-batch"), SEED, 0.0,
                             trace=True, calls=4, require_tpu=False,
                             log=lambda *_: None)
    assert res["correct"]
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert "call_p50_ms" in res["metrics"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_same_seed_same_inputs():
    ref = cells.load_cell("black-scholes.bulk").reference_module()
    traffic = {"elements_per_call": 256, "batches": 2}
    a = bench_run.make_batches(ref, traffic, SEED)
    b = bench_run.make_batches(ref, traffic, SEED)
    c = bench_run.make_batches(ref, traffic, SEED + 2**32)
    assert all(bool(jnp.all(a[i][k] == b[i][k])) for i in range(2) for k in a[i])
    assert not bool(jnp.all(a[0]["price"] == c[0]["price"]))
    assert not bool(jnp.all(a[0]["price"] == a[1]["price"]))


def _entry(cwd, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", HOME=str(tmp_path))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "black-scholes.bulk",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_without_result(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(cells.ROOT, root, ignore=shutil.ignore_patterns(
        ".git", "out", "__pycache__", "chiprun_out", "checkouts",
        ".hypothesis", ".jax_cache"))
    p = _entry(root, tmp_path)
    assert p.returncode == 2, p.stderr[-2000:]
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_exit_without_result(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(cells.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(cells.ROOT / "BENCHMARK.json", root)
    p = _entry(root, tmp_path)
    assert p.returncode != 0
    assert p.stdout == ""
