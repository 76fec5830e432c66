#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/calibrate.py --workload black-scholes.bulk \\
        --seeds 12 --control-seeds 3 --baselines

One process, one set-up: the pipeline is built and compiled once, then
for each seed the traffic's batches are made anew and a short window at
the cell's own load runs through the same ``Loop`` and ``check`` as a
benchmark run.  The control (the plain reference in the configuration's
``control_dtype``, in the pipeline's place) runs the same way on its own
seeds.  ``--baselines`` times the same calls under ``eager`` and under a
whole-function ``jax.jit`` of the un-annotated code (the float32
reference).  The last line of stdout is a JSON summary.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run as bench_run  # noqa: E402


def readings(cell, call, seeds, calls, mesh=None):
    import gc
    out = []
    for seed in seeds:
        batches = bench_run.make_batches(cell.reference_module(),
                                         cell.traffic, seed, mesh)
        loop = bench_run.Loop(call, batches, bench_run.Reservoir(
            int(cell.traffic["check_calls"]), seed))
        loop.run(calls=calls)
        r = bench_run.check(cell, batches, loop.keep.kept)
        print(f"[calibrate] seed {seed} failed {loop.failed} raised "
              f"{loop.raised} {json.dumps(r)}", flush=True)
        out.append({"seed": seed, "failed": loop.failed, **r})
        del batches, loop
        gc.collect()
    return out


def per_call_s(fn, batches, calls: int = 10) -> float:
    import jax
    import numpy as np
    for b in batches[:2]:
        jax.block_until_ready(fn(b))
    lat = []
    for i in range(calls):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(batches[i % len(batches)]))
        lat.append(time.perf_counter() - t0)
    return float(np.median(lat))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=4_100_000_000)
    ap.add_argument("--baselines", action="store_true")
    args = ap.parse_args(argv)
    jax = bench_run.setup_jax()
    from bench.cells import load_cell
    from repro import hardware
    from repro.core import mozart

    cell = load_cell(args.workload)
    dev = jax.devices()[0]
    if dev.platform != "tpu" or len(jax.devices()) < cell.chips:
        print("calibrate: JAX found no TPU, or fewer chips than the cell "
              "asks for", file=sys.stderr)
        return 2
    mesh = bench_run.make_mesh(cell.chips)
    traffic = cell.traffic
    calls = min(2 * int(traffic["batches"]), 256)
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    ctl_seeds = [args.first_seed + 104729 + 7919 * i
                 for i in range(args.control_seeds)]
    summary = {"workload": cell.name, "calls_per_seed": calls}

    batches = bench_run.make_batches(cell.reference_module(), traffic,
                                     seeds[0], mesh)
    t0 = time.perf_counter()
    call, p = bench_run.build_program(cell, batches, hardware.chip_for(dev),
                                      mesh=mesh)
    print(f"[calibrate] {p.describe()} (built in "
          f"{time.perf_counter() - t0:.1f} s)", flush=True)
    if args.baselines:
        wl = cell.workload_module().workload
        jitted = bench_run.reference_fn(cell.reference_module(),
                                        cell.config["dtype"])

        def eager(b):
            with mozart.session(executor="eager", lazy=False):
                return wl(**b)

        summary["baselines_ms"] = {
            name: per_call_s(fn, batches) * 1e3 for name, fn in (
                ("pipeline_auto", lambda b: call(b)[0]),
                ("eager", eager), ("jax_jit", lambda b: jitted(**b)))}
        print(f"[calibrate] baselines (median ms a call) "
              f"{json.dumps(summary['baselines_ms'])}", flush=True)
    del batches
    summary["program"] = readings(cell, call, seeds, calls, mesh)
    del call, p
    from repro.core import plan_cache
    plan_cache.clear()
    ctl, _ = bench_run.build_program(cell, [], None, control=True)
    summary["control"] = readings(cell, ctl, ctl_seeds, calls, mesh)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
