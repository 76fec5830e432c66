#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` on the chip and print its result.

    python3 bench/run.py --workload black-scholes.bulk --seed 7 \\
        --seconds 10 --trace 0

The cell names a configuration (``configs/<config>.*``) and a traffic mix
(``traffic/<traffic>.json``).  Set-up makes the traffic's distinct input
batches on the device from ``--seed``, builds
``mozart.pipeline(workload, executor=..., chip=chip_for(device))`` with the
executor the traffic file names, lowers and compiles it on the first batch
and warms it up.  The window is
one closed-loop caller: ``p(**batch)``, ``block_until_ready`` on every
output, then the next batch in rotation, for ``--seconds``.  A seeded
reservoir keeps the outputs of some calls; once the window has closed and
the pipeline is freed, the plain reference recomputes their batches and
the numbers of ``compare`` are held to the configuration's limits.

With ``--trace 1`` a second, traced window follows and the per-layer
metrics are read from it (``reduction.py``, ``metrics/``).  ``--control``
puts the configuration's reference, computed in its ``control_dtype``, in
the pipeline's place: that run must come out not correct.

A cell whose ``chips`` is above 1 runs on a mesh of exactly that many
chips, the first of ``jax.devices()``, with the one axis ``data``: the
pipeline is built with ``mesh=`` (a one-chip cell passes none), and each
batch is made on that mesh, every leaf of rank 1 or more split along its
first axis over the chips and every scalar replicated, so no chip holds a
whole batch.  Its trace is read on the device plane of each of those chips:
busy is the union over the chips, the breakdown's op totals are per chip,
the least time for the work counts every chip's peaks, and
``memory_peak_bytes`` is the fullest chip's.

The last line of stdout is the result; the numbers compared, each beside
its limit, are the last lines of stderr.  Without a TPU, or with fewer
chips than the cell asks for, it exits 2 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import contextlib  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
OUT = BENCH / "out"
#: JAX's persistent compilation cache: a fixed path inside the checkout.
CACHE_DIR = OUT / "jax_cache"

#: per-call counters that must stay zero: a demotion, a quarantine skip or
#: an OOM halving means the path that ran is not the path that was planned.
SESSION_COUNTERS = ("exec_demotions", "exec_quarantine_skips",
                    "chunk_oom_halvings")
#: process-wide resilience counters that must stay zero.
RESILIENCE_COUNTERS = ("swallowed_errors", "MZ402", "MZ403", "MZ404", "MZ406")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def setup_jax(cache_dir: Path | None = None):
    cache_dir = cache_dir or CACHE_DIR
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def seed_key(seed: int):
    """A PRNG key from any whole number (64 bits of it count)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    words = np.random.SeedSequence(seed % 2**64).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def make_mesh(chips: int):
    """The ``data`` mesh over the first ``chips`` devices; None for one chip."""
    import jax
    if chips == 1:
        return None
    return jax.make_mesh((chips,), ("data",), devices=jax.devices()[:chips])


def make_batches(ref, traffic: dict, seed: int, mesh=None) -> list:
    """The traffic's distinct input batches, made on the device; on a
    ``mesh``, made there already split over its ``data`` axis."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    key = seed_key(seed)
    n = int(traffic["elements_per_call"])
    shardings = None
    if mesh is not None:
        shardings = jax.tree.map(
            lambda x: NamedSharding(mesh, P("data") if x.ndim else P()),
            jax.eval_shape(lambda k: ref.make_batch(k, n), key))
    gen = jax.jit(ref.make_batch, static_argnums=1, out_shardings=shardings)
    batches = [gen(jax.random.fold_in(key, i), n)
               for i in range(int(traffic["batches"]))]
    return jax.block_until_ready(batches)


def reference_fn(ref, dtype_name: str):
    import jax
    import jax.numpy as jnp
    return jax.jit(functools.partial(ref.reference,
                                     dtype=jnp.dtype(dtype_name)))


def build_program(cell, batches: list, chip, control: bool = False,
                  mesh=None):
    """``(call, pipeline)``: ``call(batch) -> (outputs, stats delta)``."""
    if control:
        f = reference_fn(cell.reference_module(), cell.config["control_dtype"])
        return (lambda b: (f(**b), {})), None
    from repro.core import mozart
    on_mesh = {} if mesh is None else {"mesh": mesh}
    p = mozart.pipeline(cell.workload_module().workload,
                        executor=cell.traffic["executor"], chip=chip,
                        **on_mesh)
    p.lower(**batches[0]).compile()
    return (lambda b: p.call_with_stats(**b)), p


class Reservoir:
    """A uniform sample of ``k`` calls' outputs, drawn from the seed."""

    def __init__(self, k: int, seed: int):
        import numpy as np
        self.k = k
        self.rng = np.random.default_rng(seed % 2**64)
        self.kept: list = []         # (batch index, outputs)
        self.seen = 0

    def offer(self, batch_index: int, outs) -> None:
        if len(self.kept) < self.k:
            self.kept.append((batch_index, outs))
        else:
            j = int(self.rng.integers(0, self.seen + 1))
            if j < self.k:
                self.kept[j] = (batch_index, outs)
        self.seen += 1


class CompileEvents:
    """Backend compiles and compile-cache hits, counted while ``on``."""

    def __init__(self, jax):
        self.on = False
        self.compiles = self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, _secs, **_kw):
        if self.on and name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, name, **_kw):
        if self.on and name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class Loop:
    """The closed-loop caller, its latencies and its failed calls."""

    def __init__(self, call, batches: list, keep: Reservoir):
        self.call, self.batches, self.keep = call, batches, keep
        self.i = 0                   # calls made, warm-up excluded
        self.failed = 0              # raised, or moved a counter
        self.raised = 0
        self.errors: list = []
        self.stats: collections.Counter = collections.Counter()

    def one(self, index: int, spans: bool):
        import jax
        from repro.core import resilience
        b = self.batches[index % len(self.batches)]
        res0 = [resilience.stats.get(k, 0) for k in RESILIENCE_COUNTERS]
        call_span = jax.profiler.TraceAnnotation if spans else _no_span
        outs, delta = None, {}
        try:
            with call_span("bench.call"):
                outs, delta = self.call(b)
            with call_span("bench.wait"):
                jax.block_until_ready(outs)
        except Exception as e:  # a call that raises has failed
            self.raised += 1
            self.errors.append(f"{type(e).__name__}: {e}"[:500])
            outs = None
        moved = (any(delta.get(k, 0) for k in SESSION_COUNTERS)
                 or [resilience.stats.get(k, 0) for k in RESILIENCE_COUNTERS]
                 != res0)
        self.failed += int(outs is None or moved)
        self.stats.update(delta)
        return outs

    def warmup(self, calls: int) -> None:
        for j in range(calls):
            self.one(j, spans=False)
        self.failed = self.raised = 0
        self.errors.clear()
        self.stats.clear()

    def run(self, seconds: float = 0.0, calls: int = 0, spans: bool = False):
        """Call until ``seconds`` have passed or ``calls`` were made.
        Returns (latencies in s, window start, window end)."""
        lat = []
        t_first = time.perf_counter()
        deadline = t_first + seconds
        while True:
            bi = self.i % len(self.batches)
            t0 = time.perf_counter()
            outs = self.one(self.i, spans)
            t1 = time.perf_counter()
            lat.append(t1 - t0)
            self.i += 1
            if outs is not None:
                self.keep.offer(bi, outs)
            if (calls and len(lat) >= calls) or (not calls and t1 >= deadline):
                return lat, t_first, t1


@contextlib.contextmanager
def _no_span(_name):
    yield


def check(cell, batches: list, kept: list) -> dict:
    """Recompute each kept call's batch with the plain reference; the
    widest of ``compare``'s readings over the kept calls, by name."""
    ref = cell.reference_module()
    f = reference_fn(ref, cell.config["dtype"])
    readings: dict = {}
    by_batch: dict = collections.defaultdict(list)
    for bi, outs in kept:
        by_batch[bi].append(outs)
    for bi, outs_list in sorted(by_batch.items()):
        refs = f(**batches[bi])
        for outs in outs_list:
            for k, v in ref.compare(outs, refs).items():
                readings[k] = max(readings.get(k, v), v)
        del refs
    return readings


def compulsory_work(cell, batch: dict, ref) -> tuple[float, float]:
    """(FLOPs, bytes) one call must do: every input read once and every
    output written once, from shapes alone; FLOPs per element from the
    configuration."""
    import jax
    outs = jax.eval_shape(ref.reference, **batch)
    nbytes = sum(x.size * x.dtype.itemsize
                 for x in jax.tree.leaves((batch, outs)))
    flops = float(cell.config["flops_per_element"]) * int(
        cell.traffic["elements_per_call"])
    return flops, float(nbytes)


class GcPauses:
    """Time the interpreter's garbage collections inside a window."""

    def __init__(self):
        self.pauses: list = []
        self._t0 = 0.0

    def _callback(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t0))

    def __enter__(self):
        gc.callbacks.append(self._callback)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._callback)

    def report(self) -> str:
        gen2 = [s for g, s in self.pauses if g == 2]
        total = sum(s for _, s in self.pauses)
        return (f"{len(self.pauses)} collections, {total:.6f} s; gen 2: "
                f"{len(gen2)}, longest {max(gen2, default=0.0):.6f} s")


def slow_calls(lat: list) -> str:
    """Calls that took over twice the median: count, excess time, longest."""
    med = percentile(lat, 50)
    slow = [(x, i) for i, x in enumerate(lat) if x > 2 * med]
    if not slow:
        return f"no call over twice the median; longest {max(lat) * 1e3:.3f} ms"
    x, i = max(slow)
    return (f"{len(slow)} calls over twice the median, "
            f"{sum(s - med for s, _ in slow):.6f} s over it; longest "
            f"{x * 1e3:.3f} ms at call {i} "
            f"({sum(lat[:i]):.3f} s into the window)")


def percentile(values: list, q: float) -> float:
    import numpy as np
    return float(np.percentile(np.asarray(values), q))


def trace_window(loop: Loop, seconds: float, out_dir: Path):
    """Run a traced window; return (latencies, path of the .xplane.pb)."""
    import jax
    shutil.rmtree(out_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(out_dir), profiler_options=opts)
    try:
        lat, _, _ = loop.run(seconds=seconds, spans=True)
    finally:
        jax.profiler.stop_trace()
    return lat, max(out_dir.glob("plugins/profile/*/*.xplane.pb"))


def run_cell(cell, seed: int, seconds: float, trace: bool = False, *,
             control: bool = False, require_tpu: bool = True,
             wrap_call=None, calls: int = 0, t_start: float = T_START,
             log=print) -> dict:
    """One run of ``cell``; the result object (see module docstring).
    ``wrap_call`` wraps the timed call (the tests break the timed path
    with it); ``calls`` bounds the window by count instead of time."""
    import jax

    from bench import reduction
    from bench.cells import metric_reader
    from bench.peaks import peaks_for
    from repro import hardware

    devices = jax.devices()
    dev = devices[0]
    marks = [("start", t_start), ("imports and device", time.perf_counter())]
    if require_tpu:
        if dev.platform != "tpu":
            raise NoChip(f"JAX found no TPU (platform {dev.platform!r})")
        if len(devices) < cell.chips:
            raise NoChip(f"{len(devices)} chips, the cell asks for "
                         f"{cell.chips}")
    peaks = peaks_for(dev.device_kind) if require_tpu else None
    events = CompileEvents(jax)
    ref = cell.reference_module()
    mesh = make_mesh(cell.chips)
    batches = make_batches(ref, cell.traffic, seed, mesh)
    marks.append(("data", time.perf_counter()))
    call, p = build_program(cell, batches, hardware.chip_for(dev), control,
                            mesh)
    marks.append(("lower and compile", time.perf_counter()))
    if wrap_call is not None:
        call = wrap_call(call)
    if p is not None:
        log(f"[bench] {p.describe()}")
        picks = {k: v for k, v in p.stats.items()
                 if k.startswith(("auto_pick_", "pallas_"))}
        log(f"[bench] set-up picks {json.dumps(picks, sort_keys=True)}")
    loop = Loop(call, batches, Reservoir(int(cell.traffic["check_calls"]),
                                         seed))
    loop.warmup(int(cell.traffic["warmup_calls"]))
    marks.append(("warm-up", time.perf_counter()))
    setup_s = marks[-1][1] - t_start
    log("[bench] set-up s: " + ", ".join(
        f"{name} {t - t0:.3f}" for (_, t0), (name, t) in zip(marks, marks[1:])))

    events.on = True
    with GcPauses() as gc_pauses:
        lat, t_first, t_last = loop.run(seconds=seconds, calls=calls)
    events.on = False
    n = int(cell.traffic["elements_per_call"])
    e2e = {
        "elems_per_s": len(lat) * n / (t_last - t_first),
        "call_p95_ms": percentile(lat, 95) * 1e3,
        "setup_s": setup_s,
    }
    log(f"[bench] window: {len(lat)} calls in {t_last - t_first:.6f} s; "
        f"latency samples {len(lat)}, median "
        f"{percentile(lat, 50) * 1e3:.6f} ms, p95 {e2e['call_p95_ms']:.6f} ms")
    log(f"[bench] {slow_calls(lat)}; gc {gc_pauses.report()}")
    log(f"[bench] compiles in window: backend {events.compiles}, "
        f"cache hits {events.cache_hits}, jit_traces "
        f"{loop.stats.get('jit_traces', 0)}")
    trace_path = None
    if trace:
        lat_t, trace_path = trace_window(
            loop, float(cell.traffic["trace_seconds"]), OUT / "trace" / cell.name)
        log(f"[bench] traced window: {len(lat_t)} calls; trace {trace_path}")
    window_stats = {k: v for k, v in sorted(loop.stats.items())
                    if k.startswith(("auto_pick_", "pallas_", "exec_",
                                     "chunk"))}
    log(f"[bench] window counters {json.dumps(window_stats)}")
    if loop.errors:
        log(f"[bench] {loop.raised} calls raised; first: {loop.errors[0]}")

    chip_stats = [d.memory_stats() or {} for d in devices[:cell.chips]]
    peaks_in_use = [int(st.get("peak_bytes_in_use", 0)) for st in chip_stats]
    memory_peak = max(peaks_in_use)
    stats = chip_stats[peaks_in_use.index(memory_peak)]
    log(f"[bench] memory: " + ", ".join(
        f"{k} {stats[k]}" for k in ("bytes_in_use", "peak_bytes_in_use",
                                   "largest_free_block_bytes", "bytes_limit")
        if k in stats) + f"; peak_bytes_in_use per chip {peaks_in_use}")
    kept = loop.keep.kept
    attempted, failed, raised = loop.i, loop.failed, loop.raised
    flops, nbytes = compulsory_work(cell, batches[0], ref)
    # Free the program's state before the reference runs.
    del call, p, loop
    from repro.core import plan_cache
    plan_cache.clear()
    gc.collect()

    readings = check(cell, batches, kept)
    limits = cell.config["limits"]
    checks = {k: {"value": readings.get(k, float("nan")), "limit": lim}
              for k, lim in limits.items()}
    correct = (raised == 0 and bool(kept)
               and all(c["value"] <= c["limit"] for c in checks.values()))

    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    result = {"correct": correct, "attempted": attempted, "failed": failed}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    if trace:
        r = reduction.reduce(reduction.load(str(trace_path), cell.chips))
        metrics = {}
        if r is not None:
            calls_traced = r.calls
            r.flops, r.bytes = flops * calls_traced, nbytes * calls_traced
            r.call_median_s = percentile(lat, 50)
            if peaks is not None:
                r.peak_flops_per_s = peaks["flops_per_s"]
                r.peak_bytes_per_s = peaks["hbm_bytes_per_s"]
            for m in cell.per_layer:
                v = metric_reader(m["name"], cell.bench)(r)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
            # busy_s of each chip, averaged; idle_share reads the union.
            device.update(busy_s=sum(r.chip_busy_s) / r.chips,
                          window_s=r.window_s)
            result["breakdown"] = {
                "device_ops": [list(o) for o in r.op_totals],
                "idle_gaps": [list(g) for g in r.gaps]}
            log(f"[bench] trace: {r.calls} calls, window {r.window_s!r} s, "
                f"busy {r.busy_s!r} s, idle in calls {r.idle_in_calls_s!r} s, "
                f"busy per chip {list(r.chip_busy_s)} s")
        result["metrics"] = metrics
    else:
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                         "unit": units[m["name"]]}
                             for m in cell.end_to_end}
    result["device"] = device
    result["check"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="the reference in control_dtype in the program's "
                         "place (must come out not correct)")
    args = ap.parse_args(argv)
    setup_jax()
    from bench.cells import load_cell
    cell = load_cell(args.workload)
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          control=args.control)
    except NoChip as e:
        print(f"bench: {e}; this benchmark runs on the chip only",
              file=sys.stderr)
        return 2
    sys.stdout.flush()
    for name, c in result["check"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
