"""Benchmark harness: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig4,...]
    PYTHONPATH=src python -m benchmarks.run --smoke

Prints ``name,us_per_call,derived`` CSV rows (benchmarks/common.py).

``--smoke`` is the CI gate (`make bench-smoke`): it runs the Black–Scholes
pipeline under every registered StageExecutor (including ``auto``), checks
numerical parity with the un-annotated "eager" oracle, exercises the plan
cache + auto-tuner with repeated runs, verifies that ``auto`` matches or
beats the fixed ``pipelined`` default in steady state, replays a persisted
plan-cache file with zero planner calls, gates cross-stage chunk handoff
(interior boundary ``bytes_materialized`` must drop to zero and warm
wall-clock must not regress vs the merge-everything path), gates the
continuous-batching serving scheduler (per-request token parity vs the
fixed-group baseline, zero warm planner calls / retraces, p50/p99 in the
JSON artifact), gates the static graph rewrite pass (dead-elimination,
CSE and filter pushdown all fire with persisted MZ5xx records, rewritten
output matches the unrewritten chain, interior boundary bytes and library
calls both drop, warm replay does zero planner calls / retraces), and
exits nonzero on any mismatch.
"""

from __future__ import annotations

import argparse
import importlib
import os
import sys
import tempfile
import traceback

from benchmarks.common import dump_json, header, record, time_fn

MODULES = {
    "fig4_pipelines": "benchmarks.fig4_pipelines",     # Fig 4 a-d, j-m
    "fig4_dataframes": "benchmarks.fig4_dataframes",   # Fig 4 e-h
    "fig4_images": "benchmarks.fig4_images",           # Fig 4 n-o
    "table3_loc": "benchmarks.table3_loc",             # Table 3
    "table4_pipelining": "benchmarks.table4_pipelining",  # Table 4
    "fig6_batchsize": "benchmarks.fig6_batchsize",     # Fig 6
    "fig7_intensity": "benchmarks.fig7_intensity",     # Fig 7
    "kernels": "benchmarks.bench_kernels",             # Pallas kernels
    "serving": "benchmarks.bench_serving",             # decode throughput
}


def _child_env() -> dict:
    """Environment of a smoke row's child process: this checkout's ``src``
    on the path and the same persistent compilation cache."""
    from repro import hardware

    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (env.get("PYTHONPATH"),
                    os.path.join(os.path.dirname(
                        os.path.dirname(os.path.abspath(__file__))), "src"))
        if p)
    env["JAX_COMPILATION_CACHE_DIR"] = hardware.use_compile_cache()
    return env


def smoke() -> int:
    """Executor-parity + plan-cache smoke check.  Returns a process exit code.

    The rows that run in child processes come first, while this process
    has not touched JAX: on a TPU host the chip belongs to one process at a
    time, so no child may need it while the parent holds it."""
    failures: list[str] = []

    # -- sharded handoff: the mesh executor streams in both directions -----
    # A subprocess on a 2-device mesh: real devices where it sees two, else
    # the same forced-host-device mesh CI's sharded tests use.  Gates:
    # interior bytes exactly 0 on a 2-device mesh, NO gather event on the
    # sharded→sharded boundary (the device-resident global array must pass
    # through — an ``interior:gather`` in the event trail means an
    # all-gather happened), the row actually exercised sharded streaming
    # (passthrough > 0), and the warm run planned nothing and retraced
    # nothing (the session-scoped trace counter).
    import json as _json
    import subprocess as _subprocess

    _SHARDED_ROW = r'''
import warnings; warnings.filterwarnings("ignore")
import json, sys, time
import numpy as np, jax, jax.numpy as jnp
from repro.core import mozart
from repro.core import annotated_numpy as anp

handoff = sys.argv[1] == "on"
n, b, evals = 400_000, 100_000, 3
devices = jax.devices()
if len(devices) < 2:
    devices = jax.devices("cpu")         # the forced 2-device host platform
mesh = jax.sharding.Mesh(np.array(devices[:2]), ("data",))
x = jax.device_put(np.linspace(0.0, 1.0, n, dtype=np.float32), devices[0])

def chain():
    with mozart.session(executor="sharded", mesh=mesh, batch_elements=b,
                        handoff=handoff) as ctx:
        cur = x
        for _ in range(evals):
            cur = anp.multiply(anp.add(cur, 1.0), 0.5)
            mozart.evaluate()            # sharded->sharded stage boundary
        out = np.asarray(cur)
    return out, ctx

chain()                                  # plan (miss)
chain()                                  # warm cache + pinned executables
out, ctx = chain()                       # measured warm run (scoped view)
samples = []
for _ in range(5):
    t0 = time.perf_counter(); chain(); samples.append(time.perf_counter() - t0)
want = np.linspace(0.0, 1.0, n, dtype=np.float32)
for _ in range(evals):
    want = (want + 1.0) * 0.5
print(json.dumps({
    "parity": bool(np.allclose(out, want, rtol=2e-5)),
    "devices": mesh.size,
    "us": sorted(samples)[len(samples) // 2] * 1e6,
    "interior": int(ctx.counters.bytes_interior()),
    "terminal": int(ctx.counters.bytes_terminal()),
    "events": ctx.counters.materialize_events(),
    "traces": int(ctx.counters.trace_count()),
    "planner_calls": int(ctx.stats.get("planner_calls", 0)),
    "streamed": int(ctx.stats.get("streamed_outputs", 0)),
    "passthrough": int(ctx.stats.get("shard_passthrough", 0)),
    "ingests": int(ctx.stats.get("shard_ingests", 0)),
    "converted": int(ctx.stats.get("stream_converted", 0)),
    "donated": int(ctx.stats.get("donated_chunks", 0)),
    "donation_copies": int(ctx.stats.get("donation_copies", 0)),
    "rechunks": int(ctx.stats.get("handoff_rechunks", 0)),
}))
'''

    def sharded_row(handoff: bool) -> dict | None:
        # The forced count shapes only the host platform: the child meshes
        # real devices when it sees two, else two host devices.
        env = _child_env()
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            " --xla_force_host_platform_device_count=2"
                            ).strip()
        proc = _subprocess.run(
            [sys.executable, "-c", _SHARDED_ROW, "on" if handoff else "off"],
            env=env, capture_output=True, text=True, timeout=900)
        if proc.returncode != 0:
            print(f"smoke/handoff/sharded subprocess failed:\n{proc.stderr}",
                  file=sys.stderr)
            return None
        return _json.loads(proc.stdout.strip().splitlines()[-1])

    on_row = sharded_row(True)
    off_row = sharded_row(False)
    sharded_failures = []
    if on_row is None or off_row is None:
        sharded_failures.append("subprocess")
        record("smoke/handoff/sharded", 0.0, "SUBPROCESS_FAILED")
    else:
        if not (on_row["parity"] and off_row["parity"]):
            sharded_failures.append("parity")
        if on_row["devices"] < 2:
            sharded_failures.append("single_device")
        if on_row["interior"] != 0:
            lines = [f"  - {kind[len('interior:'):]} at {where}: {nb} bytes"
                     for kind, where, nb in on_row["events"]
                     if kind.startswith("interior:")]
            print("smoke/handoff/sharded: expected 0 interior boundary "
                  f"bytes, got {on_row['interior']}:\n" + "\n".join(lines),
                  file=sys.stderr)
            sharded_failures.append(f"interior_bytes={on_row['interior']}")
        # No all-gather on the sharded→sharded edge: asserted via the event
        # trail, which names every gather the warm run performed.
        gathers = [e for e in on_row["events"]
                   if e[0].startswith("interior:gather")]
        if gathers:
            sharded_failures.append(f"all_gather={gathers}")
        if on_row["streamed"] == 0 or on_row["passthrough"] == 0:
            sharded_failures.append("no_streaming")
        if on_row["planner_calls"] != 0:
            sharded_failures.append("warm_planned")
        if on_row["traces"] != 0:
            sharded_failures.append("warm_retraced")
        record("smoke/handoff/sharded", on_row["us"],
               f"merge_path_us={off_row['us']:.0f};"
               f"ratio={on_row['us'] / max(off_row['us'], 1e-9):.2f};"
               f"interior={on_row['interior']};terminal={on_row['terminal']};"
               f"off_interior={off_row['interior']};"
               f"off_terminal={off_row['terminal']};"
               f"streamed={on_row['streamed']};"
               f"passthrough={on_row['passthrough']};"
               f"ingests={on_row['ingests']};"
               f"{'ok' if not sharded_failures else 'REGRESSED'}",
               extra={
                   "interior_bytes": int(on_row["interior"]),
                   "terminal_bytes": int(on_row["terminal"]),
                   "off_interior_bytes": int(off_row["interior"]),
                   "off_terminal_bytes": int(off_row["terminal"]),
                   "streamed_outputs": int(on_row["streamed"]),
                   "stream_ingests": int(on_row["ingests"]),
                   "stream_converted": int(on_row["converted"]),
                   "donated_chunks": int(on_row["donated"]),
                   "donation_copies": int(on_row["donation_copies"]),
                   "handoff_rechunks": int(on_row["rechunks"]),
                   "shard_passthrough": int(on_row["passthrough"]),
               })
    if sharded_failures:
        failures.append(f"handoff/sharded:{sharded_failures}")

    # -- serving: continuous batching matches fixed-group, stays warm ------
    # Subprocess (fresh jax state, same pattern as the sharded row).  Gates:
    # per-request token parity between the continuous-batching scheduler
    # (mozart driver, right-pad + per-slot caches) and the fixed-group
    # baseline (jit driver, left-pad + mask) under mixed prompt lengths and
    # mixed max_new; zero planner calls and zero retraces across the warm
    # run's occupancy churn.  p50/p99 latencies land in the JSON artifact.
    _SERVING_ROW = r'''
import warnings; warnings.filterwarnings("ignore")
import json
import numpy as np, jax
from repro.configs.registry import get_smoke_config
from repro.core.serving import ContinuousBatcher, ServeRequest
from repro.launch.serve import Request, Server
from repro.models import transformer as tfm

cfg = get_smoke_config("internlm2-20b")
params = tfm.init_model(jax.random.PRNGKey(0), cfg)
rng = np.random.default_rng(0)
specs = [(5, 3), (9, 7), (6, 2), (3, 5), (8, 4), (9, 1), (7, 6), (4, 2)]
prompts = [rng.integers(0, cfg.vocab_size, p).astype(np.int32)
           for p, _ in specs]
max_len = 32

def fixed_requests():
    return [Request(rid=i, prompt=p, max_new=n)
            for i, (p, (_, n)) in enumerate(zip(prompts, specs))]

fixed = Server(cfg, params, batch=2, max_len=max_len, driver="jit",
               mode="fixed")
fixed.run(fixed_requests())                  # compile every group shape
freqs = fixed_requests()
fstats = fixed.run(freqs)

def cont_requests():
    return [ServeRequest(rid=i, prompt=p, max_new=n)
            for i, (p, (_, n)) in enumerate(zip(prompts, specs))]

b = ContinuousBatcher(cfg, params, batch=2, max_len=max_len, driver="mozart")
b.warmup(max_prompt_len=9)
b.run(cont_requests())                       # warm residual host paths
creqs = cont_requests()
cstats = b.run(creqs)

print(json.dumps({
    "parity": all(c.out == f.out for c, f in zip(creqs, freqs)),
    "planner_calls": int(cstats["planner_calls"]),
    "jit_traces": int(cstats["jit_traces"]),
    "tokens": int(cstats["tokens"]),
    "tokens_per_s": cstats["tokens_per_s"],
    "fixed_tokens_per_s": fstats["tokens_per_s"],
    "decode_p50_us": cstats["decode_p50_us"],
    "decode_p99_us": cstats["decode_p99_us"],
    "request_p50_ms": cstats["request_p50_ms"],
    "request_p99_ms": cstats["request_p99_ms"],
    "mean_occupancy": cstats["mean_occupancy"],
    "us": cstats["wall_s"] * 1e6,
}))
'''

    def serving_row() -> dict | None:
        env = _child_env()
        proc = _subprocess.run(
            [sys.executable, "-c", _SERVING_ROW],
            env=env, capture_output=True, text=True, timeout=900)
        if proc.returncode != 0:
            print(f"smoke/serving subprocess failed:\n{proc.stderr}",
                  file=sys.stderr)
            return None
        return _json.loads(proc.stdout.strip().splitlines()[-1])

    srow = serving_row()
    serving_failures = []
    if srow is None:
        serving_failures.append("subprocess")
        record("smoke/serving", 0.0, "SUBPROCESS_FAILED")
    else:
        if not srow["parity"]:
            serving_failures.append("parity")
        if srow["planner_calls"] != 0:
            serving_failures.append("warm_planned")
        if srow["jit_traces"] != 0:
            serving_failures.append("warm_retraced")
        ratio = srow["tokens_per_s"] / max(srow["fixed_tokens_per_s"], 1e-9)
        record("smoke/serving", srow["us"],
               f"tokens_per_s={srow['tokens_per_s']:.1f};"
               f"fixed_tokens_per_s={srow['fixed_tokens_per_s']:.1f};"
               f"ratio={ratio:.2f};"
               f"decode_p50_us={srow['decode_p50_us']:.0f};"
               f"decode_p99_us={srow['decode_p99_us']:.0f};"
               f"occupancy={srow['mean_occupancy']:.2f};"
               f"{'ok' if not serving_failures else 'REGRESSED'}",
               extra={
                   "tokens": int(srow["tokens"]),
                   "tokens_per_s": srow["tokens_per_s"],
                   "fixed_tokens_per_s": srow["fixed_tokens_per_s"],
                   "ratio": ratio,
                   "decode_p50_us": srow["decode_p50_us"],
                   "decode_p99_us": srow["decode_p99_us"],
                   "request_p50_ms": srow["request_p50_ms"],
                   "request_p99_ms": srow["request_p99_ms"],
                   "mean_occupancy": srow["mean_occupancy"],
                   "planner_calls": int(srow["planner_calls"]),
                   "jit_traces": int(srow["jit_traces"]),
               })
    if serving_failures:
        failures.append(f"serving:{serving_failures}")

    # -- sanitize: boundary sanitizer stays quiet on a clean handoff chain --
    # Subprocess so MOZART_SANITIZE=1 is scoped to the row: a 3-stage
    # handoff chain (exp -> add -> multiply -> sum) runs cold + warm on the
    # fused executor with every MZ3xx boundary check armed (use-after-donate
    # poisoning, stream-tiling validation, scoped-counter cross-checks).
    # Gates: value parity vs numpy and zero SanitizerError violations.
    _SANITIZE_ROW = r'''
import warnings; warnings.filterwarnings("ignore")
import json, time
import numpy as np, jax.numpy as jnp
from repro.core import mozart
from repro.core import annotated_numpy as anp
from repro.core.stage_exec import SanitizerError, sanitize_active

n = 200_000
x = jnp.linspace(0.1, 2.0, n, dtype=jnp.float32)
y = jnp.linspace(0.2, 1.0, n, dtype=jnp.float32)

def chain():
    with mozart.session(executor="fused", handoff=True) as ctx:
        a = anp.exp(x)
        mozart.evaluate()                # stage boundary: streamed handoff
        b = anp.add(a, y)
        mozart.evaluate()                # second boundary (donated chunks)
        c = anp.multiply(b, 0.5)
        out = float(np.asarray(anp.sum(c)))
    return out, ctx

violations = []
try:
    chain()                              # cold: plan + sanitized run
    t0 = time.perf_counter()
    out, ctx = chain()                   # warm: sanitized handoff replay
    us = (time.perf_counter() - t0) * 1e6
except SanitizerError as e:
    violations.append(str(e)); out, us, ctx = float("nan"), 0.0, None
xs, ys = np.asarray(x), np.asarray(y)
want = float(((np.exp(xs) + ys) * 0.5).sum())
print(json.dumps({
    "armed": bool(sanitize_active()),
    "parity": bool(np.isfinite(out) and abs(out - want) <= 1e-2 * abs(want)),
    "violations": violations,
    "us": us,
    "interior": int(ctx.counters.bytes_interior()) if ctx else -1,
    "donated": int(ctx.stats.get("donated_chunks", 0)) if ctx else -1,
}))
'''

    def sanitize_row() -> dict | None:
        env = _child_env()
        env["MOZART_SANITIZE"] = "1"
        proc = _subprocess.run(
            [sys.executable, "-c", _SANITIZE_ROW],
            env=env, capture_output=True, text=True, timeout=900)
        if proc.returncode != 0:
            print(f"smoke/sanitize subprocess failed:\n{proc.stderr}",
                  file=sys.stderr)
            return None
        return _json.loads(proc.stdout.strip().splitlines()[-1])

    zrow = sanitize_row()
    sanitize_failures = []
    if zrow is None:
        sanitize_failures.append("subprocess")
        record("smoke/sanitize", 0.0, "SUBPROCESS_FAILED")
    else:
        if not zrow["armed"]:
            sanitize_failures.append("not_armed")
        if not zrow["parity"]:
            sanitize_failures.append("parity")
        if zrow["violations"]:
            print("smoke/sanitize: boundary sanitizer tripped:\n" +
                  "\n".join(f"  - {v}" for v in zrow["violations"]),
                  file=sys.stderr)
            sanitize_failures.append(f"violations={len(zrow['violations'])}")
        record("smoke/sanitize", zrow["us"],
               f"armed={zrow['armed']};violations={len(zrow['violations'])};"
               f"interior={zrow['interior']};donated={zrow['donated']};"
               f"{'ok' if not sanitize_failures else 'TRIPPED'}",
               extra={
                   "violations": zrow["violations"],
                   "interior_bytes": int(zrow["interior"]),
                   "donated_chunks": int(zrow["donated"]),
               })
    if sanitize_failures:
        failures.append(f"sanitize:{sanitize_failures}")

    # -- chaos: injected faults recover with exact results, nothing hangs ---
    # Subprocess (fresh jax + fault-plan state).  Three scenarios from the
    # resilience layer (core/resilience.py): an injected compile failure
    # demotes down the executor ladder and quarantines the broken choice; an
    # injected chunk OOM halves the batch (bounded) below the ladder; a
    # serving step failure is routed into the in-flight requests while the
    # driver thread survives to serve the next wave.  Gates: every fault run
    # matches the fault-free baseline, retries stay bounded, recovery
    # counters moved, and zero requests hang.
    _CHAOS_ROW = r'''
import warnings; warnings.filterwarnings("ignore")
import json, time
import numpy as np, jax, jax.numpy as jnp
from repro.core import mozart, plan_cache, resilience
from repro.core import annotated_numpy as anp

n = 200_000
x = jnp.linspace(0.1, 2.0, n, dtype=jnp.float32)
y = jnp.linspace(0.2, 1.0, n, dtype=jnp.float32)

def chain():
    """3-stage handoff chain (exp -> add -> multiply -> sum)."""
    with mozart.session(executor="fused", handoff=True) as ctx:
        a = anp.exp(x)
        mozart.evaluate()                # stage boundary: streamed handoff
        b = anp.add(a, y)
        mozart.evaluate()                # second boundary
        c = anp.multiply(b, 0.5)
        out = float(np.asarray(anp.sum(c)))
    return out, ctx

want, _ = chain()                        # fault-free baseline
fails = []
t0 = time.perf_counter()

# 1) compile failure -> ladder demotion + quarantine, same answer
plan_cache.clear()                       # force a fresh driver build
with mozart.inject_faults("compile:fail:1") as p1:
    got, ctx1 = chain()
demotions = int(ctx1.stats.get("exec_demotions", 0))
if not np.isclose(got, want, rtol=1e-5):
    fails.append("compile_parity")
if not p1.fired or demotions < 1:
    fails.append("no_demotion")
quarantined = sum(1 for e in plan_cache.entries() if e.quarantined)
if quarantined < 1:
    fails.append("no_quarantine")

# 2) chunk OOM -> bounded batch halvings below the ladder, same answer
plan_cache.clear()
with mozart.inject_faults("chunk:oom:1") as p2:
    got2, ctx2 = chain()
halvings = int(ctx2.stats.get("chunk_oom_halvings", 0))
if not np.isclose(got2, want, rtol=1e-5):
    fails.append("oom_parity")
if not p2.fired or not (1 <= halvings <= resilience.MAX_OOM_HALVINGS):
    fails.append(f"halvings={halvings}")

# 3) serving churn: a step fault fails in-flight requests VISIBLY, the
#    driver survives, the next wave completes — zero hung requests
from repro.configs.registry import get_smoke_config
from repro.core.serving import AsyncServer, ContinuousBatcher
from repro.models import transformer as tfm
cfg = get_smoke_config("internlm2-20b")
params = tfm.init_model(jax.random.PRNGKey(0), cfg)
rng = np.random.default_rng(0)
prompts = [rng.integers(0, cfg.vocab_size, p).astype(np.int32)
           for p in (5, 7, 4, 6)]
b = ContinuousBatcher(cfg, params, batch=2, max_len=32, driver="jit",
                      max_queue=16)
wave1 = [b.submit(b.make_request(p, 3)) for p in prompts[:2]]
srv = AsyncServer(b, idle_poll_s=1e-4)
with mozart.inject_faults("serve_step:fail:1"):
    srv.start()
    deadline = time.time() + 120
    for r in wave1:
        r.done.wait(max(0.0, deadline - time.time()))
    wave2 = [b.submit(b.make_request(p, 4)) for p in prompts[2:]]
    for r in wave2:
        r.done.wait(max(0.0, deadline - time.time()))
srv.close()
hung = [r.rid for r in wave1 + wave2 if not r.finished]
if hung:
    fails.append(f"hung={hung}")
if b.stats.get("step_failures", 0) != 1:
    fails.append("driver_died_or_step_fault_missed")
if not all(isinstance(r.error, resilience.InjectedFault) for r in wave1):
    fails.append("fault_not_routed_to_requests")
if not all(r.error is None and len(r.out) == 4 for r in wave2):
    fails.append("post_fault_serving")

print(json.dumps({
    "fails": fails,
    "us": (time.perf_counter() - t0) * 1e6,
    "demotions": demotions,
    "quarantined_entries": quarantined,
    "oom_halvings": halvings,
    "step_failures": int(b.stats.get("step_failures", 0)),
    "failed_requests": int(b.stats.get("failed_requests", 0)),
    "mz": {k: int(v) for k, v in resilience.stats.items()
           if k.startswith("MZ")},
}))
'''

    def chaos_row() -> dict | None:
        env = _child_env()
        env.pop("MOZART_FAULTS", None)   # the row arms its own plans
        proc = _subprocess.run(
            [sys.executable, "-c", _CHAOS_ROW],
            env=env, capture_output=True, text=True, timeout=900)
        if proc.returncode != 0:
            print(f"smoke/chaos subprocess failed:\n{proc.stderr}",
                  file=sys.stderr)
            return None
        return _json.loads(proc.stdout.strip().splitlines()[-1])

    crow = chaos_row()
    chaos_failures = []
    if crow is None:
        chaos_failures.append("subprocess")
        record("smoke/chaos", 0.0, "SUBPROCESS_FAILED")
    else:
        chaos_failures.extend(crow["fails"])
        record("smoke/chaos", crow["us"],
               f"demotions={crow['demotions']};"
               f"quarantined={crow['quarantined_entries']};"
               f"oom_halvings={crow['oom_halvings']};"
               f"step_failures={crow['step_failures']};"
               f"{'ok' if not chaos_failures else 'REGRESSED'}",
               extra={
                   "demotions": int(crow["demotions"]),
                   "quarantined_entries": int(crow["quarantined_entries"]),
                   "oom_halvings": int(crow["oom_halvings"]),
                   "step_failures": int(crow["step_failures"]),
                   "failed_requests": int(crow["failed_requests"]),
                   "mz_counters": crow["mz"],
               })
    if chaos_failures:
        failures.append(f"chaos:{chaos_failures}")

    # -- in-process rows: every child has exited ----------------------------
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import workloads as w
    from repro.core import mozart, plan_cache
    from repro.core.stage_exec import available_executors

    d = w.black_scholes_data(20_000)
    plan_cache.clear()
    with mozart.session(executor="eager"):
        call, put = w.black_scholes(**d)
        want = (np.asarray(call), np.asarray(put))

    for name in available_executors():
        kwargs = {}
        if name == "sharded":
            kwargs["mesh"] = jax.make_mesh((1,), ("data",))

        def once(name=name, kwargs=kwargs):
            with mozart.session(executor=name, **kwargs):
                c, p = w.black_scholes(**d)
                return np.asarray(c), np.asarray(p)

        try:
            # Three runs: plan (miss), tune (first hit), pinned (later hit) —
            # parity must hold through every phase of the plan-cache lifecycle.
            for i in range(3):
                got = once()
                for g, expect, label in zip(got, want, ("call", "put")):
                    np.testing.assert_allclose(
                        g, expect, rtol=2e-4, atol=1e-5,
                        err_msg=f"{name} run{i} {label}")
            record(f"smoke/parity/{name}", 0.0, "ok")
        except Exception as e:  # noqa: BLE001 — report every executor
            traceback.print_exc()
            failures.append(name)
            record(f"smoke/parity/{name}", 0.0, f"MISMATCH:{type(e).__name__}")

    info = plan_cache.cache_info()
    record("smoke/plan_cache", 0.0,
           f"entries={info.get('entries', 0)};hits={info.get('hits', 0)};"
           f"misses={info.get('misses', 0)};tuned={plan_cache.tuned_batches()}")

    # -- auto-selection: steady state must match-or-beat the fixed default --
    def run_with(name):
        with mozart.session(executor=name) as ctx:
            c, p = w.black_scholes(**d)
            np.asarray(c), np.asarray(p)
        return ctx

    plan_cache.clear()
    for name in ("pipelined", "auto"):
        run_with(name)                 # miss: plan
        run_with(name)                 # first hit: tune / measure executors
    pip_us = time_fn(lambda: run_with("pipelined"), warmup=0, iters=3)
    auto_us = time_fn(lambda: run_with("auto"), warmup=0, iters=3)
    picks = {sid: name for e in plan_cache.entries()
             for sid, name in sorted(e.chosen_exec.items())}
    ratio = auto_us / max(pip_us, 1e-9)
    # generous margin: "matches or beats" with headroom for timer noise
    auto_ok = ratio <= 1.5
    record("smoke/auto_vs_pipelined", auto_us,
           f"pipelined_us={pip_us:.0f};ratio={ratio:.2f};picks={picks};"
           f"{'ok' if auto_ok else 'SLOWER'}")
    if not auto_ok:
        failures.append("auto-slower-than-pipelined")

    # -- persistence: a restarted replica replays with zero planner calls ---
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "plans.json")
        saved = plan_cache.save(path)
        plan_cache.clear()
        loaded = plan_cache.load(path)
        ctx = run_with("auto")
        warm_ok = (loaded > 0 and ctx.stats["planner_calls"] == 0
                   and ctx.stats["autotuned_stages"] == 0
                   and ctx.stats["auto_measured_stages"] == 0)
        record("smoke/warm_start", 0.0,
               f"saved={saved};loaded={loaded};"
               f"planner_calls={ctx.stats['planner_calls']};"
               f"tuning_runs={ctx.stats['autotuned_stages']};"
               f"{'ok' if warm_ok else 'COLD'}")
        if not warm_ok:
            failures.append("warm-start")

    # -- cross-stage chunk handoff: interior boundaries stop materializing --
    # One row per stream-capable executor.  The 3-evaluation chain makes
    # every evaluation boundary a producer→consumer edge; with handoff on,
    # INTERIOR boundary bytes must be exactly 0 for every executor —
    # ``fused`` iterates the producer's chunk list, ``scan`` stacks streams
    # into its carry layout, ``pallas`` stacks them into the padded launch
    # buffer.  TERMINAL bytes (the observed output's lazy merge) are
    # reported separately and never gate.  Each row reads the SESSION's
    # scoped counters (``ctx.counters``) — never the process-global
    # aggregate — so concurrent work in the same process cannot pollute
    # the gate; a violation prints a diff-style message naming the
    # offending boundary from the session's materialization event trail.
    from repro.core import stage_exec

    n_h, b_h, evals = 400_000, 65_536, 3
    xh = jnp.linspace(0.0, 1.0, n_h, dtype=jnp.float32)

    def handoff_chain(executor, handoff):
        # pallas stages merge their own outputs to whole arrays, so a
        # pallas-only chain would gate nothing: its row drives a FUSED
        # producer into pallas consumers — the launch-buffer stream-ingest
        # path the gate exists to protect.
        first = "fused" if executor == "pallas" else executor
        with mozart.session(executor=first, batch_elements=b_h,
                            handoff=handoff) as ctx:
            cur = xh
            for i in range(evals):
                cur = w.anp.multiply(w.anp.add(cur, 1.0), 0.5)
                mozart.evaluate()       # stage boundary between evaluations
                if i == 0 and first != executor:
                    mozart.configure(executor=executor)
            out = np.asarray(cur)
        return out, ctx

    import time as _time

    def timed(executor, handoff):
        plan_cache.clear()
        handoff_chain(executor, handoff)        # plan (miss)
        handoff_chain(executor, handoff)        # warm the cache + executables
        out, ctx = handoff_chain(executor, handoff)
        # Scoped view: each chain is one fresh session, so its counters hold
        # exactly this row's boundary traffic — nothing to reset, and other
        # work in the process cannot leak in.
        interior = ctx.counters.bytes_interior()
        terminal = ctx.counters.bytes_terminal()
        events = ctx.counters.materialize_events()
        samples = []
        for _ in range(5):
            t0 = _time.perf_counter()
            handoff_chain(executor, handoff)
            samples.append(_time.perf_counter() - t0)
        return (out, ctx, interior, terminal, events,
                sorted(samples)[len(samples) // 2] * 1e6)

    for h_exec in ("fused", "scan", "pallas"):
        on_out, on_ctx, on_int, on_term, on_events, on_us = timed(h_exec, True)
        off_out, off_ctx, off_int, off_term, _eo, off_us = timed(h_exec, False)
        handoff_failures = []
        if not np.allclose(on_out, off_out, rtol=2e-5):
            handoff_failures.append("parity")
        if on_int != 0:
            # Diff-style report: WHICH boundary materialized, not a bare
            # byte count.
            lines = [f"  - {kind[len('interior:'):]} at {where}: {nb} bytes"
                     for kind, where, nb in on_events
                     if kind.startswith("interior:")]
            print(f"smoke/handoff/{h_exec}: expected 0 interior boundary "
                  f"bytes, got {on_int}:\n" + "\n".join(lines),
                  file=sys.stderr)
            handoff_failures.append(f"interior_bytes={on_int}")
        if off_int + off_term > 0 and on_int + on_term >= off_int + off_term:
            handoff_failures.append("no_traffic_reduction")
        # The row must actually exercise streaming, or interior==0 is
        # vacuous and a broken ingest path would pass the gate.
        if (on_ctx.stats.get("streamed_outputs", 0) == 0
                or on_ctx.stats.get("stream_ingests", 0) == 0):
            handoff_failures.append("no_streaming")
        if on_ctx.stats["planner_calls"] != 0:
            handoff_failures.append("warm_planned")
        # Wall-clock gates only the fused row: the scan/pallas drivers run
        # identically either way (only boundary work differs) and pallas
        # interpret-mode timing is too noisy to gate in CI.
        if h_exec == "fused" and on_us > off_us * 1.15:
            handoff_failures.append("slower_than_merge_path")
        stats = on_ctx.stats
        record(f"smoke/handoff/{h_exec}", on_us,
               f"merge_path_us={off_us:.0f};"
               f"ratio={on_us / max(off_us, 1e-9):.2f};"
               f"interior={on_int};terminal={on_term};"
               f"off_interior={off_int};off_terminal={off_term};"
               f"streamed={stats.get('streamed_outputs', 0)};"
               f"ingests={stats.get('stream_ingests', 0)};"
               f"donated={stats.get('donated_chunks', 0)};"
               f"{'ok' if not handoff_failures else 'REGRESSED'}",
               extra={
                   "interior_bytes": int(on_int),
                   "terminal_bytes": int(on_term),
                   "off_interior_bytes": int(off_int),
                   "off_terminal_bytes": int(off_term),
                   "streamed_outputs": int(stats.get("streamed_outputs", 0)),
                   "stream_ingests": int(stats.get("stream_ingests", 0)),
                   "stream_converted": int(stats.get("stream_converted", 0)),
                   "donated_chunks": int(stats.get("donated_chunks", 0)),
                   "donation_copies": int(stats.get("donation_copies", 0)),
                   "handoff_rechunks": int(stats.get("handoff_rechunks", 0)),
               })
        if handoff_failures:
            failures.append(f"handoff/{h_exec}:{handoff_failures}")

    # -- AOT pipeline: warm calls do ZERO planner calls and ZERO retraces ---
    plan_cache.clear()
    p = mozart.pipeline(lambda: w.black_scholes(**d), executor="auto")
    p.lower()
    p.compile()
    traces_before = stage_exec.trace_count()
    pipe_failures = []
    for i in range(3):
        c, pt = p()
        for g, expect, label in zip((np.asarray(c), np.asarray(pt)), want,
                                    ("call", "put")):
            np.testing.assert_allclose(g, expect, rtol=2e-4, atol=1e-5,
                                       err_msg=f"pipeline run{i} {label}")
        if p.last_call_stats.get("planner_calls", 0):
            pipe_failures.append(f"run{i}-planned")
        if p.last_call_stats.get("jit_traces", 0):
            pipe_failures.append(f"run{i}-retraced")
    record("smoke/pipeline_warm", 0.0,
           f"compiled={p.compiled};warm={p.warm()};"
           f"trace_delta={stage_exec.trace_count() - traces_before};"
           f"planner_calls={p.ctx.stats['planner_calls']};"
           f"{'ok' if not pipe_failures else 'RETRACED'}")
    if pipe_failures:
        failures.append(f"pipeline-warm:{pipe_failures}")

    # -- static graph rewrite: dead-elim, CSE and pushdown fire + pay off ---
    # One chain with one dead stage, one repeated call and one pushdown
    # opportunity.  Gates: all three MZ5xx rewrite records persist in the
    # plan entry, rewritten output is exactly the unrewritten output,
    # interior boundary bytes DROP vs the unrewritten chain (the pushdown
    # shrinks the map's input extent), and the warm (third) call replays the
    # rewritten graph with zero planner calls and zero retraces.
    n_r = 8192
    xr = jnp.linspace(0.1, 1.0, n_r, dtype=jnp.float32)
    dead_mat = jnp.ones((256, n_r), jnp.float32)
    mask_r = np.arange(n_r) % 2 == 0

    def rewrite_chain(x, mask):
        a = w.anp.exp(x)
        # Dead branch: the matvec's 256-row extent forces its own stage, so
        # ``a`` crosses a boundary — eliminating it (plus the cascade into
        # ``a`` itself) removes real interior traffic, not just calls.
        w.anp.matvec(dead_mat, a)
        b1 = w.anp.exp(x)
        b2 = w.anp.exp(x)                # CSE duplicate of b1
        s = w.anp.add(b1, b2)
        m = w.anp.multiply(x, 3.0)
        f = w.anp.compress(mask, m)      # pushdown: m itself is unobserved
        return s, f

    def run_rewrite(on):
        # handoff off so every stage boundary materializes (the saving is
        # visible in isolation); fixed chunking so byte counts are stable.
        with mozart.session(executor="fused", rewrite=on, handoff=False,
                            autotune=False,
                            batch_elements=n_r // 4) as ctx:
            s, f = rewrite_chain(xr, mask_r)
            out = (np.asarray(s.value), np.asarray(f.value))
        return out, ctx

    rewrite_failures = []
    plan_cache.clear()
    (on_s, on_f), rint_on_ctx = run_rewrite(True)
    (off_s, off_f), rint_off_ctx = run_rewrite(False)
    if not (np.array_equal(on_s, off_s) and np.array_equal(on_f, off_f)):
        rewrite_failures.append("parity")
    rw_codes = sorted({r["code"] for e in plan_cache.entries()
                       for r in e.rewrites})
    for code in ("MZ501", "MZ502", "MZ503"):
        if code not in rw_codes:
            rewrite_failures.append(f"missing:{code}")
    rint_on = rint_on_ctx.counters.bytes_interior()
    rint_off = rint_off_ctx.counters.bytes_interior()
    if rint_on >= rint_off:
        rewrite_failures.append(f"interior_not_reduced:{rint_on}>={rint_off}")
    rcalls_on = rint_on_ctx.stats.get("calls", 0)
    rcalls_off = rint_off_ctx.stats.get("calls", 0)
    if rcalls_on >= rcalls_off:
        rewrite_failures.append(f"calls_not_reduced:{rcalls_on}>={rcalls_off}")
    # Warm replay of the rewritten graph: zero planner calls, zero retraces.
    run_rewrite(True)                    # second hit: everything compiled
    rtraces0 = stage_exec.trace_count()
    _, rw_warm_ctx = run_rewrite(True)
    rw_trace_delta = stage_exec.trace_count() - rtraces0
    if rw_warm_ctx.stats["planner_calls"] != 0:
        rewrite_failures.append("warm_planned")
    if rw_trace_delta != 0:
        rewrite_failures.append(f"warm_retraced:{rw_trace_delta}")
    record("smoke/rewrite", 0.0,
           f"codes={','.join(rw_codes)};"
           f"interior_on={rint_on};interior_off={rint_off};"
           f"calls_on={rcalls_on};calls_off={rcalls_off};"
           f"warm_planner_calls={rw_warm_ctx.stats['planner_calls']};"
           f"warm_trace_delta={rw_trace_delta};"
           f"{'ok' if not rewrite_failures else 'REGRESSED'}",
           extra={
               "rewrite_codes": rw_codes,
               "interior_bytes_rewritten": int(rint_on),
               "interior_bytes_unrewritten": int(rint_off),
               "library_calls_rewritten": int(rcalls_on),
               "library_calls_unrewritten": int(rcalls_off),
               "warm_planner_calls":
                   int(rw_warm_ctx.stats["planner_calls"]),
               "warm_trace_delta": int(rw_trace_delta),
               "rewrites_applied":
                   int(rw_warm_ctx.stats.get("rewrites_applied", 0)),
           })
    if rewrite_failures:
        failures.append(f"rewrite:{rewrite_failures}")

    if failures:
        print(f"SMOKE FAILED: {failures}", file=sys.stderr)
        return 1
    return 0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced sizes (CI-friendly)")
    ap.add_argument("--smoke", action="store_true",
                    help="executor-parity + plan-cache + pipeline-warm check; "
                         "nonzero exit on mismatch")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also dump recorded rows as JSON (CI artifact)")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of " + ",".join(MODULES))
    args = ap.parse_args()

    from repro import hardware
    hardware.use_compile_cache()
    header()
    try:
        if args.smoke:
            sys.exit(smoke())

        names = list(MODULES) if not args.only else args.only.split(",")
        failures = []
        for name in names:
            try:
                mod = importlib.import_module(MODULES[name])
                mod.main(quick=args.quick)
            except Exception as e:  # noqa: BLE001 — keep the harness running
                failures.append((name, e))
                traceback.print_exc()
        if failures:
            print(f"FAILED benchmarks: {[n for n, _ in failures]}",
                  file=sys.stderr)
            sys.exit(1)
    finally:
        # Rows recorded so far are dumped even on a failing exit, so the CI
        # artifact exists exactly when the upload step (if: always()) runs.
        if args.json:
            dump_json(args.json)


if __name__ == "__main__":
    main()
