#!/usr/bin/env python3
"""Run the system's main path once on a TPU and check what comes out.

    python3 chip_smoke.py                # one chip
    python3 chip_smoke.py --four-chips   # the sharded path on a 4-chip host

One process holds the chip from start to end, and every input is made on
the device from a seed (``PRNGKey(0)``).  Phases on one chip:

(a) device — platform, kind and count; anything but a TPU fails before any
    other output.
(b) Black–Scholes, 2^27 options: the AOT pipeline
    (``mozart.pipeline(...).lower/compile/__call__``) under ``auto``, and
    sessions under ``scan``, ``fused`` and ``pallas``.  Each is compared
    with the un-annotated library (``eager``: every function runs whole)
    over the whole array, and with the float64 NumPy reference on a seeded
    sample of 2^16 options.  ``erf`` has no Pallas TPU lowering, so under
    ``pallas`` the stage must show up as a counted decline.
(c) the data-cleaning body on a 2^28-value column under ``pallas``: the
    kernel must run with no decline; compared with ``eager`` and with the
    float64 NumPy reference.
(d) rwkv6-1.6b at published widths with random weights: the
    continuous-batching ``Server`` under the ``jit`` and ``mozart`` drivers,
    token for token against fixed-group mode.
(e) after each phase the demotion, halving, quarantine and swallow
    counters must be zero.
(f) the last line of stdout is the JSON result.

``--four-chips`` runs only Black–Scholes at 2^29 options on a 4-device
``data`` mesh under ``sharded``, compared slice by slice with ``eager`` on
the chip that holds each slice, and checks that each output shard lives on
its own device.

Any failed check exits 1 without the result line.  Times printed here are
smoke figures from one run, compilation included: not benchmarks.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

#: ``eager`` parity tolerance (the benchmark harness's executor gate).
RTOL, ATOL = 2e-4, 1e-5
#: float32 pipeline vs float64 NumPy (the repo's end-to-end system test).
F64_RTOL, F64_ATOL = 2e-3, 1e-3

#: per-session counters that must stay zero: a demotion, a quarantine skip
#: or an OOM halving means the path that ran is not the path asked for.
SESSION_COUNTERS = ("exec_demotions", "exec_quarantine_skips",
                    "chunk_oom_halvings")
#: process-wide resilience counters that must stay zero (swallowed errors
#: and the MZ4xx recovery records).
RESILIENCE_COUNTERS = ("swallowed_errors", "MZ402", "MZ403", "MZ404", "MZ406")


@dataclasses.dataclass(frozen=True)
class Sizes:
    bs_n: int = 1 << 27               # Black–Scholes options (5 f32 inputs)
    sample_n: int = 1 << 16           # float64 reference sample
    dc_n: int = 1 << 28               # data-cleaning column (f32)
    arch: str = "rwkv6-1.6b"
    published_widths: bool = True     # get_config, not get_smoke_config
    requests: int = 8
    prompt_len: int = 128
    max_new: int = 32
    batch: int = 4
    four_chip_bs_n: int = 1 << 29


class Phase:
    """One phase's checks and the counters it must leave at zero."""

    def __init__(self, name: str):
        from repro.core import resilience
        self.name = name
        self.failures: list[str] = []
        self.sessions: list = []
        self._resilience0 = dict(resilience.stats)
        self._t0 = time.perf_counter()
        print(f"[{name}] start", flush=True)

    def check(self, ok: bool, what: str) -> bool:
        print(f"[{self.name}] {'ok  ' if ok else 'FAIL'} {what}", flush=True)
        if not ok:
            self.failures.append(what)
        return ok

    def track(self, ctx):
        self.sessions.append(ctx)
        return ctx

    def close(self) -> bool:
        from repro.core import resilience
        counters = {k: sum(int(c.stats.get(k, 0)) for c in self.sessions)
                    for k in SESSION_COUNTERS}
        counters.update({k: int(resilience.stats.get(k, 0))
                         - int(self._resilience0.get(k, 0))
                         for k in RESILIENCE_COUNTERS})
        print(f"[{self.name}] counters {json.dumps(counters)}", flush=True)
        for k, v in counters.items():
            if v:
                self.check(False, f"counter {k} = {v}")
        print(f"[{self.name}] {'passed' if not self.failures else 'FAILED'} "
              f"in {time.perf_counter() - self._t0:.1f}s (compile included)",
              flush=True)
        return not self.failures


def _close_on_device(got, want):
    """(every element within RTOL/ATOL and finite, max abs error), computed
    on the device so whole arrays never cross to the host."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def cmp(g, w):
        err = jnp.abs(g - w)
        ok = (jnp.all(err <= ATOL + RTOL * jnp.abs(w))
              & jnp.all(jnp.isfinite(g)))
        return ok, jnp.max(err)

    ok, err = cmp(got, want)
    return bool(ok), float(err)


def _eager(fn, *args, **kwargs):
    """The un-annotated library: every call runs whole, at once, and an
    intermediate lives as long as the library code holds it."""
    from repro.core import mozart
    with mozart.session(executor="eager", lazy=False):
        return fn(*args, **kwargs)


# ---------------------------------------------------------------------------
# (b) Black–Scholes through every executor family
# ---------------------------------------------------------------------------


def phase_black_scholes(sizes: Sizes, chip) -> bool:
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import workloads as w
    from repro.core import mozart, plan_cache

    ph = Phase("black_scholes")
    n = sizes.bs_n
    d = w.black_scholes_data(n)
    ref = _eager(w.black_scholes, **d)
    idx = jnp.asarray(np.sort(np.random.default_rng(0).choice(
        n, sizes.sample_n, replace=False)))
    sample = {k: np.asarray(jnp.take(v, idx)) for k, v in d.items()}
    want64 = w.black_scholes_np(sample)
    ph.check(all(bool(jnp.all(jnp.isfinite(r))) for r in ref),
             f"eager reference finite, n={n}")

    def compare(label, outs):
        for name, got, want, w64 in zip(("call", "put"), outs, ref, want64):
            ok, err = _close_on_device(got, want)
            ph.check(ok, f"{label} {name} vs eager over {n}: "
                         f"max abs err {err:.3g}")
            got64 = np.asarray(jnp.take(got, idx), np.float64)
            ph.check(np.allclose(got64, w64, rtol=F64_RTOL, atol=F64_ATOL),
                     f"{label} {name} vs float64 NumPy on {sizes.sample_n}: "
                     f"max abs err {np.max(np.abs(got64 - w64)):.3g}")

    plan_cache.clear()
    p = mozart.pipeline(w.black_scholes, executor="auto", chip=chip)
    ph.track(p.ctx)
    p.lower(**d).compile()
    outs = p(**d)
    picks = sorted({name for e in plan_cache.entries()
                    for name in e.chosen_exec.values()})
    ph.check(p.warm(), f"pipeline(auto) warm after compile (picks {picks})")
    compare("pipeline(auto)", outs)
    del outs

    for executor in ("scan", "fused", "pallas"):
        with mozart.session(executor=executor, chip=chip) as ctx:
            ph.track(ctx)
            call, put = w.black_scholes(**d)
            outs = (call.value, put.value)
        compare(executor, outs)
        del call, put, outs
        if executor == "pallas":
            declined = {k: v for k, v in ctx.stats.items()
                        if k.startswith("pallas_declined")}
            ph.check(ctx.stats.get("pallas_declined:erf", 0) >= 1,
                     f"pallas declined the erf stage {declined}")
    return ph.close()


# ---------------------------------------------------------------------------
# (c) the data-cleaning body on the split-pipeline kernel
# ---------------------------------------------------------------------------


def phase_data_cleaning(sizes: Sizes, chip) -> bool:
    import numpy as np

    from benchmarks import workloads as w
    from repro.core import mozart, plan_cache

    ph = Phase("data_cleaning")
    col = w.data_cleaning_data(sizes.dc_n)
    want = [float(x) for x in _eager(w.clean_column, col)]
    want64 = w.clean_column_np(np.asarray(col))
    plan_cache.clear()
    # Two runs: the first plans and launches, the second is the plan
    # cache's first hit, where the tuner compiles and times its blocks.
    for run in ("cold", "tuning"):
        with mozart.session(executor="pallas", chip=chip) as ctx:
            ph.track(ctx)
            got = [float(x) for x in w.clean_column(col)]
        ph.check(ctx.stats.get("pallas_stages", 0) >= 1
                 and ctx.stats.get("pallas_declined", 0) == 0,
                 f"{run}: kernel ran (pallas_stages="
                 f"{ctx.stats.get('pallas_stages', 0)}, declined="
                 f"{ctx.stats.get('pallas_declined', 0)})")
        for name, g, e, e64 in zip(("valid", "total"), got, want, want64):
            ph.check(bool(np.isclose(g, e, rtol=RTOL, atol=ATOL)),
                     f"{run} {name} {g!r} vs eager {e!r}")
            ph.check(bool(np.isclose(g, e64, rtol=RTOL, atol=ATOL)),
                     f"{run} {name} {g!r} vs float64 NumPy {e64!r}")
    shapes = {sid: tuple(s) for e in plan_cache.entries()
              for sid, s in e.block_shape.items()}
    print(f"[data_cleaning] kernel block shapes {shapes}", flush=True)
    return ph.close()


# ---------------------------------------------------------------------------
# (d) serving at published widths
# ---------------------------------------------------------------------------


def _first_divergence(cfg, params, prompt, want, got, max_len):
    """At the first step where two token streams differ: the reference
    logits of both tokens and whether they meet the bf16 bound (two bf16
    spacings at their magnitude: an argmax that rounding can flip)."""
    import jax.numpy as jnp
    import numpy as np

    from repro.models import transformer as tfm

    t = next(i for i, (a, b) in enumerate(zip(want, got)) if a != b)
    toks = jnp.asarray(np.concatenate([prompt, want[:t]])[None], jnp.int32)
    logits, _ = tfm.prefill(params, cfg, tokens=toks,
                            caches=tfm.init_caches(cfg, 1, max_len))
    row = np.asarray(logits[0, -1], np.float32)
    la, lb = float(row[want[t]]), float(row[got[t]])
    bound = 2.0 * 2.0 ** (np.floor(np.log2(max(abs(la), abs(lb), 1e-30))) - 7)
    return t, la, lb, bound, abs(la - lb) <= bound


def phase_serving(sizes: Sizes, chip) -> bool:
    import jax
    import numpy as np

    from repro.configs.registry import get_config, get_smoke_config
    from repro.launch.serve import Request, Server
    from repro.models import transformer as tfm

    ph = Phase("serving")
    cfg = (get_config if sizes.published_widths else get_smoke_config)(
        sizes.arch)
    print(f"[serving] {cfg.name}: {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, vocab {cfg.vocab_size}, {jax.numpy.dtype(cfg.dtype)}",
          flush=True)
    params = tfm.init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, sizes.prompt_len)
               .astype(np.int32) for _ in range(sizes.requests)]
    max_len = sizes.prompt_len + sizes.max_new + 1

    def requests():
        return [Request(rid=i, prompt=p, max_new=sizes.max_new)
                for i, p in enumerate(prompts)]

    fixed = requests()
    Server(cfg, params, sizes.batch, max_len, driver="jit",
           mode="fixed").run(fixed)
    for driver in ("jit", "mozart"):
        srv = Server(cfg, params, sizes.batch, max_len, driver=driver,
                     mode="continuous")
        srv.warmup(sizes.prompt_len)
        reqs = requests()
        stats = srv.run(reqs)
        if driver == "mozart":
            ph.track(srv._batcher._prefill.ctx)
            ph.track(srv._batcher._decode.ctx)
        ph.check(stats["completed"] == sizes.requests
                 and stats["failed"] == 0,
                 f"{driver}: {stats['completed']} of {sizes.requests} "
                 f"requests completed, {stats['failed']} failed")
        for f, r in zip(fixed, reqs):
            if r.out == f.out:
                continue
            t, la, lb, bound, tie = _first_divergence(
                cfg, params, f.prompt, f.out, r.out, max_len)
            ph.check(tie, f"{driver} request {r.rid} differs from fixed mode "
                          f"at step {t}: logit {la!r} (fixed token "
                          f"{f.out[t]}) vs {lb!r} (token {r.out[t]}), bf16 "
                          f"bound {bound!r}")
        ph.check(all(len(r.out) == sizes.max_new for r in reqs),
                 f"{driver}: every request got {sizes.max_new} tokens; "
                 f"{sum(r.out == f.out for f, r in zip(fixed, reqs))} of "
                 f"{sizes.requests} identical to fixed mode")
        ph.check(stats["warm"], f"{driver}: warm serving (planner_calls="
                                f"{stats['planner_calls']}, jit_traces="
                                f"{stats['jit_traces']})")
        print(f"[serving] smoke figure, not a benchmark: driver={driver} "
              f"{stats['tokens_per_s']:.1f} tokens/s, decode p50 "
              f"{stats['decode_p50_us']:.0f} us, p99 "
              f"{stats['decode_p99_us']:.0f} us", flush=True)
    return ph.close()


# ---------------------------------------------------------------------------
# --four-chips: Black–Scholes sharded over a 4-device data mesh
# ---------------------------------------------------------------------------


def phase_four_chips(sizes: Sizes, chip) -> bool:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmarks import workloads as w
    from repro.core import mozart

    ph = Phase("four_chips")
    if not ph.check(len(jax.devices()) == 4,
                    f"{len(jax.devices())} devices (need 4)"):
        return ph.close()
    n = sizes.four_chip_bs_n
    mesh = jax.make_mesh((4,), ("data",))
    rows = NamedSharding(mesh, P("data"))
    d = jax.jit(functools.partial(w.black_scholes_data, n),
                out_shardings=rows)()
    with mozart.session(executor="sharded", mesh=mesh, chip=chip) as ctx:
        ph.track(ctx)
        call, put = w.black_scholes(**d)
        outs = (call.value, put.value)
    del call, put
    quarter = n // 4
    for name, out in zip(("call", "put"), outs):
        shards = out.addressable_shards
        devices = {s.device for s in shards}
        ph.check(len(shards) == 4 and len(devices) == 4
                 and all(s.data.shape == (quarter,) for s in shards),
                 f"{name}: {len(shards)} shards of {quarter} on "
                 f"{len(devices)} distinct devices")
    # eager on one chip cannot hold the whole run's temporaries: compare
    # each slice on the device that holds it.
    by_device = {k: {s.device: s for s in v.addressable_shards}
                 for k, v in d.items()}
    for dev in mesh.devices.flat:
        start = by_device["price"][dev].index[0].start or 0
        ref = _eager(w.black_scholes,
                     **{k: by_device[k][dev].data for k in d})
        for name, out, want in zip(("call", "put"), outs, ref):
            got = next(s for s in out.addressable_shards if s.device == dev)
            same_rows = (got.index[0].start or 0) == start
            ok, err = _close_on_device(got.data, want)
            ph.check(same_rows and ok,
                     f"{name} rows [{start}, {start + quarter}) on {dev}: "
                     f"vs eager max abs err {err:.3g}")
        del ref
    return ph.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only sharded Black–Scholes on a 4-chip host")
    args = ap.parse_args(argv)

    import jax

    from repro import hardware

    cache_dir = hardware.use_compile_cache()
    cache_events = {"hits": 0, "misses": 0}

    def on_event(name, **_kw):
        if name == "/jax/compilation_cache/cache_hits":
            cache_events["hits"] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            cache_events["misses"] += 1

    jax.monitoring.register_event_listener(on_event)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              "this script runs on the chip only", file=sys.stderr)
        return 2
    print(f"device: platform={dev.platform} kind={dev.device_kind!r} "
          f"count={len(devices)}", flush=True)
    chip = hardware.chip_for(dev)
    sizes = Sizes()

    if args.four_chips:
        phases = (phase_four_chips,)
    else:
        phases = (phase_black_scholes, phase_data_cleaning, phase_serving)
    ok = True
    for phase in phases:
        ok = phase(sizes, chip) and ok
    print(f"compile cache: dir={cache_dir} hits={cache_events['hits']} "
          f"misses={cache_events['misses']}", flush=True)
    if not ok:
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
