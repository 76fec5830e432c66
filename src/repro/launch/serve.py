"""Batched serving driver: prefill + decode with a request queue.

Two scheduling modes:

* ``--mode continuous`` (default) — ``Server.run`` rides the
  continuous-batching scheduler (``repro.core.serving``): requests join a
  rolling decode batch at step boundaries and leave the moment they finish,
  so a finished request's slot refills immediately instead of decoding dead
  air until the group's ``max(r.max_new)``.
* ``--mode fixed`` — the legacy fixed-group batcher (baseline): requests
  are grouped into fixed decode batches; each group drains fully before the
  next is admitted.  Prompts are left-padded to the group's longest prompt
  and prefill masks the pad keys out of every attention softmax.

Two drivers, orthogonal to the mode:

* ``--driver jit``     — raw ``jax.jit`` around prefill/decode (baseline).
* ``--driver mozart``  — the decode loop rides the AOT pipeline API
  (``mozart.pipeline``): prefill and decode are annotated library calls,
  lowered + compiled ahead of the request loop, and every decode step is a
  warm ``Pipeline.__call__`` (zero planner calls, zero retraces).  With
  ``MOZART_PLAN_CACHE`` set, a restarted replica replays the pinned plan.

``decode_us_per_call`` is honest per-step latency: the timer spans the
decode dispatch AND the host sync on the sampled token (``np.asarray`` of
the argmax), not just the async dispatch.

    PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-1.6b --smoke \
        --requests 8 --batch 4 --prompt-len 16 --max-new 16 --driver mozart
"""

from __future__ import annotations

import argparse
import dataclasses
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import ARCH_IDS, get_config, get_smoke_config
from repro.models import lm
from repro.models import transformer as tfm
from repro.models.config import ModelConfig

log = logging.getLogger("repro.serve")


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray          # (S,) int32
    max_new: int
    out: list = dataclasses.field(default_factory=list)
    done: bool = False


def _mozart_steps(cfg: ModelConfig):
    """Annotate prefill/decode as opaque library calls for the pipeline API.

    Every argument broadcasts ("_" — the values are whole-model state, not
    splittable rows) and the return is ``Unknown`` (logits + caches pytree):
    each step forms its own stage and runs the unmodified jitted function.
    What the pipeline API adds over raw ``jax.jit`` is the lifecycle: the
    plan is resolved ahead of the request loop and persists via the plan
    cache, so a restarted replica's first decode is already planned."""
    from repro.core import annotate
    from repro.core.split_types import Unknown, _

    decode = annotate(
        lambda p, tok, caches: tfm.decode_step(p, cfg, tok, caches),
        name="serve_decode_step", ret=Unknown(), p=_, tok=_, caches=_)
    prefill = annotate(
        lambda p, toks, mask, caches: tfm.prefill(p, cfg, tokens=toks,
                                                  caches=caches,
                                                  pad_mask=mask),
        name="serve_prefill", ret=Unknown(), p=_, toks=_, mask=_, caches=_)
    return prefill, decode


class Server:
    def __init__(self, cfg: ModelConfig, params, batch: int, max_len: int,
                 driver: str = "jit", plan_cache_path: str | None = None,
                 mode: str = "continuous"):
        self.cfg = cfg
        self.params = params
        self.batch = batch
        self.max_len = max_len
        self.driver = driver
        self.mode = mode
        self._batcher = None
        if mode == "continuous":
            from repro.core.serving import ContinuousBatcher
            self._batcher = ContinuousBatcher(
                cfg, params, batch, max_len, driver=driver,
                plan_cache_path=plan_cache_path)
            return
        if driver == "mozart":
            from repro.core import mozart
            prefill_fn, decode_fn = _mozart_steps(cfg)
            self._prefill = mozart.pipeline(
                prefill_fn, executor="eager", plan_cache_path=plan_cache_path)
            self._decode = mozart.pipeline(
                decode_fn, executor="eager", plan_cache_path=plan_cache_path)
        else:
            self._decode = jax.jit(
                lambda p, tok, caches: tfm.decode_step(p, cfg, tok, caches))
            self._prefill = jax.jit(
                lambda p, toks, mask, caches: tfm.prefill(
                    p, cfg, tokens=toks, caches=caches, pad_mask=mask))

    def warmup(self, prompt_len: int) -> None:
        """AOT: lower + compile the pipelines before the first request."""
        if self.mode == "continuous":
            if self._batcher.pad_free:
                self._batcher.warmup(prompt_lens=[prompt_len])
            else:
                self._batcher.warmup(max_prompt_len=prompt_len)
            return
        if self.driver != "mozart":
            return
        caches = tfm.init_caches(self.cfg, self.batch, self.max_len)
        toks = jnp.zeros((self.batch, prompt_len), jnp.int32)
        mask = jnp.ones((self.batch, prompt_len), bool)
        logits, caches = self._prefill.lower(self.params, toks, mask, caches) \
                                      .compile()(self.params, toks, mask,
                                                 caches)
        tok = jnp.zeros((self.batch, 1), jnp.int32)
        self._decode.lower(self.params, tok, caches).compile()

    def run(self, requests: list[Request]) -> dict:
        if self.mode == "continuous":
            return self._run_continuous(requests)
        return self._run_fixed(requests)

    def _run_continuous(self, requests: list[Request]) -> dict:
        from repro.core.serving import ServeRequest
        sreqs = [ServeRequest(rid=r.rid, prompt=np.asarray(r.prompt, np.int32),
                              max_new=r.max_new) for r in requests]
        stats = self._batcher.run(sreqs)
        for r, s in zip(requests, sreqs):
            r.out[:] = s.out
            r.done = True
        if self.driver == "mozart":
            stats["decode_warm"] = self._batcher._decode.warm()
            stats["decode_last_call"] = dict(
                self._batcher._decode.last_call_stats)
        return stats

    def _run_fixed(self, requests: list[Request]) -> dict:
        t0 = time.time()
        queue = list(requests)
        tokens_out = 0
        decode_calls = 0
        decode_s = 0.0
        while queue:
            group = queue[: self.batch]
            queue = queue[self.batch:]
            # pad group to fixed batch
            while len(group) < self.batch:
                group.append(Request(rid=-1, prompt=group[0].prompt,
                                     max_new=group[0].max_new))
            plen = max(len(r.prompt) for r in group)
            # left-pad to the group's longest prompt; the mask keeps the pad
            # keys out of every attention softmax and out of the KV cache's
            # valid span (True = real token).
            prompts = np.stack([
                np.pad(r.prompt, (plen - len(r.prompt), 0)) for r in group])
            mask = np.stack([
                np.arange(plen) >= plen - len(r.prompt) for r in group])
            caches = tfm.init_caches(self.cfg, self.batch, self.max_len)
            logits, caches = self._prefill(self.params,
                                           jnp.asarray(prompts, jnp.int32),
                                           jnp.asarray(mask),
                                           caches)
            tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
            steps = max(r.max_new for r in group)
            for _ in range(steps):
                for r, t in zip(group, np.asarray(tok)[:, 0]):
                    if r.rid >= 0 and not r.done:
                        r.out.append(int(t))
                        tokens_out += 1
                        if len(r.out) >= r.max_new:
                            r.done = True
                # time through the host sync on the sampled token: dispatch
                # alone would report async-enqueue cost, not decode latency.
                td = time.perf_counter()
                logits, caches = self._decode(self.params, tok, caches)
                tok_host = np.asarray(
                    jnp.argmax(logits[:, -1], axis=-1)).astype(np.int32)
                decode_s += time.perf_counter() - td
                tok = jnp.asarray(tok_host)[:, None]
                decode_calls += 1
        wall = time.time() - t0
        stats = {"wall_s": wall, "tokens": tokens_out,
                 "tokens_per_s": tokens_out / max(wall, 1e-9),
                 "decode_us_per_call": decode_s * 1e6 / max(decode_calls, 1)}
        if self.driver == "mozart":
            stats["decode_warm"] = self._decode.warm()
            stats["decode_last_call"] = dict(self._decode.last_call_stats)
        return stats


def main():
    logging.basicConfig(level=logging.INFO)
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--driver", choices=("jit", "mozart"), default="jit")
    ap.add_argument("--mode", choices=("continuous", "fixed"),
                    default="continuous")
    ap.add_argument("--plan-cache", default=None,
                    help="plan-cache path for --driver mozart (also honours "
                         "MOZART_PLAN_CACHE)")
    args = ap.parse_args()

    from repro import hardware
    hardware.use_compile_cache()
    cfg = (get_smoke_config(args.arch) if args.smoke else get_config(args.arch))
    params = tfm.init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size, args.prompt_len),
                    max_new=args.max_new)
            for i in range(args.requests)]
    srv = Server(cfg, params, args.batch,
                 max_len=args.prompt_len + args.max_new + 1,
                 driver=args.driver, plan_cache_path=args.plan_cache,
                 mode=args.mode)
    srv.warmup(args.prompt_len)
    stats = srv.run(reqs)
    print(f"served {stats['tokens']} tokens in {stats['wall_s']:.2f}s "
          f"({stats['tokens_per_s']:.1f} tok/s, "
          f"{stats['decode_us_per_call']:.0f}us/decode, driver={args.driver}, "
          f"mode={args.mode})")
    if args.mode == "continuous":
        print(f"decode p50={stats['decode_p50_us']:.0f}us "
              f"p99={stats['decode_p99_us']:.0f}us  "
              f"request p50={stats['request_p50_ms']:.1f}ms "
              f"p99={stats['request_p99_ms']:.1f}ms  "
              f"occupancy={stats['mean_occupancy']:.2f}")
    if args.driver == "mozart":
        print(f"decode warm={stats['decode_warm']} "
              f"last_call={stats['decode_last_call']}")


if __name__ == "__main__":
    main()
