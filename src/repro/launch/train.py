"""End-to-end training driver.

CPU-scale runs train a real (reduced or full) config with the full
production stack: pjit + mesh, ZeRO-1 AdamW, SA-annotated data pipeline,
async checkpointing, straggler watchdog, and crash-restart.  The same
driver, pointed at a TPU fleet and the full mesh, is the production
entry point.

    PYTHONPATH=src python -m repro.launch.train --arch gemma-7b --smoke \
        --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse
import functools
import logging
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.ckpt import checkpoint as ckpt
from repro.configs.registry import ARCH_IDS, get_config, get_smoke_config
from repro.data.pipeline import DataPipeline
from repro.launch import shardings as shd
from repro.launch.mesh import data_axes_of, dp_extent, make_host_mesh
from repro.models import lm
from repro.models import transformer as tfm
from repro.models.config import ModelConfig
from repro.optim import adamw
from repro.runtime.fault import FaultConfig, StepTimer, with_retries

log = logging.getLogger("repro.train")


def make_train_step(cfg: ModelConfig, opt_cfg: adamw.AdamWConfig, mesh,
                    p_shard, o_shard, b_shard):
    def step_fn(params, opt_state, batch):
        loss, grads = jax.value_and_grad(lm.loss_fn)(params, batch, cfg)
        new_p, new_s, metrics = adamw.update(params, grads, opt_state, opt_cfg)
        return new_p, new_s, {"loss": loss, **metrics}

    metric_shard = jax.tree_util.tree_map(
        lambda _: NamedSharding(mesh, P()),
        {"loss": 0.0, "lr": 0.0, "grad_norm": 0.0})
    return jax.jit(step_fn, donate_argnums=(0, 1),
                   in_shardings=(p_shard, o_shard, b_shard),
                   out_shardings=(p_shard, o_shard, metric_shard))


def train(cfg: ModelConfig, *, steps: int, batch: int, seq: int,
          ckpt_dir: str | None = None, ckpt_every: int = 20,
          lr: float = 3e-4, seed: int = 0, mesh=None,
          log_every: int = 10, resume: bool = True):
    mesh = mesh or make_host_mesh(n_data=1, n_model=1)
    opt_cfg = adamw.AdamWConfig(lr=lr, total_steps=max(steps, 2),
                                warmup_steps=max(steps // 20, 1))

    params_aval = jax.eval_shape(
        functools.partial(tfm.init_model, cfg=cfg), jax.random.PRNGKey(seed))
    p_specs = shd.param_specs(params_aval, mesh)
    p_shard = shd.named(p_specs, mesh)
    m_specs = shd.zero1_specs(params_aval, mesh)
    o_shard = shd.named(adamw.AdamWState(step=P(), m=m_specs, v=m_specs), mesh)

    pipe = DataPipeline(cfg, batch, seq, seed=seed)
    b0 = pipe.batch_for_step(0)
    b_specs = shd.batch_specs(jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), b0), mesh)
    b_shard = shd.named(b_specs, mesh)

    step_fn = make_train_step(cfg, opt_cfg, mesh, p_shard, o_shard, b_shard)

    # -- init or resume -------------------------------------------------------
    start = 0
    saver = ckpt.AsyncCheckpointer(ckpt_dir) if ckpt_dir else None
    if ckpt_dir and resume and ckpt.latest_step(ckpt_dir) is not None:
        start = ckpt.latest_step(ckpt_dir)
        meta_tree = {"params": params_aval,
                     "opt": jax.eval_shape(adamw.init, params_aval)}
        restored = ckpt.restore(ckpt_dir, start, meta_tree,
                                {"params": p_shard, "opt": o_shard})
        params, opt_state = restored["params"], restored["opt"]
        log.info("resumed from step %d", start)
    else:
        with jax.set_mesh(mesh):
            params = jax.jit(functools.partial(tfm.init_model, cfg=cfg),
                             out_shardings=p_shard)(jax.random.PRNGKey(seed))
            opt_state = jax.jit(adamw.init, out_shardings=o_shard)(params)

    timer = StepTimer(FaultConfig())
    losses = []
    t_start = time.time()
    for step, raw in pipe.iterate(start):
        if step >= steps:
            break
        hbatch = jax.device_put(raw, b_shard)

        def one():
            return step_fn(params, opt_state, hbatch)

        t0 = time.time()
        params, opt_state, metrics = with_retries(one, retries=1)
        loss = float(metrics["loss"])
        timer.record(step, time.time() - t0)
        losses.append(loss)
        if step % log_every == 0 or step == steps - 1:
            log.info("step %d loss %.4f lr %.2e gnorm %.2f (%.2fs)",
                     step, loss, float(metrics["lr"]),
                     float(metrics["grad_norm"]), time.time() - t0)
        if saver and step > 0 and step % ckpt_every == 0:
            saver.save_async(step, {"params": params, "opt": opt_state},
                             meta={"arch": cfg.name})
    pipe.stop()
    if saver:
        saver.save_async(steps, {"params": params, "opt": opt_state},
                         meta={"arch": cfg.name})
        saver.wait()
    wall = time.time() - t_start
    return {"params": params, "opt_state": opt_state, "losses": losses,
            "wall_s": wall, "stragglers": timer.stragglers}


def main():
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--no-resume", action="store_true")
    args = ap.parse_args()

    cfg = (get_smoke_config(args.arch) if args.smoke else get_config(args.arch))
    out = train(cfg, steps=args.steps, batch=args.batch, seq=args.seq,
                ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                lr=args.lr, resume=not args.no_resume)
    print(f"final loss {out['losses'][-1]:.4f} "
          f"(first {out['losses'][0]:.4f}) in {out['wall_s']:.1f}s")


if __name__ == "__main__":
    main()
