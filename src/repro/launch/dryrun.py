"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

MUST be imported/run as a fresh process: the first two lines force 512
placeholder host devices BEFORE jax initializes.  Do not import this module
from tests or benchmarks (they must see the real 1-device CPU).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-7b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] \
        --out results/dryrun
"""

import os
# NOTE: while-loop LICM is disabled because XLA:CPU hoists per-layer
# dtype converts out of the (scan) loops, materializing a full f32 copy of
# the stacked layer carries / KV cache and inflating the reported peak by
# 2-3x (see EXPERIMENTS.md "Dry-run methodology").
os.environ["XLA_FLAGS"] = os.environ.get(
    "DRYRUN_XLA_FLAGS",
    "--xla_force_host_platform_device_count=512 "
    "--xla_disable_hlo_passes=while-loop-invariant-code-motion")

# ruff: noqa: E402  (env var must precede any jax import)
import argparse
import functools
import json
import re
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.registry import ARCH_IDS, get_config
from repro.configs.shapes import SHAPES, applicable, enc_len_for, input_specs
from repro.launch.mesh import data_axes_of, dp_extent, make_production_mesh
from repro.launch import shardings as shd
from repro.models import lm
from repro.models import shard_ctx
from repro.models import transformer as tfm
from repro.models.config import ModelConfig, active_param_count, param_count
from repro.optim import adamw


def runtime_config(cfg: ModelConfig, mesh, shape) -> ModelConfig:
    """Install mesh-dependent runtime knobs on the arch config."""
    me = mesh.shape["model"] if "model" in mesh.axis_names else 1
    return cfg.with_runtime(
        kv_cache_blocks=me,
        moe_groups=int(mesh.devices.size),
        # train uses the blocked (flash-style, rematerialized-bwd) attention;
        # decode attends through the blocked-LSE cache path anyway
        dense_attn_threshold=2048 if shape.kind == "train" else 8192,
        attn_block_k=1024,
        vocab_pad=16 * 16,     # logits shard over TP even for odd vocabs
    )


# ---------------------------------------------------------------------------
# Step builders
# ---------------------------------------------------------------------------


def build_train_step(cfg: ModelConfig, opt_cfg: adamw.AdamWConfig):
    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(lm.loss_fn)(params, batch, cfg)
        new_p, new_s, metrics = adamw.update(params, grads, opt_state, opt_cfg)
        return new_p, new_s, {"loss": loss, **metrics}
    return train_step


def build_prefill_step(cfg: ModelConfig, max_len: int):
    def prefill_step(params, batch, caches):
        kw = {k: v for k, v in batch.items() if k != "tokens"}
        logits, caches = tfm.prefill(params, cfg, tokens=batch.get("tokens"),
                                     caches=caches, **kw)
        return jnp.argmax(logits[:, -1], axis=-1), caches
    return prefill_step


def build_serve_step(cfg: ModelConfig):
    def serve_step(params, token, caches, enc_out=None):
        logits, caches = tfm.decode_step(params, cfg, token, caches,
                                         enc_out=enc_out)
        return jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32), caches
    return serve_step


# ---------------------------------------------------------------------------
# Collective-bytes extraction (for §Roofline)
# ---------------------------------------------------------------------------

_COLLECTIVE_RE = re.compile(
    r"^\s*(?:\S+\s*=\s*)?(?:\([^)]*\)|\S+)\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)",
)

_SHAPE_RE = re.compile(r"(f32|bf16|f16|s32|u32|s8|u8|pred|f64|s64|c64)\[([\d,]*)\]")

_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
                "u8": 1, "pred": 1, "f64": 8, "s64": 8, "c64": 8}


def collective_bytes_from_hlo(hlo_text: str) -> dict:
    """Sum operand bytes of every collective op in compiled HLO text."""
    totals: dict[str, float] = {}
    counts: dict[str, int] = {}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = re.match(r"^(?:%?[\w.\-]+\s*=\s*)(.*)$", stripped)
        body = m.group(1) if m else stripped
        op = None
        for name in ("all-gather-start", "all-reduce-start",
                     "reduce-scatter", "all-to-all", "collective-permute-start",
                     "all-gather", "all-reduce", "collective-permute"):
            if body.startswith(name + "(") or (" " + name + "(") in body[:80] \
                    or body.split("(")[0].strip().endswith(name):
                op = name.replace("-start", "")
                break
        if op is None:
            continue
        # output shapes on the line (result types precede the op name)
        nbytes = 0
        for dt, dims in _SHAPE_RE.findall(stripped.split("(")[0]):
            n = 1
            if dims:
                for d in dims.split(","):
                    if d:
                        n *= int(d)
            nbytes += n * _DTYPE_BYTES[dt]
        totals[op] = totals.get(op, 0) + nbytes
        counts[op] = counts.get(op, 0) + 1
    return {"bytes_by_op": totals, "counts_by_op": counts,
            "total_bytes": sum(totals.values())}


# ---------------------------------------------------------------------------
# One dry-run cell
# ---------------------------------------------------------------------------


def dryrun_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
                save_hlo: str | None = None, cfg_override=None,
                runtime_overrides: dict | None = None) -> dict:
    shape = SHAPES[shape_name]
    base_cfg = cfg_override or get_config(arch)
    ok, reason = applicable(base_cfg, shape)
    rec: dict = {"arch": arch, "shape": shape_name,
                 "mesh": "2x16x16" if multi_pod else "16x16"}
    if not ok:
        rec.update(status="skipped", reason=reason)
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = runtime_config(base_cfg, mesh, shape)
    if runtime_overrides:
        cfg = cfg.with_runtime(**runtime_overrides)
    dpa = data_axes_of(mesh)
    dpe = dp_extent(mesh)
    t0 = time.time()

    params_aval = jax.eval_shape(
        functools.partial(tfm.init_model, cfg=cfg), jax.random.PRNGKey(0))
    p_specs = shd.param_specs(params_aval, mesh)
    p_shard = shd.named(p_specs, mesh)

    # sequence-parallel residual stream
    dp_spec = dpa if len(dpa) > 1 else (dpa[0] if dpa else None)
    bspec_act = dp_spec if shape.global_batch % dpe == 0 else None
    seq_spec = "model" if cfg.seq_shard_residual else None
    shard_ctx.set_residual(NamedSharding(mesh, P(bspec_act, seq_spec, None)))
    if cfg.encdec and cfg.attn is not None:
        me_ = mesh.shape["model"] if "model" in mesh.axis_names else 1
        hspec = "model" if me_ > 1 and cfg.attn.n_kv_heads % me_ == 0 else None
        shard_ctx.set_cross_kv(NamedSharding(
            mesh, P(None, bspec_act, hspec, None, None)))
    if cfg.moe is not None:
        all_axes = tuple(mesh.axis_names)
        shard_ctx.set_moe_groups(NamedSharding(mesh, P(all_axes)))
    if cfg.padded_vocab % (mesh.shape.get("model", 1) if hasattr(mesh.shape, "get") else mesh.shape["model"]) == 0:
        shard_ctx.set_logits(NamedSharding(mesh, P(bspec_act, None, "model")))

    try:
        if shape.kind == "train":
            opt_cfg = adamw.AdamWConfig()
            opt_aval = jax.eval_shape(adamw.init, params_aval)
            o_specs = jax.tree_util.tree_map(
                lambda l, s=None: None, opt_aval)  # placeholder, replaced below
            m_specs = shd.zero1_specs(params_aval, mesh)
            o_specs = adamw.AdamWState(step=P(), m=m_specs, v=m_specs)
            batch_aval = input_specs(cfg, shape)
            b_specs = shd.batch_specs(batch_aval, mesh)
            step = build_train_step(cfg, opt_cfg)
            jitted = jax.jit(
                step,
                donate_argnums=(0, 1),          # params + opt state reuse
                in_shardings=(p_shard, shd.named(o_specs, mesh),
                              shd.named(b_specs, mesh)),
                out_shardings=(p_shard, shd.named(o_specs, mesh),
                               shd.named(jax.tree_util.tree_map(
                                   lambda _: P(), jax.eval_shape(
                                       lambda: {"loss": jnp.float32(0),
                                                "lr": jnp.float32(0),
                                                "grad_norm": jnp.float32(0)})),
                                   mesh)),
            )
            with jax.set_mesh(mesh):
                lowered = jitted.lower(params_aval, opt_aval, batch_aval)
        elif shape.kind == "prefill":
            batch_aval = input_specs(cfg, shape)
            b_specs = shd.batch_specs(batch_aval, mesh)
            caches_aval = jax.eval_shape(functools.partial(
                tfm.init_caches, cfg, shape.global_batch, shape.seq_len))
            c_specs = shd.cache_specs(caches_aval, mesh, cfg)
            step = build_prefill_step(cfg, shape.seq_len)
            tok_spec = P(dp_spec if shape.global_batch % dpe == 0 else None)
            jitted = jax.jit(
                step,
                donate_argnums=(2,),            # caches are consumed
                in_shardings=(p_shard, shd.named(b_specs, mesh),
                              shd.named(c_specs, mesh)),
                out_shardings=(NamedSharding(mesh, tok_spec),
                               shd.named(c_specs, mesh)),
            )
            with jax.set_mesh(mesh):
                lowered = jitted.lower(params_aval, batch_aval, caches_aval)
        else:  # decode
            spec_in = input_specs(cfg, shape)
            caches_aval = jax.eval_shape(functools.partial(
                tfm.init_caches, cfg, shape.global_batch, shape.seq_len))
            c_specs = shd.cache_specs(caches_aval, mesh, cfg)
            bspec = dp_spec if shape.global_batch % dpe == 0 else None
            tok_aval = spec_in["token"]
            step = build_serve_step(cfg)
            donate = (2,)
            in_shardings = [p_shard,
                            NamedSharding(mesh, P(bspec, None)),
                            shd.named(c_specs, mesh)]
            args = [params_aval, tok_aval, caches_aval]
            if cfg.encdec:
                enc_aval = spec_in["enc_out"]
                in_shardings.append(NamedSharding(mesh, P(bspec, None, None)))
                args.append(enc_aval)
            jitted = jax.jit(
                step,
                donate_argnums=donate,          # caches are consumed
                in_shardings=tuple(in_shardings),
                out_shardings=(NamedSharding(mesh, P(bspec, None)),
                               shd.named(c_specs, mesh)),
            )
            with jax.set_mesh(mesh):
                lowered = jitted.lower(*args)

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        coll = collective_bytes_from_hlo(hlo)
        if save_hlo:
            Path(save_hlo).write_text(hlo)

        n_devices = mesh.devices.size
        rec.update(
            status="ok",
            lower_s=round(t_lower, 1),
            compile_s=round(t_compile, 1),
            flops=float(cost.get("flops", 0.0)),
            hlo_bytes=float(cost.get("bytes accessed", 0.0)),
            collective=coll,
            memory=dict(
                argument_bytes=int(getattr(mem, "argument_size_in_bytes", 0)),
                output_bytes=int(getattr(mem, "output_size_in_bytes", 0)),
                temp_bytes=int(getattr(mem, "temp_size_in_bytes", 0)),
                alias_bytes=int(getattr(mem, "alias_size_in_bytes", 0)),
                # donated outputs alias their argument buffers
                peak_bytes=int(
                    getattr(mem, "temp_size_in_bytes", 0)
                    + getattr(mem, "argument_size_in_bytes", 0)
                    + getattr(mem, "output_size_in_bytes", 0)
                    - getattr(mem, "alias_size_in_bytes", 0)),
            ),
            n_devices=int(n_devices),
            params=param_count(base_cfg),
            active_params=active_param_count(base_cfg),
        )
        print(f"[dryrun] {arch} x {shape_name} x {rec['mesh']}: OK "
              f"(lower {t_lower:.0f}s, compile {t_compile:.0f}s, "
              f"temp/device {rec['memory']['temp_bytes']/2**30:.2f} GiB)")
    except Exception as e:  # noqa: BLE001 — recorded as cell failure
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   trace=traceback.format_exc()[-2000:])
        print(f"[dryrun] {arch} x {shape_name}: FAIL {type(e).__name__}: {e}")
    finally:
        shard_ctx.clear()
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--save-hlo", default=None)
    args = ap.parse_args()

    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)

    cells = []
    if args.all:
        for arch in ARCH_IDS:
            for shape in SHAPES:
                cells.append((arch, shape))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all required"
        cells = [(args.arch, args.shape)]

    for arch, shape in cells:
        tag = f"{arch}__{shape}__{'mp' if args.multi_pod else 'sp'}"
        path = outdir / f"{tag}.json"
        if path.exists():
            print(f"[dryrun] {tag}: cached, skipping")
            continue
        rec = dryrun_cell(arch, shape, multi_pod=args.multi_pod,
                          save_hlo=args.save_hlo)
        path.write_text(json.dumps(rec, indent=2))


if __name__ == "__main__":
    main()
