"""Data cleaning: the benchmark's inputs and its plain reference, in
``jax.numpy`` with no chunking, no annotations and nothing of the program.

``make_batch`` draws the column as the repo's seeded generator does;
``reference`` counts and sums the valid values in ``dtype`` (float32 as
configured, bfloat16 for the control); ``compare`` gives the number
checked against its limit.
"""

import math

import jax
import jax.numpy as jnp


def make_batch(key, n):
    kv, kb = jax.random.split(key)
    vals = jax.random.normal(kv, (n,), jnp.float32) * 1e5
    return dict(vals=jnp.where(jax.random.uniform(kb, (n,)) < 0.05, -5.0, vals))


def reference(vals, dtype=jnp.float32):
    v = vals.astype(dtype)
    ok = (v >= 0) & (v <= 1e6) & ~jnp.isnan(v)
    valid = jnp.sum(ok.astype(dtype), dtype=dtype)
    total = jnp.sum(jnp.where(ok, v, 0), dtype=dtype)
    return valid.astype(jnp.float32), total.astype(jnp.float32)


def compare(outs, refs) -> dict:
    """The larger relative gap of the valid count and the total."""
    gaps = []
    for g, r in zip(outs, refs):
        g, r = float(g), float(r)
        gap = abs(g - r) / abs(r) if r else abs(g)
        gaps.append(gap if math.isfinite(gap) else math.inf)
    return {"sum_rel_err": max(gaps)}
