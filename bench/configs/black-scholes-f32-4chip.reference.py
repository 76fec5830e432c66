"""Black–Scholes: the benchmark's inputs and its plain reference, in
``jax.numpy`` with no chunking, no annotations and nothing of the program.

``make_batch`` draws the options as the repo's seeded generator does;
``reference`` prices them in ``dtype`` (float32 as configured, bfloat16
for the control); ``compare`` gives the numbers checked against limits.
"""

import math

import jax
import jax.numpy as jnp

INV_SQRT2 = 1.0 / math.sqrt(2.0)


def make_batch(key, n):
    kp, kk, kt, kv = jax.random.split(key, 4)

    def uniform(k, lo, hi):
        return jax.random.uniform(k, (n,), jnp.float32, lo, hi)

    return dict(price=uniform(kp, 10.0, 60.0), strike=uniform(kk, 10.0, 60.0),
                t=uniform(kt, 0.5, 2.0), rate=jnp.full((n,), 0.02, jnp.float32),
                vol=uniform(kv, 0.1, 0.6))


def reference(price, strike, t, rate, vol, dtype=jnp.float32):
    p, k, t, r, v = (x.astype(dtype) for x in (price, strike, t, rate, vol))
    rsig = r + v * v * 2.0
    vol_sqrt = v * jnp.sqrt(t)
    d1 = (jnp.log(p / k) + rsig * t) / vol_sqrt
    d2 = d1 - vol_sqrt
    nd1 = (jax.lax.erf(d1 * INV_SQRT2) + 1.0) * 0.5
    nd2 = (jax.lax.erf(d2 * INV_SQRT2) + 1.0) * 0.5
    e_rt = jnp.exp(-(r * t))
    call = p * nd1 - e_rt * k * nd2
    put = e_rt * k * (1.0 - nd2) - p * (1.0 - nd1)
    return call.astype(jnp.float32), put.astype(jnp.float32)


@jax.jit
def _widest_gap(outs, refs):
    return jnp.max(jnp.stack([
        jnp.max(jnp.where(jnp.isfinite(g), jnp.abs(g - r), jnp.inf))
        for g, r in zip(outs, refs)]))


def compare(outs, refs) -> dict:
    """Widest absolute gap over every option of both prices."""
    return {"price_abs_err": float(_widest_gap(tuple(outs), tuple(refs)))}
