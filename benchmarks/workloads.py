"""The paper's evaluation workloads (Table 2) re-expressed over the
annotated libraries.  Each function builds the dataflow lazily under the
ambient Mozart context; callers force the returned futures."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import annotated_numpy as anp
from repro.core import annotated_table as tb
from repro.core import annotated_image as img

INV_SQRT2 = 1.0 / math.sqrt(2.0)


# -- Black Scholes (32 vector ops, paper Listing 1 / Fig 4a,j) ---------------

def black_scholes(price, strike, t, rate, vol):
    rsig = anp.add(rate, anp.multiply(anp.multiply(vol, vol), 2.0))
    vol_sqrt = anp.multiply(vol, anp.sqrt(t))
    d1 = anp.divide(
        anp.add(anp.log(anp.divide(price, strike)), anp.multiply(rsig, t)),
        vol_sqrt)
    d2 = anp.subtract(d1, vol_sqrt)
    nd1 = anp.multiply(anp.add(anp.erf(anp.multiply(d1, INV_SQRT2)), 1.0), 0.5)
    nd2 = anp.multiply(anp.add(anp.erf(anp.multiply(d2, INV_SQRT2)), 1.0), 0.5)
    e_rt = anp.exp(anp.negative(anp.multiply(rate, t)))
    call = anp.subtract(anp.multiply(price, nd1),
                        anp.multiply(anp.multiply(e_rt, strike), nd2))
    put = anp.subtract(
        anp.multiply(anp.multiply(e_rt, strike), anp.subtract(1.0, nd2)),
        anp.multiply(price, anp.subtract(1.0, nd1)))
    return call, put


def black_scholes_data(n, seed=0):
    """``n`` options made on the device from ``PRNGKey(seed)`` (traceable:
    under ``jit`` with ``out_shardings`` it builds them already sharded)."""
    kp, kk, kt, kv = jax.random.split(jax.random.PRNGKey(seed), 4)

    def uniform(key, lo, hi):
        return jax.random.uniform(key, (n,), jnp.float32, lo, hi)

    return dict(
        price=uniform(kp, 10.0, 60.0),
        strike=uniform(kk, 10.0, 60.0),
        t=uniform(kt, 0.5, 2.0),
        rate=jnp.full((n,), 0.02, jnp.float32),
        vol=uniform(kv, 0.1, 0.6),
    )


def black_scholes_np(d):
    p, k, t, r, v = (np.asarray(d[x], np.float64)
                     for x in ("price", "strike", "t", "rate", "vol"))
    from numpy import log, sqrt, exp
    import math as m
    erf = np.vectorize(m.erf)
    rsig = r + v * v * 2.0
    vs = v * sqrt(t)
    d1 = (log(p / k) + rsig * t) / vs
    d2 = d1 - vs
    nd1 = 0.5 * (erf(d1 * INV_SQRT2) + 1)
    nd2 = 0.5 * (erf(d2 * INV_SQRT2) + 1)
    ert = exp(-r * t)
    return p * nd1 - ert * k * nd2, ert * k * (1 - nd2) - p * (1 - nd1)


# -- Haversine (18 ops, Fig 4b,k) --------------------------------------------

def haversine(lat2, lon2, lat1=0.70984286, lon1=1.23892197):  # radians
    miles = 3959.0
    dlat = anp.subtract(lat2, lat1)
    dlon = anp.subtract(lon2, lon1)
    a = anp.add(
        anp.square(anp.sin(anp.multiply(dlat, 0.5))),
        anp.multiply(
            anp.multiply(anp.cos(lat2), math.cos(lat1)),
            anp.square(anp.sin(anp.multiply(dlon, 0.5)))))
    c = anp.multiply(anp.arcsin(anp.sqrt(a)), 2.0)
    return anp.multiply(c, miles)


def haversine_np(lat2, lon2, lat1=0.70984286, lon1=1.23892197):
    lat2, lon2 = np.asarray(lat2, np.float64), np.asarray(lon2, np.float64)
    a = (np.sin((lat2 - lat1) / 2) ** 2
         + np.cos(lat2) * np.cos(lat1) * np.sin((lon2 - lon1) / 2) ** 2)
    return 2 * 3959.0 * np.arcsin(np.sqrt(a))


# -- nBody (pairwise forces; Fig 4c,l) ----------------------------------------

def nbody_step(pos, mass, dt=0.01, eps=1e-3):
    """pos (n,3), mass (n,).  Row-split pairwise force computation."""
    forces = []
    for axis in range(3):
        xi = anp.matmul(pos[:, axis:axis + 1], jnp.ones((1, pos.shape[0]),
                                                        jnp.float32))
        # xi[i, j] = pos[i]; transpose-free difference via broadcast matmul
        xj_row = jnp.asarray(np.asarray(pos[:, axis]))[None, :]
        dx = anp.subtract(xi, xj_row)                       # (n, n) rows split
        forces.append(dx)
    d2 = anp.add(anp.add(anp.square(forces[0]), anp.square(forces[1])),
                 anp.add(anp.square(forces[2]), eps))
    inv_d3 = anp.power(d2, -1.5)
    acc = []
    for axis in range(3):
        f = anp.multiply(anp.multiply(forces[axis], inv_d3),
                         jnp.asarray(np.asarray(mass))[None, :])
        acc.append(anp.sum_axis(anp.negative(f), axis=1))   # (n,)
    return acc


def nbody_np(pos, mass, dt=0.01, eps=1e-3):
    pos = np.asarray(pos, np.float64)
    mass = np.asarray(mass, np.float64)
    d = pos[:, None, :] - pos[None, :, :]
    d2 = (d ** 2).sum(-1) + eps
    inv = d2 ** -1.5
    return [-(d[:, :, a] * inv * mass[None, :]).sum(1) for a in range(3)]


# -- Shallow Water (stencil; Fig 4d,m) ----------------------------------------

def _roll(m, shift, axis):
    return jnp.roll(m, shift, axis)


from repro.core import split_types as _st
from repro.core.annotation import annotate as _annotate

#: whole-array boundary op: input merged ("_"), output re-splittable by rows.
roll = _annotate(_roll, name="roll", static=("shift", "axis"),
                 m=_st._, ret=_st.Along(0))


def shallow_water_step(eta, u, v, g=9.8, dt=0.01, dx=1.0):
    """One explicit step of the 2D shallow-water equations (Bohrium bench).
    Rolls are whole-array stage boundaries; everything else pipelines."""
    detadx = anp.multiply(anp.subtract(roll(eta, -1, 1), roll(eta, 1, 1)),
                          1.0 / (2 * dx))
    detady = anp.multiply(anp.subtract(roll(eta, -1, 0), roll(eta, 1, 0)),
                          1.0 / (2 * dx))
    u2 = anp.subtract(u, anp.multiply(detadx, g * dt))
    v2 = anp.subtract(v, anp.multiply(detady, g * dt))
    dudx = anp.multiply(anp.subtract(roll(u2, -1, 1), roll(u2, 1, 1)),
                        1.0 / (2 * dx))
    dvdy = anp.multiply(anp.subtract(roll(v2, -1, 0), roll(v2, 1, 0)),
                        1.0 / (2 * dx))
    eta2 = anp.subtract(eta, anp.multiply(anp.add(dudx, dvdy), dt))
    return eta2, u2, v2


def shallow_water_np(eta, u, v, g=9.8, dt=0.01, dx=1.0):
    eta, u, v = (np.asarray(x, np.float64) for x in (eta, u, v))
    detadx = (np.roll(eta, -1, 1) - np.roll(eta, 1, 1)) / (2 * dx)
    detady = (np.roll(eta, -1, 0) - np.roll(eta, 1, 0)) / (2 * dx)
    u2 = u - detadx * g * dt
    v2 = v - detady * g * dt
    dudx = (np.roll(u2, -1, 1) - np.roll(u2, 1, 1)) / (2 * dx)
    dvdy = (np.roll(v2, -1, 0) - np.roll(v2, 1, 0)) / (2 * dx)
    return eta - (dudx + dvdy) * dt, u2, v2


# -- Pandas-style (Fig 4e-h) ---------------------------------------------------

def crime_index(table: tb.Table):
    """Fig 4f: per-city crime index = avg(crime*100/pop) over big cities."""
    pop = tb.col(table, "pop")
    crime = tb.col(table, "crime")
    big = anp.greater(pop, 500.0)
    kept = tb.filter_rows(table, big)
    pop2 = tb.col(kept, "pop")
    crime2 = tb.col(kept, "crime")
    idx = anp.divide(anp.multiply(crime2, 100.0), pop2)
    total = anp.sum(idx)
    return total


def crime_index_np(table: tb.Table):
    pop = np.asarray(table.cols["pop"])
    crime = np.asarray(table.cols["crime"])
    m = pop > 500.0
    return (crime[m] * 100.0 / pop[m]).sum()


def data_cleaning(table: tb.Table):
    """Fig 4e: replace broken values with NaN, then count valid per column."""
    return clean_column(tb.col(table, "value"))


def clean_column(vals):
    """The body of ``data_cleaning`` on one column: (valid count, total)."""
    bad = anp.logical_or(anp.less(vals, 0.0), anp.greater(vals, 1e6))
    clean = anp.where(bad, jnp.float32(np.nan), vals)
    valid = anp.sum(anp.where(anp.isnan(clean), 0.0, 1.0))
    total = anp.sum(anp.where(anp.isnan(clean), 0.0, clean))
    return valid, total


def data_cleaning_data(n, seed=0):
    """A column of ``n`` values made on the device from ``PRNGKey(seed)``:
    normal with scale 1e5, and about 5% set to -5 (broken readings)."""
    kv, kb = jax.random.split(jax.random.PRNGKey(seed))
    vals = jax.random.normal(kv, (n,), jnp.float32) * 1e5
    return jnp.where(jax.random.uniform(kb, (n,)) < 0.05, -5.0, vals)


def data_cleaning_np(table: tb.Table):
    return clean_column_np(table.cols["value"])


def clean_column_np(vals):
    v = np.asarray(vals, np.float64)
    bad = (v < 0) | (v > 1e6)
    c = np.where(bad, np.nan, v)
    return float((~np.isnan(c)).sum()), float(np.nansum(c))


def birth_analysis(table: tb.Table):
    """Fig 4g: groupBy aggregation (no pipelined ops, pure parallel agg)."""
    return tb.groupby_agg(table, key="year", val="births", op="sum")


def movielens(ratings: tb.Table, movies: tb.Table):
    """Fig 4h: join + grouped means."""
    joined = tb.join_inner(ratings, movies, on="movie")
    g = tb.groupby_agg(joined, key="movie", val="rating", op="mean")
    return g


# -- ImageMagick (Fig 4n-o) -----------------------------------------------------

def nashville(im):
    a = img.colortone(im, (0.8, 0.2, 0.2), 0.2, True)
    b = img.level(a, 0.02, 0.95)
    c = img.gamma(b, 1.1)
    d = img.modulate(c, 100.0, 150.0, 100.0)
    e = img.contrast(d, 1.1)
    f = img.colortone(e, (0.1, 0.1, 0.5), 0.15, False)
    return f


def gotham(im):
    a = img.modulate(im, 120.0, 10.0, 100.0)
    b = img.colortone(a, (0.13, 0.13, 0.35), 0.3, True)
    c = img.gamma(b, 0.9)
    d = img.contrast(c, 1.4)
    e = img.level(d, 0.05, 0.95)
    return e


def image_pipeline_ref(pipeline, im):
    """Eager reference: run the same ops un-annotated (call .fn directly)."""
    from repro.core import mozart
    with mozart.session(executor="eager"):
        out = pipeline(im)
        return np.asarray(out)
