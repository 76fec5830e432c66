"""Black–Scholes written against the annotated NumPy library: 32 vector
calls per option (the paper's Listing 1).  This is the user code under
test; the benchmark passes it to ``mozart.pipeline``."""

import math

from repro.core import annotated_numpy as anp

INV_SQRT2 = 1.0 / math.sqrt(2.0)


def workload(price, strike, t, rate, vol):
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
