"""The data-cleaning body (the paper's Pandas workload) on one column,
written against the annotated NumPy library: broken readings become NaN,
then the valid values are counted and summed.  This is the user code
under test; the benchmark passes it to ``mozart.pipeline``."""

import jax.numpy as jnp
import numpy as np

from repro.core import annotated_numpy as anp


def workload(vals):
    bad = anp.logical_or(anp.less(vals, 0.0), anp.greater(vals, 1e6))
    clean = anp.where(bad, jnp.float32(np.nan), vals)
    valid = anp.sum(anp.where(anp.isnan(clean), 0.0, 1.0))
    total = anp.sum(anp.where(anp.isnan(clean), 0.0, clean))
    return valid, total
