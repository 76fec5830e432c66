"""Quickstart: split annotations in 60 lines.

Annotate two "library" functions, let Mozart pipeline them through
cache-sized chunks, and inspect the plan.

    PYTHONPATH=src python examples/quickstart.py
"""

import jax.numpy as jnp
import numpy as np

from repro.core import mozart, splittable, Along, Reduce, Generic
from repro.core import annotated_numpy as anp


# --- 1. annotate your own functions (the function bodies are UNMODIFIED) ---

@splittable(x=Along(0), y=Along(0), ret=Along(0), elementwise=True)
def saxpy(x, y):
    return 2.0 * x + y


@splittable(x=Generic("S"), ret=Reduce("add"))
def total(x):
    return jnp.sum(x)


def main():
    x = jnp.arange(1_000_000, dtype=jnp.float32) / 1e6
    y = jnp.ones(1_000_000, jnp.float32)

    # --- 2. run lazily under a Mozart session ------------------------------
    with mozart.session(executor="scan") as ctx:
        a = saxpy(x, y)                # -> Future (nothing ran yet)
        b = anp.exp(a)                 # library ops compose with yours
        c = anp.multiply(b, 0.5)
        s = total(c)

        # --- 3. inspect the plan: one pipelined stage ----------------------
        stages = ctx.last_plan()
        print("plan:", [[n.fn.name for n in st.nodes] for st in stages])

        # --- 4. force evaluation -------------------------------------------
        result = float(s)              # touch -> evaluate

    expected = float(np.sum(np.exp(2 * np.asarray(x) + 1) * 0.5))
    print(f"mozart={result:.2f} expected={expected:.2f}")
    print(f"stats: {dict(ctx.stats)}")
    assert abs(result - expected) / expected < 1e-5

    # --- 5. the AOT pipeline API: lower / compile / call -------------------
    # When the program is fixed (serving replicas), skip per-call planning
    # and retracing entirely: lower once, compile once, then every call only
    # splits, drives the pinned compiled drivers, and merges.
    def program(x, y):
        c = anp.multiply(anp.exp(saxpy(x, y)), 0.5)
        return total(c)

    p = mozart.pipeline(program, executor="auto")
    p.lower(x, y)                      # dataflow graph + plan, no execution
    p.compile()                        # pin batches, executors, executables
    result = float(p(x, y))            # warm: zero planner calls, 0 retraces
    print(f"pipeline={result:.2f} warm={p.warm()} "
          f"last_call={p.last_call_stats}")
    assert p.last_call_stats["jit_traces"] == 0
    assert abs(result - expected) / expected < 1e-5


if __name__ == "__main__":
    main()
