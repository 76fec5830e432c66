"""Hardware constants for the roofline model and the Mozart batch heuristic.

The TARGET is TPU v5e.  Pallas kernels run compiled on a TPU and in
interpret mode on any other backend (the CPU test tier);
tests/test_tpu_compile.py compiles the main path's kernel for a described
v5e topology, and ``chip_smoke.py`` runs the main path on the chip.
``CHIPS`` maps a device's ``device_kind`` to its constants (``chip_for``).
The paper's batch-size heuristic sizes one pipeline batch to fit in fast
memory: L2 on CPU, VMEM on TPU.
"""

from __future__ import annotations

import dataclasses
import os


@dataclasses.dataclass(frozen=True)
class Chip:
    name: str
    peak_bf16_flops: float      # FLOP/s per chip
    hbm_bandwidth: float        # bytes/s per chip
    ici_link_bandwidth: float   # bytes/s per link
    ici_links: int              # links per chip participating in a collective
    hbm_bytes: int              # HBM capacity per chip
    vmem_bytes: int             # fast scratch memory per core
    # Fraction of fast memory one Mozart pipeline batch should occupy
    # (paper: "C x L2CacheSize", C fixed constant; they found C s.t. batches
    # also leave room for intermediates in the shared LLC).
    mozart_c: float = 0.25
    # Per-dispatch overhead of launching ONE library call from the Python
    # driver loop (jit call + XLA launch).  The cost model weighs this
    # against memory traffic when scoring chunked executors.
    dispatch_overhead_s: float = 50e-6
    # One-time cost of tracing/compiling a new XLA program (scan drivers,
    # fused chains).  Amortized over a session; charged once per stage.
    compile_overhead_s: float = 50e-3

    @property
    def kernel_vmem_limit_bytes(self) -> int:
        """Scoped VMEM limit a Pallas kernel is compiled with: three quarters
        of the fast memory, leaving the rest to the compiler's own scratch."""
        return self.vmem_bytes * 3 // 4


# TPU v5e peaks: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16,
# 16 GB HBM at 819 GB/s, 1,600 Gbit/s chip-to-chip interconnect (4 links).
TPU_V5E = Chip(
    name="tpu_v5e",
    peak_bf16_flops=197e12,
    hbm_bandwidth=819e9,
    ici_link_bandwidth=50e9,
    ici_links=4,                 # 2D torus, 2 axes x 2 directions
    hbm_bytes=16 * 2**30,
    vmem_bytes=128 * 2**20,
)

# The host this container runs on (used only so that the *paper-faithful*
# chunk heuristic is meaningful when benchmarks execute on CPU).  The fast
# tier is modelled as L3-scale rather than L2: unlike the paper's native
# Rust driver, our per-chunk dispatch goes through Python/XLA (~50us), which
# moves the optimal chunk size up by ~2 orders of magnitude — confirmed by
# the Fig 6 batch-size sweep (best ~256k elements on this host).
CPU_HOST = Chip(
    name="cpu_host",
    peak_bf16_flops=1e11,
    hbm_bandwidth=20e9,
    ici_link_bandwidth=10e9,
    ici_links=1,
    hbm_bytes=32 * 2**30,
    vmem_bytes=4 * 2**20,        # L3-scale fast tier (see note above)
    mozart_c=1.0,
)

TARGET = TPU_V5E

#: chip constants keyed by ``jax.Device.device_kind``.
CHIPS = {
    "TPU v5 lite": TPU_V5E,
    "cpu": CPU_HOST,
}


def chip_for(device) -> Chip:
    """The constants of ``device`` (a ``jax.Device``).  A kind the table
    lacks is an error, never a default: wrong peaks would silently skew
    every batch size and roofline derived from them."""
    try:
        return CHIPS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no chip constants for device_kind {device.device_kind!r}; "
            f"known: {sorted(CHIPS)}") from None


#: the checkout this package runs from (``src/repro/hardware.py`` -> root).
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; entry points call this,
    library imports never do.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is
    left alone.  Otherwise the cache lives at ``<checkout>/.jax_cache``: a
    fixed path, because the path is part of what a later run must find."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


# ---------------------------------------------------------------------------
# Online dispatch-overhead calibration
# ---------------------------------------------------------------------------
#
# ``Chip.dispatch_overhead_s`` is a guess baked into a dataclass; the actual
# per-dispatch cost (Python jit-call + XLA launch) varies by an order of
# magnitude across hosts and runtime versions.  The cost model therefore
# blends the constant with a per-process measurement of a tiny jitted no-op:
# the geometric mean keeps the prior's scale when the measurement is noisy
# while still correcting a constant that is wrong by 10x.

_measured_dispatch_s: float | None = None


def measured_dispatch_overhead_s() -> float:
    """Wall seconds of one warm jitted no-op dispatch, measured once per
    process (median of a handful of calls; first call pays one compile)."""
    global _measured_dispatch_s
    if _measured_dispatch_s is None:
        import time

        import jax
        import jax.numpy as jnp

        f = jax.jit(lambda x: x + 1)
        x = jnp.zeros((), jnp.float32)
        jax.block_until_ready(f(x))          # compile outside the timed loop
        samples = []
        for _ in range(7):
            t0 = time.perf_counter()
            jax.block_until_ready(f(x))
            samples.append(time.perf_counter() - t0)
        _measured_dispatch_s = max(sorted(samples)[len(samples) // 2], 1e-9)
    return _measured_dispatch_s


def effective_dispatch_overhead_s(chip: Chip = TARGET) -> float:
    """Per-dispatch overhead the cost model should charge: the chip constant
    blended (geometric mean) with the measured per-process no-op dispatch."""
    import math

    return math.sqrt(chip.dispatch_overhead_s * measured_dispatch_overhead_s())


def fast_memory_bytes(chip: Chip = TARGET) -> int:
    """Size of the 'cache' tier Mozart batches must fit in."""
    return chip.vmem_bytes


def mozart_batch_elements(total_elem_bytes: int, chip: Chip = TARGET) -> int:
    """Paper Section 5.2: batch = C * FastMem / sum(sizeof(element)).

    ``total_elem_bytes`` is the summed per-element byte width across every
    live split value in the stage (inputs + intermediates + outputs).
    """
    if total_elem_bytes <= 0:
        return 1
    n = int(chip.mozart_c * fast_memory_bytes(chip) / total_elem_bytes)
    return max(n, 1)
