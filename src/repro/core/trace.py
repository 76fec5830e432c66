"""Spans and counters of Mozart's call path, on the profiler's clock.

A span is a ``jax.profiler.TraceAnnotation``: while a profiler runs, it
lands in the same ``.xplane.pb`` as the device's operations, on the same
clock, so a gap in the device's work can be put down to the program step
that was running; when none runs, entering and leaving one costs about a
microsecond.  There is no recorder and no exporter here: the profiler is
the recorder.  Attributes are given at entry, or with ``set_metadata`` on
the span once they are known.

A warm call opens ``mozart.call`` and, inside it, ``mozart.capture``,
``mozart.evaluate``, ``mozart.plan`` (with ``mozart.rewrite`` and
``mozart.fingerprint``) and ``mozart.force``; each stage opens
``mozart.stage`` (``StageExecutor.run``) with ``mozart.inputs``,
``mozart.layout``, ``mozart.drive`` and ``mozart.merge`` inside it.  No
span is opened per annotated call or per chunk.

One counter serves the same purpose: ``ctx.stats["layout_bytes"]`` is the
bytes of every array an executor builds outside its pinned driver to change
the layout of a stage's inputs or outputs (``count_layout``).  Each
``mozart.stage`` span carries its stage's share of it as the attribute
``layout_bytes``, so a trace holds the count of the calls it covers.
"""

from __future__ import annotations

import jax

SPANS = ("mozart.call", "mozart.capture", "mozart.plan", "mozart.rewrite",
         "mozart.fingerprint", "mozart.evaluate", "mozart.stage",
         "mozart.inputs", "mozart.layout", "mozart.drive", "mozart.merge",
         "mozart.force")


def span(name: str, **attrs) -> jax.profiler.TraceAnnotation:
    """A span named ``name`` (one of ``SPANS``), for a ``with`` block."""
    return jax.profiler.TraceAnnotation(name, **attrs)


def count_layout(ctx, *results) -> None:
    """Add the bytes of ``results`` (arrays, or pytrees of them) to
    ``ctx.stats["layout_bytes"]``: the layout copies one call issues, from
    their shapes, whether or not XLA later elides one."""
    ctx.stats["layout_bytes"] += sum(
        leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(results))
