"""Production meshes.

Defined as FUNCTIONS (not module constants) so importing this module never
touches jax device state.  Single pod: 16x16 = 256 chips (data, model).
Multi-pod: 2x16x16 = 512 chips (pod, data, model); the pod axis composes
with data for DP (gradient all-reduce crosses the inter-pod links).
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh(n_data: int = 1, n_model: int = 1):
    """Small mesh over whatever devices exist (tests / CPU runs)."""
    n = len(jax.devices())
    n_data = min(n_data, n)
    n_model = max(min(n_model, n // n_data), 1)
    return jax.make_mesh((n_data, n_model), ("data", "model"))


def data_axes_of(mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def dp_extent(mesh) -> int:
    out = 1
    for a in data_axes_of(mesh):
        out *= mesh.shape[a]
    return out


def tp_extent(mesh) -> int:
    return mesh.shape.get("model", 1) if hasattr(mesh.shape, "get") else mesh.shape["model"]
