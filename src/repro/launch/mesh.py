"""Production mesh builders (multi-pod dry-run contract).

``make_production_mesh`` is a FUNCTION so importing this module never
touches jax device state.  Single pod: (data=16, model=16) = 256 chips.
Multi-pod: a leading pure-DP "pod" axis (2 pods = 512 chips) — the lowest
ICI-pressure placement for the slower inter-pod links (DESIGN.md §5).

A mesh is made ambient with ``jax.set_mesh(mesh)`` (a context manager);
jit then takes bare PartitionSpecs as in/out shardings."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(shape))


def make_smoke_mesh(devices: int | None = None):
    """Tiny mesh over whatever devices exist (tests)."""
    n = devices or len(jax.devices())
    d = max(1, n // 2) if n > 1 else 1
    m = n // d
    return jax.make_mesh((d, m), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
