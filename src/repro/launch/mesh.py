"""Production mesh construction.

Single pod: 16 x 16 = 256 chips (data x model).
Multi-pod:  2 x 16 x 16 = 512 chips (pod x data x model) — the 'pod'
axis carries the paper's replica semantics (each pod = one parameter
replica / one "datacenter" for the cost model).

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before any jax
import; tests and benches see the real single device).
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Arbitrary mesh for tests/elastic rescale (every axis Auto)."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def n_pods(mesh) -> int:
    return int(mesh.shape.get("pod", 1))


def devices_required(*, multi_pod: bool = False) -> int:
    return 512 if multi_pod else 256
