"""Production mesh construction (brief: MULTI-POD DRY-RUN step 1).

A FUNCTION, not a module-level constant — importing this module never
touches jax device state. Single-pod: (data=16, model=16) = 256 chips;
multi-pod: (pod=2, data=16, model=16) = 512 chips. ``pod`` and ``data``
jointly form the FSDP/batch axes; ``model`` is TP/EP.

Use ``with jax.set_mesh(mesh):`` around lowering — that installs the
mesh that repro.parallel.sharding reads.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Arbitrary mesh for experiments (e.g. scaling the pod axis), every
    axis of type Auto."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
