"""Production mesh construction (assignment-mandated shapes).

Defined as functions — importing this module never touches jax device
state, so smoke tests keep their single CPU device.

Every mesh has Auto axes: the model code places activations with
``with_sharding_constraint`` and leaves gathers to the partitioner, which
``jax.make_mesh``'s default Explicit axes refuse.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh

__all__ = ["make_production_mesh", "make_local_mesh"]


def _auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 single pod (256 chips) or 2x16x16 two-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1) -> Mesh:
    """data x model mesh over the local devices (tests/examples)."""
    n = len(jax.devices())
    if data * model > n:
        raise ValueError(f"a {data}x{model} mesh needs {data * model} "
                         f"devices; {n} available")
    return _auto_mesh((data, model), ("data", "model"))
