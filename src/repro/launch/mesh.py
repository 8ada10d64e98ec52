"""Production mesh construction.

Kept as functions (never module-level constants) so importing this
module never touches jax device state -- required because dryrun.py must
set XLA_FLAGS before the first jax initialization.

Topology: TPU v5e pods of 256 chips arranged (16, 16) = (data, model);
multi-pod adds a leading 'pod' axis for 2 x 256 = 512 chips. The model
axis stays within a pod (ICI); the pod axis carries only data-parallel
gradient reductions (DCN-friendly), which is where the int8 gradient
compression applies.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _mk_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk_mesh(shape, axes)


def make_host_mesh(
    shape: tuple[int, ...] = (1, 1),
    axes: tuple[str, ...] = ("data", "model"),
) -> Mesh:
    """Small mesh over however many (host) devices exist -- tests."""
    return _mk_mesh(shape, axes)
