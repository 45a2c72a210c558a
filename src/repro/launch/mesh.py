"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
initialization and only then calls these.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _make_mesh(shape, axes) -> Mesh:
    """jax.make_mesh with Auto axes: the sharding rules place arrays with
    ``NamedSharding`` and leave propagation to the compiler (``make_mesh``
    defaults to Explicit axes)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Single pod: (data=16, model=16) = 256 chips.  Multi-pod: a leading
    pod axis of pure data parallelism, (pod=2, data=16, model=16) = 512."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1) -> Mesh:
    """(data, model) mesh over the first ``data * model`` local devices.
    Raises when fewer devices are visible: a smaller mesh would hide the
    device count from every number measured on it."""
    n = len(jax.devices())
    if data * model > n:
        raise ValueError(f"a ({data}, {model}) mesh needs {data * model} "
                         f"devices; {n} visible")
    return _make_mesh((data, model), ("data", "model"))


def mesh_num_devices(mesh: Mesh) -> int:
    n = 1
    for s in mesh.shape.values():
        n *= s
    return n
