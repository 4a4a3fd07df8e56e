"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state.  Shapes per the deliverable:

  single pod : (data=16, model=16)              -- 256 chips
  multi-pod  : (pod=2, data=16, model=16)       -- 512 chips

The ``pod`` axis is hierarchical data parallelism by default: gradients
reduce-scatter in-pod over ICI and cross pods over DCI (optionally int8-
compressed, see optim.compress); switching it to a pipeline axis is a
config choice in launch.train.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_debug_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for unit tests (requires >= prod(shape) local devices)."""
    return _mesh(shape, axes)


def hosted_mesh(mesh_shape):
    """The ``--mesh`` shape ('2x2' or (2, 2)) over this process's devices.

    Raises ``ValueError`` when the process has too few devices: a mesh
    that cannot be hosted is an error, never a silent single-device run.
    """
    import math

    from ..search.space import mesh_axis_names, parse_mesh_shape

    if isinstance(mesh_shape, str):
        mesh_shape = parse_mesh_shape(mesh_shape)
    shape = tuple(int(s) for s in mesh_shape)
    if math.prod(shape) > jax.device_count():
        raise ValueError(
            f"mesh {'x'.join(map(str, shape))} needs {math.prod(shape)} "
            f"devices; {jax.device_count()} visible"
        )
    return make_debug_mesh(shape, mesh_axis_names(len(shape)))


def batch_axes(mesh) -> tuple:
    """Mesh axes that carve the global batch (pod+data when present)."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def active_mesh():
    """The mesh the current context runs under (``jax.set_mesh``), or None.

    Returns the abstract mesh, which is what is visible while ``jax.jit``
    traces; ``codegen.bind_mesh`` shard_maps over it and the jitted
    program runs on the concrete devices ``jax.set_mesh`` was given.
    ``ops._tuned_kernel`` consults this to decide whether a
    mesh-qualified plan lookup applies.
    """
    m = jax.sharding.get_abstract_mesh()
    if m.empty or m.size <= 1:
        return None
    return m


def mesh_shape_descriptor(mesh) -> str:
    """'2x4'-style descriptor of a mesh (the plan-key qualifier)."""
    return "x".join(str(int(s)) for s in mesh.axis_sizes)
