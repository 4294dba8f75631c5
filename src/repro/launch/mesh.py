"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state -- the 512-placeholder-device environment is set up
only by launch/dryrun.py before its first jax import.

Mesh shapes (TPU v5e pods):
  single-pod: (16, 16)      axes ("data", "model")   = 256 chips
  multi-pod:  (2, 16, 16)   axes ("pod", "data", "model") = 512 chips

Axis roles (see launch/sharding.py):
  pod+data -> DP/FSDP (params + batch), sequence sharding for long-context
  model    -> TP (heads / ffn) + EP (experts) + vocab-parallel logits
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape, axis_names, *, devices=None) -> Mesh:
    """``jax.make_mesh`` with ``Auto`` axes -- the one mesh constructor for
    tests, benchmarks, examples and scripts.  ``jax.make_mesh`` defaults to
    ``Explicit`` axes, under which a gather of a sharded array needs an
    ``out_sharding``; every sharded path here is written for ``Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axis_names), devices=devices,
                         axis_types=(AxisType.Auto,) * len(axis_names))


def auto_axes(mesh: Mesh) -> Mesh:
    """The same devices and axis names with ``Auto`` axes (a mesh a caller
    built with ``jax.make_mesh`` has ``Explicit`` ones)."""
    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(devices=None) -> Mesh:
    """Tiny mesh over whatever devices exist (subprocess multi-device tests)."""
    devices = devices or jax.devices()
    n = len(devices)
    if n >= 4:
        dp, tp = n // 2, 2
    else:
        dp, tp = n, 1
    return make_mesh((dp, tp), ("data", "model"), devices=devices[: dp * tp])


def fsdp_axes(mesh: Mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def num_chips(mesh: Mesh) -> int:
    import numpy as np
    return int(np.prod(list(mesh.shape.values())))
