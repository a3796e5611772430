"""Production mesh construction.

A FUNCTION (never a module-level constant) so importing this module never
touches jax device state — required by the dry-run protocol.

Meshes are built with ``Auto`` axis types. ``jax.make_mesh`` defaults to
``Explicit`` axes, under which every sharded scatter (the adjacency fold
in ``core/executor.apply_batch``, the emitted-mask updates) must name an
``out_sharding``; the executors place state with ``NamedSharding`` and let
the SPMD partitioner propagate, which is the ``Auto`` contract.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 chips per v5e pod; the multi-pod mesh adds a leading 'pod' axis
    (2 pods = 512 chips). Sources sharded over 'pod' need no per-round
    collectives in the RPQ engine (tree independence — DESIGN.md §4)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model_axis: int = 2):
    """Small mesh over whatever devices exist (CPU tests: set
    XLA_FLAGS=--xla_force_host_platform_device_count=8 in the TEST process)."""
    n = len(jax.devices())
    data = max(n // model_axis, 1)
    return _auto_mesh((data, model_axis), ("data", "model"))


def mesh_context(mesh):
    """Scope ``mesh`` as the ambient mesh (``jax.set_mesh``). All in-repo
    mesh-scoped blocks go through here."""
    return jax.set_mesh(mesh)
