"""Production meshes (single-pod and multi-pod) with optional reordering.

``make_production_mesh()`` builds the assigned meshes:

* single-pod: ``(data=16, model=16)``  — 256 chips (TPU v5e-256 pod)
* multi-pod:  ``(pod=2, data=16, model=16)`` — 512 chips, ``pod`` on DCN

``make_reordered_mesh(plan)`` is the Cloud-Collectives integration point:
it permutes the device array with a solved :class:`MeshPlan` before
constructing the Mesh — the JAX equivalent of feeding the paper's
reordered IP list to an unmodified backend (DESIGN.md §2).

Defined as functions (never at import time) so importing this module
never touches JAX device state.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np


def production_shape(multi_pod: bool = False) -> Tuple[Tuple[int, ...], Tuple[str, ...]]:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return shape, axes


def make_production_mesh(*, multi_pod: bool = False):
    import jax

    shape, axes = production_shape(multi_pod)
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_reordered_mesh(plan, devices: Optional[Sequence] = None):
    """Mesh whose device order follows a solved rank plan (the paper)."""
    import jax
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    devices = np.asarray(devices, dtype=object)
    assert devices.size == plan.flat.size, (devices.size, plan.flat.size)
    arr = devices[plan.flat].reshape(plan.assignment.shape)
    return Mesh(arr, plan.axis_names)


def make_planned_mesh(plan, devices: Optional[Sequence] = None):
    """Mesh from a compiled :class:`repro.plan.Plan` (its N-D mesh plan).

    The plan side is the `repro.plan` subsystem's integration point: the
    planning service compiles (and caches, keyed by fabric fingerprint)
    the mesh assignment together with the per-collective entries, and
    this helper applies the assignment exactly like
    :func:`make_reordered_mesh` applies a bare :class:`MeshPlan`.
    """
    assert plan.mesh_plan is not None, \
        "plan was compiled without mesh_shape; request one from the service"
    return make_reordered_mesh(plan.mesh_plan, devices=devices)


def make_mesh_for_tests(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Small mesh over however many devices the test process has."""
    import jax

    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
