"""Production mesh definitions.

Single pod: (16, 16) = 256 chips ("data", "model").
Multi-pod:  (2, 16, 16) = 512 chips ("pod", "data", "model") — the pod axis
carries outer data parallelism (training) / replica groups (serving) over
the inter-pod DCN, while "model" stays inside the pod's ICI domain.

Defined as functions so importing this module never touches jax device
state (device count is locked at first backend init — see dryrun.py).
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (host) devices exist — used by tests."""
    return _make_mesh((data, model), ("data", "model"))
