"""Production mesh construction.

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state (smoke tests see 1 CPU device; only dryrun.py
sets the 512-placeholder-device XLA flag before any jax import).

All meshes are built through :func:`repro.compat.make_mesh`, which gives
every axis ``AxisType.Auto``.
"""
from __future__ import annotations

import jax

from repro.compat import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int | None = None, model: int = 1, pod: int = 0):
    """Mesh over this host's devices. ``data`` defaults to every device
    the host has, over ``model`` x ``pod``: ``(len(jax.devices()), 1)``
    with no sizes given, so one chip gets a ``(1, 1)`` mesh and a
    four-chip host a ``(4, 1)`` one."""
    if data is None:
        data = len(jax.devices()) // (model * max(pod, 1))
    if pod:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))
