"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
initialization and only then calls ``make_production_mesh``.
"""
from __future__ import annotations

from typing import Optional

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """(16, 16) ("data", "model") single pod; (2, 16, 16) with a leading
    "pod" axis for the 512-chip two-pod deployment."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_local_mesh():
    """(n_devices, 1) ("data", "model") mesh over whatever devices the
    local runtime actually has — one CPU device on the smoke container,
    all of them under ``--xla_force_host_platform_device_count`` or on
    a real multi-chip host — instead of assuming a topology."""
    return jax.make_mesh((len(jax.devices()), 1), ("data", "model"))


def mesh_for_sweep(n_trials: Optional[int] = None,
                   devices: Optional[int] = None,
                   axis: str = "data"):
    """1-D trial mesh for the sweep fabric (DESIGN.md §11), or ``None``
    for the single-device vmap.

    Picks ``min(devices or all-local-devices, n_trials)`` devices on a
    1-D ``(axis,)`` mesh. Asking for more devices than the runtime has
    raises: a sweep that was meant to shard never quietly runs on
    fewer. ``None`` (rather than a 1-device mesh) tells
    ``sweep_fabric.run_table`` to skip ``shard_map`` entirely; results
    are bit-identical either way."""
    avail = len(jax.devices())
    want = avail if devices is None else int(devices)
    if want > avail:
        raise ValueError(
            f"mesh_for_sweep: {want} devices requested but only {avail} "
            f"present ({jax.devices()[0].platform})")
    if n_trials is not None:
        want = min(want, max(int(n_trials), 1))
    if want <= 1:
        return None
    return jax.make_mesh((want,), (axis,))


def batch_axes(mesh) -> tuple:
    """Mesh axes that shard the batch dimension."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
