"""Mesh construction.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state.  `make_production_mesh()` derives its shape
from the devices actually present: ``len(jax.devices())`` is factored
into (data, model) with the model axis the largest divisor not
exceeding sqrt(n), so 8 host devices become a (4, 2) mesh and 256 chips
a (16, 16) pod.  Multi-pod prepends a ``pod`` axis of 2 (an outer
data-parallel dimension whose collectives cross DCN).  Callers modeling
a *specific* production topology (the dry-run's 16×16 v5e pod, the
serving `MeshConfig`) pass ``shape=`` explicitly; an explicit shape
larger than the host raises with the XLA_FLAGS hint.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType


def factor_devices(n: int) -> Tuple[int, int]:
    """Factor n into (data, model) with model the largest divisor of n
    that does not exceed sqrt(n) — so data >= model and data*model == n
    (n=8 -> (4, 2), n=256 -> (16, 16), a prime n -> (n, 1))."""
    model = 1
    for d in range(1, int(n**0.5) + 1):
        if n % d == 0:
            model = d
    return n // model, model


def make_production_mesh(
    *,
    multi_pod: bool = False,
    shape: Optional[Sequence[int]] = None,
    axis_names: Optional[Sequence[str]] = None,
    devices=None,
):
    devices = list(jax.devices()) if devices is None else list(devices)
    if shape is None:
        n_avail = len(devices)
        if multi_pod:
            if n_avail % 2:
                raise RuntimeError(
                    f"multi-pod mesh needs an even device count to split "
                    f"across 2 pods, found {n_avail}"
                )
            data, model = factor_devices(n_avail // 2)
            shape = (2, data, model)
        else:
            data, model = factor_devices(n_avail)
            shape = (data, model)
    shape = tuple(int(s) for s in shape)
    if axis_names is None:
        axis_names = ("pod", "data", "model") if len(shape) == 3 else ("data", "model")
    axis_names = tuple(axis_names)
    if len(axis_names) != len(shape):
        raise ValueError(
            f"mesh shape {shape} has {len(shape)} dims but axis_names="
            f"{axis_names} has {len(axis_names)}"
        )
    n = 1
    for s in shape:
        n *= s
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, found {len(devices)}; the "
            f"dry-run sets XLA_FLAGS=--xla_force_host_platform_device_count"
            f"=512 before importing jax"
        )
    # Auto axes: GSPMD propagates the param/arena shardings through the
    # model code, which carries no sharding annotations of its own
    # (make_mesh's default, Explicit, would demand one per gather)
    return jax.make_mesh(shape, axis_names, devices=devices[:n],
                         axis_types=(AxisType.Auto,) * len(shape))


def data_axes(mesh) -> tuple:
    """The axes batch-like dimensions shard over ('pod' included if present)."""
    names = mesh.axis_names
    return ("pod", "data") if "pod" in names else ("data",)


def axis_size(mesh, axes) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n
