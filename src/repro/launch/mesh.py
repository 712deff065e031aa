"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before any device query).

``make_mesh`` / ``make_production_mesh`` validate the device count up front
and raise an actionable error (requested shape, found devices, and the
XLA_FLAGS incantation for CPU testing) instead of jax's opaque
"len(devices) != prod(shape)" failure deep inside mesh_utils.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.configs.base import MeshConfig, MULTI_POD, SINGLE_POD, TINY_MESH


def _check_device_count(shape: tuple, axes: tuple) -> None:
    need = 1
    for s in shape:
        need *= s
    have = len(jax.devices())
    if have < need:  # a surplus is fine — jax uses the first `need` devices
        raise ValueError(
            f"requested mesh shape {tuple(shape)} over axes {tuple(axes)} "
            f"needs {need} devices, found {have} "
            f"(hint: XLA_FLAGS=--xla_force_host_platform_device_count={need} "
            f"to emulate {need} devices on CPU)")


def _make(shape: tuple, axes: tuple):
    _check_device_count(shape, axes)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make(shape, axes)


def make_mesh(cfg: MeshConfig):
    return _make(cfg.shape, cfg.axes)


def make_data_mesh(dp: int | None = None):
    """Pure data-parallel mesh ("data", "model") with model axis 1 — the
    multi-device CI topology (dp defaults to every visible device)."""
    dp = dp if dp is not None else len(jax.devices())
    return _make((dp, 1), ("data", "model"))


def mesh_config(name: str) -> MeshConfig:
    return {"single": SINGLE_POD, "multi": MULTI_POD, "tiny": TINY_MESH}[name]
