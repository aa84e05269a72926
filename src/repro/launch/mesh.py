"""Production mesh builders.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run must set
``xla_force_host_platform_device_count`` before any jax initialization.

Every axis is ``AxisType.Auto``: the sharding rules in
``distributed/sharding.py`` place arrays with ``with_sharding_constraint``
and let the partitioner propagate, which only Auto axes allow.
"""
from __future__ import annotations

import os

import jax
from jax.sharding import AxisType


def use_host_devices(n: int) -> None:
    """Give the CPU backend ``n`` devices, for tools that compile for a
    production mesh on the host (run them with ``JAX_PLATFORMS=cpu``).
    Takes effect only before JAX first initialises a backend, so CLIs call
    it at the top of ``main``."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}".strip())


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """(data, model) mesh over this host's devices; the shape must use them
    all, so a run never silently trains on fewer chips than it asked for."""
    n = len(jax.devices())
    if data * model != n:
        raise ValueError(
            f"mesh (data={data}, model={model}) needs {data * model} "
            f"devices; {n} {jax.default_backend()} device(s) present")
    return _mesh((data, model), ("data", "model"))
