"""Production mesh construction (see MULTI-POD DRY-RUN spec).

A FUNCTION, not a module-level constant: importing this module never touches
jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape: tuple, axes: tuple, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``: the sharding rules and
    ``shard_map`` bodies place data, so no op has to carry an explicit
    ``out_sharding`` (JAX 0.9 makes Explicit axes by default)."""
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(shape),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_dev_mesh(data: int = 1, model: int = 1):
    """Small mesh for CPU tests (uses however many devices exist).

    Validates the device count up front — ``jax.make_mesh`` with too few
    devices otherwise surfaces as an opaque XLA reshape failure.
    """
    n = len(jax.devices())
    if data < 1 or model < 1:
        raise ValueError(f"mesh axes must be >= 1, got data={data}, "
                         f"model={model}")
    if data * model > n:
        raise ValueError(
            f"make_dev_mesh(data={data}, model={model}) needs "
            f"{data * model} devices but only {n} are visible — launch with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={data * model}"
            f" (CPU) or shrink the mesh")
    return auto_mesh((data, model), ("data", "model"))


def make_sig_mesh(batch: int | None = None):
    """1-axis mesh for the signature stack: install it with
    ``sharding_ctx(make_sig_mesh())`` and every entry point in
    ``repro.kernels.ops`` shards the "batch" logical axis over it (the
    default rules map "batch" onto the 'data' axis).

    ``batch=None`` uses every visible device.
    """
    n = len(jax.devices())
    if batch is None:
        batch = n
    if batch < 1:
        raise ValueError(f"batch axis must be >= 1, got {batch}")
    if batch > n:
        raise ValueError(
            f"make_sig_mesh(batch={batch}) needs {batch} devices but only "
            f"{n} are visible — launch with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={batch} (CPU)"
            f" or shrink the axis")
    return auto_mesh((batch,), ("data",))
