"""Production mesh construction (a FUNCTION — importing this module never
touches jax device state).

Single pod: (data=16, model=16) = 256 chips of TPU v5e.
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the `pod` axis is the
DCN-connected data-parallel replica axis.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the shard_map and
    ``with_sharding_constraint`` code of the round engine is written for
    compiler-propagated (not explicit) shardings."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(data: int = 2, model: int = 2, *, pod: int = 0):
    """Small mesh for in-process tests (requires >= data*model host devices)."""
    if pod:
        return _auto_mesh((pod, data, model), ("pod", "data", "model"))
    return _auto_mesh((data, model), ("data", "model"))


def make_engine_mesh(data: int | None = None, model: int | None = None):
    """Best-effort ``(data, model)`` mesh over whatever devices exist.

    The sharded round engine's default: with both factors unset, the device
    count is split into its most square factorization (8 host devices →
    (2, 4); 1 device → (1, 1), which still exercises every sharded code
    path).  Force host devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
    """
    n = jax.device_count()
    if data is None and model is None:
        data = 1
        for f in range(int(n ** 0.5), 0, -1):
            if n % f == 0:
                data = f
                break
        model = n // data
    elif data is None:
        data = n // model
    elif model is None:
        model = n // data
    if data * model > n:
        raise ValueError(f"mesh ({data}, {model}) needs {data * model} devices, have {n}")
    return _auto_mesh((data, model), ("data", "model"))
