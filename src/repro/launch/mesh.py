"""Production mesh builders.

Defined as functions (module import never touches jax device state).
The production topology is a TPU v5e pod of 16 x 16 = 256 chips
(axes: data, model) and the multi-pod variant stacks 2 pods on a 'pod'
axis connected by DCN (512 chips).  Axes are logical: ``pods`` scales to
any count for 1000+-node deployments; elastic resume re-shards onto
whatever mesh the restarted job builds.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "make_production_mesh", "make_serve_mesh",
           "make_smoke_mesh", "AXES"]

AXES = ("pod", "data", "model")


def make_mesh(shape, axes, *, devices=None):
    """Every mesh of the repo: ``Auto`` axes, so sharding follows the
    ``in_shardings``/``shard_map`` specs and the propagation rules the
    sharding layer is written against (``jax.make_mesh`` defaults to
    ``Explicit`` axes)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_smoke_mesh(n_data: int = 2, n_model: int = 2):
    """Small mesh over however many (fake) devices a test process has."""
    return make_mesh((n_data, n_model), ("data", "model"))


def make_serve_mesh(n_shards: int | None = None):
    """1-D tensor-parallel serving mesh over the ``model`` axis.

    Serving has no data axis — continuous batching fills one decode batch
    per step and the batch rides every shard — so the serve mesh is just
    ``(n_shards,)`` over ``model``.  ``n_shards=None`` takes every visible
    device (on a forced-host test process that is the
    ``--xla_force_host_platform_device_count`` value)."""
    n = n_shards or len(jax.devices())
    if n > len(jax.devices()):
        raise ValueError(
            f"serve mesh wants {n} shards but only {len(jax.devices())} "
            "devices are visible")
    return make_mesh((n,), ("model",))
