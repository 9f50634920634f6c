"""Production mesh builders.

A function (not a module-level constant) so importing never touches jax
device state.  Production target: TPU v5e, 256 chips/pod (16x16), two pods
= 512 chips for the multi-pod dry-run.
"""

from __future__ import annotations

import jax

from repro.models.config import ParallelConfig


def make_mesh(shape, axes):
    """`jax.make_mesh` with Auto axes: the models place activations with
    `with_sharding_constraint`, which jax accepts on Auto axes only
    (`jax.make_mesh` defaults to Explicit ones)."""
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(tuple(shape), tuple(axes), axis_types=auto)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def parallel_config_for(mesh) -> ParallelConfig:
    data_axes = ("pod", "data") if "pod" in mesh.shape else ("data",)
    return ParallelConfig(data_axes=data_axes)


def make_host_mesh(model: int = 1):
    """Single-process debug mesh over the visible devices."""
    n = len(jax.devices())
    data = n // model
    return make_mesh((data, model), ("data", "model"))
