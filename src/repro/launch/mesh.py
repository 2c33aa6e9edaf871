"""Production mesh builders.

Target: TPU v5e pods — 256 chips/pod arranged (data=16, model=16);
multi-pod doubles with a leading 'pod' axis (2, 16, 16) = 512 chips.
A FUNCTION (not a module-level constant) so importing never touches jax
device state.

Every mesh is built with `AxisType.Auto` axes: the engine places its
buffers with GSPMD-style `with_sharding_constraint`, while `jax.make_mesh`
defaults to explicit axes, under which those constraints raise
`ShardingTypeError`.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType


def _auto_mesh(shape, axes, devices=None):
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = int(np.prod(shape))
    devs = jax.devices()
    if len(devs) == need:
        return _auto_mesh(shape, axes)
    if len(devs) > need:   # e.g. single-pod mesh on a 512-device dry-run host
        return _auto_mesh(shape, axes, devs[:need])
    raise RuntimeError(
        f"need {need} devices for mesh {shape}, have {len(devs)} — "
        "run under launch/dryrun.py (it forces 512 host devices)")


def make_debug_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over however many devices the host actually has."""
    devs = jax.devices()[: data * model]
    return _auto_mesh((data, model), ("data", "model"), devs)


def make_host_mesh(*, model: int = 1):
    """(data, model) mesh over ALL visible devices: data = n_devices/model.

    The topology builder for the sharded federation engine off-pod: on a
    laptop it is a 1x1 mesh (the sharded code paths run but every spec
    degrades to replication); under XLA_FLAGS=--xla_force_host_platform_
    device_count=8 (the CI smoke job) it is a real 8-way mesh. `model`
    must divide the device count."""
    n = len(jax.devices())
    if n % model:
        raise ValueError(f"model={model} does not divide {n} devices")
    return make_debug_mesh(n // model, model)
