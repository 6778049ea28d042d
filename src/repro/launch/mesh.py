"""Mesh construction: every mesh of the repo is built here.

A v5e pod is 16x16 = 256 chips; the multi-pod config stacks 2 pods (DCN
`pod` axis on the outside, ICI `data`/`model` inside). Defined as
functions so importing this module never touches jax device state.

All axes are `AxisType.Auto`: the sharding rules place activations with
`with_sharding_constraint`, which accepts only Auto axes, while
`jax.make_mesh` defaults to Explicit ones.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

#: the chip the production meshes describe (`jax.Device.device_kind`)
PRODUCTION_DEVICE_KIND = "TPU v5 lite"


def make_mesh(shape, axes, devices=None) -> jax.sharding.Mesh:
    """A mesh of `shape` over `devices` (default: all local devices)
    with Auto axis types."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """`data x model` mesh over whatever devices exist (one host)."""
    n = len(jax.devices())
    if n % model:
        raise ValueError(f"model={model} does not divide {n} devices")
    return make_mesh((n // model, model), ("data", "model"))
