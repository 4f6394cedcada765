"""Production mesh construction (assignment-mandated shapes).

single pod : (16, 16)    axes ("data", "model")      = 256 chips
multi pod  : (2, 16, 16) axes ("pod", "data", "model") = 512 chips
one host   : (1, n)      axes ("data", "model")      = n local chips

Defined as a function so importing this module never touches jax device
state.  The dry-run launcher forces 512 host devices via XLA_FLAGS before
any jax import; the single-pod mesh then uses the first 256 devices.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devices)}; "
            "launch via launch/dryrun.py which forces 512 host devices")
    return jax.make_mesh(
        shape, axes,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
        devices=devices[:n])


def make_chip_mesh(n_chips: int):
    """(1, n_chips) ("data", "model") mesh over this host's first n_chips
    devices: tensor parallelism across the chips of one host."""
    devices = jax.devices()
    if len(devices) < n_chips:
        raise RuntimeError(
            f"mesh (1, {n_chips}) needs {n_chips} devices, have {len(devices)}")
    return jax.make_mesh(
        (1, n_chips), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2,
        devices=devices[:n_chips])


def make_host_mesh():
    """Degenerate (1, 1) mesh for CPU smoke tests."""
    return jax.make_mesh(
        (1, 1), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2,
        devices=jax.devices()[:1])
