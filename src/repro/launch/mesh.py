"""Device mesh of one host: tensor parallelism across its chips.

Defined as a function so importing this module never touches jax device
state.
"""
from __future__ import annotations

import jax


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
