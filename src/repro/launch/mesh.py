"""Device meshes. Functions (not module constants) so importing this
module never touches jax device state."""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def host_device_mesh(n_data: int = 1, n_model: int = 1):
    """Small mesh over however many (host) devices exist — tests/examples."""
    n = n_data * n_model
    assert n <= len(jax.devices()), (n, len(jax.devices()))
    return make_mesh((n_data, n_model), ("data", "model"))
