"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state.
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (smoke tests, elastic reconfigurations)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(n_data: int = 1, n_model: int = 1):
    """Mesh over however many (possibly forced-host) devices exist."""
    n = len(jax.devices())
    assert n_data * n_model <= n, (n_data, n_model, n)
    return make_mesh((n_data, n_model), ("data", "model"))
