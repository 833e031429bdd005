"""Declarative parameter schemas.

A schema is a nested dict mapping param name -> ``Spec(shape, axes, init)``:

* ``shape``  — global shape
* ``axes``   — logical axis name per dim (see launch/sharding.py for the
               logical->mesh mapping); ``None`` = never sharded
* ``init``   — 'normal' (1/sqrt(fan_in)), 'embed', 'zeros', 'ones',
               'ssm_a', 'ssm_dt'

From one schema we derive: real initialized params (smoke tests / training),
``jax.ShapeDtypeStruct`` stand-ins (dry-run), and PartitionSpecs.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Spec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "normal"
    dtype: Optional[str] = None  # override param dtype

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def is_spec(x) -> bool:
    return isinstance(x, Spec)


def _fan_in(spec: Spec) -> int:
    # Last dim is fan-out by convention; everything else but stacking dims
    # ('layers', 'periods', 'stack') contributes to fan-in.
    fan = 1
    for dim, ax in zip(spec.shape[:-1], spec.axes[:-1]):
        if ax not in ("layers", "periods", "stack"):
            fan *= dim
    return max(fan, 1)


@functools.partial(jax.jit, static_argnums=(0, 2))
def init_one(spec: Spec, key: jax.Array, dtype) -> jax.Array:
    """One initialized leaf, made on the device in its target dtype.

    Jitted so the float32 draw, the scale and the cast fuse into one
    program whose only buffer is the leaf: a stacked bf16 leaf never has
    a float32 twin of its whole size.
    """
    dt = jnp.dtype(spec.dtype or dtype)
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dt)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dt)
    if spec.init == "embed":
        return (0.02 * jax.random.normal(key, spec.shape)).astype(dt)
    if spec.init == "ssm_a":  # A_log: log of A in [1, 16]
        u = jax.random.uniform(key, spec.shape, minval=1.0, maxval=16.0)
        return jnp.log(u).astype(dt)
    if spec.init == "ssm_dt":  # dt_bias: softplus^-1 of dt in [1e-3, 1e-1]
        u = jax.random.uniform(key, spec.shape, minval=math.log(1e-3),
                               maxval=math.log(1e-1))
        dtv = jnp.exp(u)
        return (dtv + jnp.log(-jnp.expm1(-dtv))).astype(dt)
    scale = 1.0 / math.sqrt(_fan_in(spec))
    return (scale * jax.random.normal(key, spec.shape)).astype(dt)


def init_params(schema, key: jax.Array, dtype="float32"):
    leaves, treedef = jax.tree.flatten(schema, is_leaf=is_spec)
    keys = jax.random.split(key, len(leaves))
    vals = [init_one(s, k, dtype) for s, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, vals)


def abstract_params(schema, dtype="float32"):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.dtype(s.dtype or dtype)),
        schema, is_leaf=is_spec)


def param_logical_axes(schema):
    return jax.tree.map(lambda s: s.axes, schema, is_leaf=is_spec)


def count_params(schema) -> int:
    leaves = jax.tree.leaves(schema, is_leaf=is_spec)
    return int(sum(np.prod(s.shape) for s in leaves))
