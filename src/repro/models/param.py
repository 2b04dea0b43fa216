"""Parameter-spec system: declare params as (shape, logical axes, init),
materialize them with a PRNG key, and derive sharding from the same tree.

This keeps model code functional (pure pytrees of jnp arrays), makes
``jax.eval_shape``-based dry-runs trivial, and gives one source of truth
for logical-axis sharding rules.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    """Declarative parameter: shape + logical axis names + init scheme."""
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "normal"        # normal | zeros | ones | embed | scaled
    scale: float = 1.0
    dtype: Any = jnp.float32

    def __post_init__(self):
        if len(self.shape) != len(self.axes):
            raise ValueError(f"shape {self.shape} vs axes {self.axes}")


def is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def _fan_in(shape: Tuple[int, ...]) -> int:
    # Last axis is the output axis by convention (x @ W with W (in, out));
    # for >2D weights everything but the last axis is fan-in.
    if len(shape) <= 1:
        return shape[0] if shape else 1
    return int(np.prod(shape[:-1]))


def init_leaf(key, spec: ParamSpec):
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, spec.dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, spec.dtype)
    if spec.init == "alog":
        # Mamba A_log: log(1..N) along the last axis, tiled over the rest.
        n = spec.shape[-1]
        row = jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))
        return jnp.broadcast_to(row, spec.shape).astype(spec.dtype)
    if spec.init == "embed":
        return (jax.random.normal(key, spec.shape) * 0.02 * spec.scale
                ).astype(spec.dtype)
    # normal / scaled: truncated-normal fan-in scaling.  A leading "layers"
    # stack axis is not part of the fan-in.
    shape = spec.shape[1:] if (spec.axes and spec.axes[0] == "layers") \
        else spec.shape
    std = spec.scale / math.sqrt(_fan_in(shape))
    return (jax.random.truncated_normal(key, -2.0, 2.0, spec.shape) * std
            ).astype(spec.dtype)


def init_params(key, specs, dtype=None):
    """Materialize a pytree of ParamSpec into a pytree of arrays, cast to
    ``dtype`` (a config's ``param_dtype``) leaf by leaf as each is drawn,
    so a bf16 model never holds its whole float32 draw at once."""
    leaves, treedef = jax.tree.flatten(specs, is_leaf=is_spec)
    keys = jax.random.split(key, len(leaves))
    vals = [init_leaf(k, s) if dtype is None
            else init_leaf(k, s).astype(dtype)
            for k, s in zip(keys, leaves)]
    return jax.tree.unflatten(treedef, vals)


def abstract_params(specs):
    """ShapeDtypeStruct pytree matching ``init_params`` (no allocation)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), specs, is_leaf=is_spec)


def logical_axes(specs):
    """Pytree of logical-axis tuples aligned with the param pytree."""
    return jax.tree.map(lambda s: s.axes, specs, is_leaf=is_spec)


def map_specs(fn: Callable[[ParamSpec], Any], specs):
    return jax.tree.map(fn, specs, is_leaf=is_spec)


def count_params(specs) -> int:
    return sum(int(np.prod(s.shape)) for s in
               jax.tree.leaves(specs, is_leaf=is_spec))


def param_bytes(specs, bytes_per_param: int = 4) -> int:
    return count_params(specs) * bytes_per_param


def cast_tree(tree, dtype):
    return jax.tree.map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x,
        tree)
