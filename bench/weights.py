"""Seeded weights, made on the device by the benchmark.

Every leaf is drawn from a key folded from the run seed, the model
("target" or "draft") and the leaf's path, and for a leaf stacked over
layers from the layer index too.  So the whole tree can be made in one
jitted call, and the plain reference can make any one layer again, alone
and bit for bit, without reading anything the program holds.

Draws, by the leaf's name: a norm ``scale`` is 1 + 0.1 N(0, 1), a bias
``b_*`` 0.02 N, the embedding table ``tok`` 0.02 N, any other matrix
N / sqrt(fan_in) with fan_in its input width (all input axes for the
attention output ``wo``).  Values are drawn in float32 and rounded once
to the leaf's dtype.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

# top-level groups whose leaves carry a leading layer axis
STACKED = ("body", "pre")


def root_key(seed: int, which: str):
    """The key of one model's weights for a run seed of up to 64 bits."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, zlib.crc32(which.encode()) & 0x7FFFFFFF)


def _draw(key, path: str, shape) -> jnp.ndarray:
    name = path.rsplit("/", 1)[-1]
    z = jax.random.normal(key, shape, jnp.float32)
    if name == "scale":
        return 1.0 + 0.1 * z
    if name.startswith("b_"):
        return 0.02 * z
    if name == "tok":
        return 0.02 * z
    fan_in = math.prod(shape[:-1]) if name == "wo" else shape[0]
    return z / math.sqrt(fan_in)


def leaf(root, path: str, shape, dtype, layer=None) -> jnp.ndarray:
    """One leaf (or one layer's slice of a stacked leaf) as the tree holds
    it: drawn in float32, rounded to ``dtype``."""
    key = jax.random.fold_in(root, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    if layer is not None:
        key = jax.random.fold_in(key, layer)
    return _draw(key, path, tuple(shape)).astype(dtype)


def path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def make(shapes, seed: int, which: str):
    """The whole tree for ``shapes`` (a pytree of ShapeDtypeStruct, as
    ``jax.eval_shape`` of the program's init gives it), made in one
    jitted call on the default device."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(root):
        out = []
        for path, s in flat:
            p = path_str(path)
            if p.split("/", 1)[0] in STACKED:
                out.append(jnp.stack([leaf(root, p, s.shape[1:], s.dtype, l)
                                      for l in range(s.shape[0])]))
            else:
                out.append(leaf(root, p, s.shape, s.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    # the key is an argument: a seed closed over would be a constant of
    # the program, and every seed would compile it anew
    return jax.jit(build)(root_key(seed, which))
