"""The weights of a run, made from ``--seed`` by the benchmark.

Weights are inputs: the benchmark makes them and hands them to the program
(``serve_child`` and ``train_child`` put them where the program's own
initialiser would) and, leaf by leaf, to the plain reference.  Neither side
takes anything the other has made.

A leaf is named by its path in the published architecture
(``layers/attn/wq/kernel``); its values depend on the seed, that name and,
for a stacked leaf, the layer's index, so the reference can make one layer
at a time.  Matrices are N(0, 0.02) rounded to the dtype they are stored
in; norm scales are 1.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

STD = 0.02


def root_key(seed: int) -> jax.Array:
    # the driver's seeds pass 2**31: split them into two 31-bit halves
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def leaf_key(seed_key: jax.Array, name: str) -> jax.Array:
    return jax.random.fold_in(seed_key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def make_leaf(seed_key: jax.Array, name: str, shape, dtype,
              layer=None) -> jax.Array:
    """One leaf, or with `layer` one layer's slice of a stacked leaf
    (`shape` is then the slice's shape)."""
    if name.endswith("scale"):
        return jnp.ones(shape, dtype)
    key = leaf_key(seed_key, name)
    if layer is not None:
        key = jax.random.fold_in(key, layer)
    return (STD * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def path_name(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "name", p)))
                    for p in path)


def make_tree(seed_key: jax.Array, shapes):
    """A whole parameter tree shaped like `shapes` (ShapeDtypeStructs named
    by their paths).  Leaves under ``layers/`` are stacked on a leading
    layer axis and made layer by layer, so that no float32 image of a
    stacked leaf ever exists."""

    def one(path, s):
        name = path_name(path)
        if name.startswith("layers/"):
            return jax.lax.map(
                lambda l: make_leaf(seed_key, name, s.shape[1:], s.dtype, l),
                jnp.arange(s.shape[0]))
        return make_leaf(seed_key, name, s.shape, s.dtype)

    return jax.tree_util.tree_map_with_path(one, shapes)
