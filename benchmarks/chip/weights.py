"""Seeded weights, made by the benchmark and handed to the program.

Every leaf is named by its path in the program's parameter tree
(``stack/pos0/ffn/w_gate``) and, for the layer stack, by its layer.  The
values depend only on (seed, path, layer, shape): the program's tree is
built from them under one jit, and the plain reference regenerates the
leaves it needs one layer at a time from the same names, so it never
reads a weight the program holds.

Scales: matrices are N(0, 1/fan_in) with fan_in the second-to-last dim
(the contraction dim of every projection, router and expert weight), the
embedding table N(0, 1), norm scales 1 + 0.1 N(0, 1) (so a norm whose
scale were ignored would show), other vectors 0.1 N(0, 1).
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed, also past 32 bits."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed % (1 << 31))
    return jax.random.fold_in(key, seed >> 31)


def path_name(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def leaf(key: jax.Array, name: str, shape, layer: int = 0) -> jax.Array:
    """The f32 value of one leaf (one layer's slice for stacked leaves)."""
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    k = jax.random.fold_in(k, layer)
    n = jax.random.normal(k, tuple(shape), jnp.float32)
    last = name.rsplit("/", 1)[-1]
    if last == "scale":
        return 1.0 + 0.1 * n
    if last == "table":
        return n
    if len(shape) >= 2:
        return n * np.float32(shape[-2] ** -0.5)
    return 0.1 * n


def build_leaf(name: str, shape, seed_words, dtype):
    """One leaf of the program's tree: ``name`` its path, ``shape`` its
    full shape (stacked leaves, under ``stack``, carry the layer as their
    leading dim)."""
    if name.startswith("stack/"):
        rows = [leaf(seed_words, name, shape[1:], l) for l in range(shape[0])]
        return jnp.stack(rows).astype(dtype)
    return leaf(seed_words, name, shape).astype(dtype)


def build_tree(shapes, seed_words, dtype):
    """The program's parameter tree (``shapes``: its ``eval_shape``),
    filled from the seed (``seed_words``: ``seed_key(seed)``)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, s: build_leaf(path_name(path), s.shape, seed_words, dtype), shapes
    )
