"""Seeded weights, made on the device in one jitted call.

A reference module describes its parameters as a tree of ``Leaf``s in the
layout the program takes them in.  ``make`` draws every leaf from the seed
in one compiled program, in the type the configuration serves them in, so
the program and the reference see the same numbers and neither made them.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp


class Leaf(NamedTuple):
    shape: tuple
    kind: str = "normal"      # normal | gain | alog | head
    std: float = 1.0
    real: int = 0             # head: columns past ``real`` (vocab padding) are 0


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, also one past 32 bits."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def _draw(leaf: Leaf, key) -> jax.Array:
    shape = tuple(leaf.shape)
    if leaf.kind == "normal":
        return jax.random.normal(key, shape, jnp.float32) * leaf.std
    if leaf.kind == "gain":        # norm gains and skip weights near ``std``
        return leaf.std * (1.0 + 0.1 * jax.random.normal(key, shape,
                                                         jnp.float32))
    if leaf.kind == "alog":        # Mamba's A init: log(1..n) over the state
        n = shape[-1]
        base = jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))
        return base + 0.1 * jax.random.normal(key, shape, jnp.float32)
    if leaf.kind == "head":        # (d, vocab_padded); padded ids never win
        w = jax.random.normal(key, shape, jnp.float32) * leaf.std
        keep = jnp.arange(shape[-1]) < leaf.real
        return jnp.where(keep, w, 0.0)
    raise ValueError(f"unknown leaf kind {leaf.kind!r}")


def is_leaf(x) -> bool:
    return isinstance(x, Leaf)


def make(layout, seed: int, dtype, out_shardings=None):
    """Every leaf of ``layout`` drawn from ``seed``, as ``dtype`` arrays."""
    leaves, treedef = jax.tree.flatten(layout, is_leaf=is_leaf)

    def build(key):
        return [_draw(lf, jax.random.fold_in(key, i)).astype(dtype)
                for i, lf in enumerate(leaves)]

    shard = (None if out_shardings is None
             else jax.tree.leaves(out_shardings))
    arrs = jax.jit(build, out_shardings=shard)(seed_key(seed))
    return jax.tree.unflatten(treedef, arrs)


def n_params(layout) -> int:
    return sum(math.prod(lf.shape)
               for lf in jax.tree.leaves(layout, is_leaf=is_leaf))
