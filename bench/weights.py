"""Seeded bf16 weights, made on the device in one call.

The family the sizes name (``bench/families/<family>.py``, ``shapes``)
gives every leaf's path, shape and spread, in the layout the serving
program consumes (layer-stacked leaves under ``layers``); the same tree is
what :mod:`bench.reference` reads, so this module imports nothing of the
program.  Leaves are drawn in sorted path order, leaf ``i`` from
``fold_in(key, i)``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import spec


def seed_key(seed: int):
    """A PRNG key for any non-negative whole number, beyond 32 bits too."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        *parents, last = path.split("/")
        node = tree
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return tree


def make(dims: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """All weights, drawn on the default device in one jitted call."""
    leaves = spec.family(dims["family"]).shapes(dims)

    @jax.jit
    def draw(key):
        flat = {}
        for i, (path, (shape, std)) in enumerate(sorted(leaves.items())):
            k = jax.random.fold_in(key, i)
            flat[path] = (jax.random.normal(k, shape, jnp.float32)
                          * std).astype(dtype)
        return _nest(flat)

    return draw(seed_key(seed))
