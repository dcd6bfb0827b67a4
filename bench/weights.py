"""Random weights from the run's seed, made on the device in one jitted
call, in the type they are served in.

The tree's layout (names and shapes) is the program's parameter interface,
read with ``jax.eval_shape`` so that nothing is allocated or computed by
the program.  The values are the benchmark's own:

- a leaf whose path names a norm: ``1 + 0.1 * N(0, 1)``, so that a norm
  scale applied on the wrong axis, or not at all, shows in the logits;
- the embedding table: ``N(0, 1)``;
- every other matrix: ``N(0, 1 / fan_in)``, ``fan_in`` its second-to-last
  axis (stacked layers lead with the layer axis).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.tree_util import keystr, tree_flatten_with_path, tree_unflatten


def _leaf(key, path: str, sd: jax.ShapeDtypeStruct):
    shape, dtype = sd.shape, sd.dtype
    z = jax.random.normal(key, shape, dtype)
    if "norm" in path:
        return 1 + z * jnp.asarray(0.1, dtype)
    if path.endswith("['table']"):
        return z
    return z * jnp.asarray(1.0 / math.sqrt(shape[-2]), dtype)


def make_params(layout, seed: int):
    """Fill ``layout`` (a tree of ``ShapeDtypeStruct``) from ``seed``."""
    flat, treedef = tree_flatten_with_path(layout)
    paths = [keystr(p) for p, _ in flat]
    sds = [sd for _, sd in flat]

    def build(key):
        return [_leaf(jax.random.fold_in(key, i), paths[i], sds[i])
                for i in range(len(sds))]

    leaves = jax.jit(build)(seed_key(seed))
    return tree_unflatten(treedef, leaves)


def seed_key(seed: int):
    """A JAX key for any whole-number seed: the low 31 bits make the key
    and the rest is folded in, so seeds past 32 bits stay distinct."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0xFFFFFFFF)
