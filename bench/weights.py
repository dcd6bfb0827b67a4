"""Random weights from the run's seed, made on the device in one jitted
call, in the type they are served in.

The tree's layout (names and shapes) is the program's parameter interface,
read with ``jax.eval_shape`` so that nothing is allocated or computed by
the program.  The values are the benchmark's own.  The architecture's
``init_leaf`` (``bench/archs``) is asked first; a leaf it leaves (or every
leaf, where there is none) takes the first of these rules that covers it:

- a leaf whose path names a norm: ``1 + 0.1 * N(0, 1)``, so that a norm
  scale applied on the wrong axis, or not at all, shows in the logits;
- the embedding table: ``N(0, 1)``;
- a matrix: ``N(0, 1 / fan_in)``, ``fan_in`` its second-to-last axis.
  Stacked layers (``['reps']``, ``['encoder']``) lead with the layer
  axis, which makes no matrix of a vector.

A leaf that none covers is an error naming its path.

Given ``shardings`` (a tree like the layout), the jitted call writes each
leaf straight into its shards, so no chip ever holds the whole tree.  With
``jax_threefry_partitionable`` (JAX's default) the values are the same
bits however the leaves are sharded.
"""
from __future__ import annotations

import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.tree_util import keystr, tree_flatten_with_path, tree_unflatten

STACKED = ("['reps']", "['encoder']")


def _leaf(key, path: str, sd: jax.ShapeDtypeStruct,
          init_leaf: Optional[Callable] = None):
    if init_leaf is not None:
        v = init_leaf(path, sd, key)
        if v is not None:
            return v
    shape, dtype = sd.shape, sd.dtype
    if "norm" in path:
        return 1 + jax.random.normal(key, shape, dtype) \
            * jnp.asarray(0.1, dtype)
    if path.endswith("['table']"):
        return jax.random.normal(key, shape, dtype)
    if len(shape) - path.startswith(STACKED) >= 2:
        return jax.random.normal(key, shape, dtype) \
            * jnp.asarray(1.0 / math.sqrt(shape[-2]), dtype)
    raise ValueError(
        f"weights: no rule covers leaf {path} {tuple(shape)} {dtype}; "
        f"give its values in init_leaf of the architecture's module "
        f"(bench/archs/<model_type>.py)")


def make_params(layout, seed: int, init_leaf: Optional[Callable] = None,
                shardings=None):
    """Fill ``layout`` (a tree of ``ShapeDtypeStruct``) from ``seed``;
    ``shardings``, a tree like ``layout``, places each leaf."""
    flat, treedef = tree_flatten_with_path(layout)
    paths = [keystr(p) for p, _ in flat]
    sds = [sd for _, sd in flat]

    def build(key):
        return [_leaf(jax.random.fold_in(key, i), paths[i], sds[i],
                      init_leaf)
                for i in range(len(sds))]

    made = jax.jit(build) if shardings is None else jax.jit(
        build, out_shardings=jax.tree.leaves(shardings))
    leaves = made(seed_key(seed))
    return tree_unflatten(treedef, leaves)


def seed_key(seed: int):
    """A JAX key for any whole-number seed: the low 31 bits make the key
    and the rest is folded in, so seeds past 32 bits stay distinct."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0xFFFFFFFF)
