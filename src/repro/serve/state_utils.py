"""Batch-dimension surgery on DecodeState pytrees.

DecodeState has three differently-shaped regions:
  * ``pos`` / ``rope_offset``: (B, ...)
  * ``reps``: leaves stacked (R, B, ...) — scan-stacked layer states
  * ``rest``: leaves (B, ...)
so generic tree_map can't slice the batch axis uniformly; these helpers
apply a function to the correct axis per region.
"""
from __future__ import annotations

from typing import Callable, List, Sequence

import jax
import jax.numpy as jnp

from repro.models.transformer import DecodeState


def _map_batch(state: DecodeState, f0: Callable, f1: Callable) -> DecodeState:
    """f0 applied to batch-leading leaves, f1 to scan-stacked (R, B, ...)"""
    return DecodeState(
        pos=f0(state.pos),
        rope_offset=f0(state.rope_offset),
        reps=jax.tree.map(f1, state.reps),
        rest=jax.tree.map(f0, state.rest),
    )


def take(state: DecodeState, idx: Sequence[int]) -> DecodeState:
    i = jnp.asarray(list(idx), jnp.int32)
    return _map_batch(state,
                      lambda x: jnp.take(x, i, axis=0),
                      lambda x: jnp.take(x, i, axis=1))


def concat(states: List[DecodeState]) -> DecodeState:
    if len(states) == 1:
        return states[0]
    first = states[0]
    return DecodeState(
        pos=jnp.concatenate([s.pos for s in states], axis=0),
        rope_offset=jnp.concatenate([s.rope_offset for s in states], axis=0),
        reps=jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=1),
                          *[s.reps for s in states]),
        rest=jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0),
                          *[s.rest for s in states]),
    )


def with_rows(state: DecodeState, n: int) -> DecodeState:
    """The shapes and dtypes of ``state`` with ``n`` rows (nothing is
    allocated)."""
    sds = jax.ShapeDtypeStruct
    return _map_batch(state,
                      lambda x: sds((n,) + x.shape[1:], x.dtype),
                      lambda x: sds(x.shape[:1] + (n,) + x.shape[2:],
                                    x.dtype))


def put(state: DecodeState, rows, src: DecodeState) -> DecodeState:
    """``state`` with row ``rows[i]`` replaced by row ``i`` of ``src``."""
    def at(x, y, lead):
        ix = (slice(None),) * lead + (rows,)
        return x.at[ix].set(y, unique_indices=True)

    return DecodeState(
        pos=at(state.pos, src.pos, 0),
        rope_offset=at(state.rope_offset, src.rope_offset, 0),
        reps=jax.tree.map(lambda x, y: at(x, y, 1), state.reps, src.reps),
        rest=jax.tree.map(lambda x, y: at(x, y, 0), state.rest, src.rest),
    )
