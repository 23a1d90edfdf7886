"""2-D vector helpers shared by the Pallas TPU kernels.

Mosaic lays a vector out in (8, 128) tiles: sublanes by lanes. A 1-D
vector, or a cast that moves an axis between lanes and sublanes (a
``[:, None]`` of a lane vector), is refused or relaid by the compiler.
So the kernels keep every vector 2-D: a per-point quantity is a
``(1, n)`` row, a per-center one an ``(m, 1)`` column, and the helpers
below move between the two with a broadcast and an aligned transpose.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

LANE = 128


def round_up(v: int, m: int) -> int:
    return ((v + m - 1) // m) * m


def row(col: jax.Array) -> jax.Array:
    """(m, 1) column -> (1, m) row."""
    return jnp.broadcast_to(col, (col.shape[0], LANE)).T[:1]


def col(row_: jax.Array) -> jax.Array:
    """(1, m) row -> (m, 1) column."""
    return jnp.broadcast_to(row_, (LANE, row_.shape[1])).T[:, :1]


def first_min(d: jax.Array, axis: int):
    """(min, index of the first min) along ``axis``, both kept 2-D.
    Ties resolve to the smallest index, exactly like ``jnp.argmin``."""
    m = jnp.min(d, axis=axis, keepdims=True)
    iota = jax.lax.broadcasted_iota(jnp.int32, d.shape, axis)
    idx = jnp.min(jnp.where(d == m, iota, d.shape[axis]), axis=axis,
                  keepdims=True)
    return m, idx


def precision(dtype):
    """Contraction precision for ``dtype`` operands: f32 contracts at
    full f32 precision (the TPU default rounds f32 operands to bf16);
    bf16 products are exact at the default."""
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None
