"""Center-update (segment sum) Pallas TPU kernel.

Computes per-cluster sums and counts from an assignment vector by turning
the scatter into a one-hot matmul per (bn, d) tile, accumulated across the
sequential TPU grid directly into the (k, d) output block. Padded / invalid
points carry ``assign == -1`` and match no one-hot row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.layout import LANE, precision, round_up, row


def _make_kernel(bn: int, kp: int):
    def kernel(x_ref, a_ref, w_ref, sums_ref, cnt_ref):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _init():
            sums_ref[...] = jnp.zeros_like(sums_ref)
            cnt_ref[...] = jnp.zeros_like(cnt_ref)

        x = x_ref[...].astype(jnp.float32)
        rows = jax.lax.broadcasted_iota(jnp.int32, (kp, bn), 0)
        # Weighted one-hot columns (weight 1.0 for the unweighted
        # update); assignments and weights arrive as (1, bn) rows.
        oh = (a_ref[...] == rows).astype(jnp.float32) * w_ref[...]
        # one-hot @ x on the MXU: (kp, bn) x (bn, d) -> (kp, d)
        sums_ref[...] += jax.lax.dot_general(
            oh, x, (((1,), (0,)), ((), ())),
            precision=precision(jnp.float32),
            preferred_element_type=jnp.float32)
        cnt_ref[...] += row(jnp.sum(oh, axis=1, keepdims=True))

    return kernel


def _tiles(n: int, k: int, bn: int):
    """(bn, padded n, padded k): ``bn`` rounds up to whole lane tiles,
    the width of the (1, bn) assignment and weight rows."""
    bn = round_up(bn, LANE)
    return bn, round_up(n, bn), round_up(k, LANE)


def block_plan(n: int, d: int, k: int, *, bn: int = 256,
               dtype: str = "f32") -> dict:
    """Static BlockSpec/grid metadata of :func:`kmeans_update` for the
    §15 kernel checker. The (kp, d) and (1, kp) output blocks have
    grid-constant index maps (the sequential-grid accumulation target),
    so they are resident — single-buffered — for the whole grid."""
    store = "f32" if dtype == "f32" else "bf16"
    bn, np_, kp = _tiles(n, k, bn)
    blk = [
        dict(name="x", shape=(bn, d), dtype=store, kind="in",
             resident=False, array_shape=(np_, d)),
        dict(name="assign", shape=(1, bn), dtype="i32", kind="in",
             resident=False, array_shape=(1, np_)),
        dict(name="weights", shape=(1, bn), dtype="f32", kind="in",
             resident=False, array_shape=(1, np_)),
        dict(name="sums", shape=(kp, d), dtype="f32", kind="out",
             resident=True, array_shape=(kp, d)),
        dict(name="counts", shape=(1, kp), dtype="f32", kind="out",
             resident=True, array_shape=(1, kp)),
    ]
    return dict(kernel="kmeans_update", grid=(np_ // bn,), storage=store,
                accum="f32", blocks=blk)


def kmeans_update(x: jax.Array, assign: jax.Array, k: int,
                  weights: jax.Array | None = None,
                  *, bn: int = 256, interpret: bool | None = None):
    """Per-cluster (weighted) sums/counts. x: (n, d), assign: (n,) int32
    in [-1, k); weights: optional (n,) per-point mass.

    Returns (sums (k, d) f32, counts (k,) f32). Matches
    ``ref.kmeans_update`` (including the optional weights argument).
    ``interpret=None`` uses the ``kernels.ops`` platform auto-detection.
    """
    from repro.kernels import ops
    return _kmeans_update(x, assign, k, weights, bn=bn,
                          interpret=ops.resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("k", "bn", "interpret"))
def _kmeans_update(x, assign, k, weights, *, bn: int, interpret: bool):
    n, d = x.shape
    bn, np_, kp = _tiles(n, k, bn)

    xp = jnp.zeros((np_, d), x.dtype).at[:n].set(x)
    ap = jnp.full((1, np_), -1, jnp.int32).at[0, :n].set(
        assign.astype(jnp.int32))
    w = (jnp.ones((n,), jnp.float32) if weights is None
         else weights.astype(jnp.float32))
    wp = jnp.zeros((1, np_), jnp.float32).at[0, :n].set(w)

    sums, cnt = pl.pallas_call(
        _make_kernel(bn, kp),
        grid=(np_ // bn,),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((1, bn), lambda i: (0, i)),
            pl.BlockSpec((1, bn), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((kp, d), lambda i: (0, 0)),
            pl.BlockSpec((1, kp), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((kp, d), jnp.float32),
            jax.ShapeDtypeStruct((1, kp), jnp.float32),
        ],
        interpret=interpret,
    )(xp, ap, wp)
    return sums[:k], cnt[0, :k]
