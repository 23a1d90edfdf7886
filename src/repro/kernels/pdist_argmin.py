"""Fused pairwise-distance + argmin Pallas TPU kernel.

The assignment step of Lloyd's method (the compute hot-spot of both
Algorithm 1 and the one-round server Lloyd of k-FED) is matmul-shaped:

    d(i, r) = ||x_i||^2 - 2 x_i . c_r + ||c_r||^2

We tile (n, d) into (bn, bd) VMEM blocks and the center axis into bk
blocks, drive the -2 c @ x^T term through the MXU (128-aligned tiles),
accumulate partial dot products over d-blocks in a (bk, bn) VMEM scratch
accumulator (centers on sublanes, points on lanes), and fuse the argmin
so the (n, k) distance matrix never round-trips to HBM. The per-point
running (idx, val) best lives in the output block (resident across the
k/d grid axes), so VMEM usage is fixed at O(bn * (bd + bk)) regardless
of k — large-k center sets (the induced labeling of a production round
with thousands of retained centers) stream through in tiles instead of
materializing one (k, bn) scratch. Outputs are the assignment indices
and the min squared distance per point, written as lane-dense (1, n)
rows; ties resolve to the smallest center index (first occurrence),
matching ``jnp.argmin``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.layout import (LANE, first_min, precision, round_up,
                                  row)
from repro.kernels.ref import MASKED_DIST


def _kernel(x_ref, c_ref, cn_ref, idx_ref, val_ref, acc_ref, xn_ref):
    # Centers on sublanes, points on lanes: the distance block is
    # (bk, bn) and every per-point vector a lane-dense (1, bn) row.
    kb = pl.program_id(1)
    j = pl.program_id(2)
    nj = pl.num_programs(2)
    bk = acc_ref.shape[0]

    @pl.when((kb == 0) & (j == 0))
    def _init_best():
        idx_ref[...] = jnp.zeros_like(idx_ref)
        val_ref[...] = jnp.full_like(val_ref, jnp.inf)
        xn_ref[...] = jnp.zeros_like(xn_ref)

    @pl.when(j == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)
    c = c_ref[...].astype(jnp.float32)
    # -2 * c @ x.T on the MXU, accumulated over d-blocks.
    acc_ref[...] += -2.0 * jax.lax.dot_general(
        c, x, (((1,), (1,)), ((), ())), precision=precision(jnp.float32),
        preferred_element_type=jnp.float32)

    # ||x||^2 depends only on the row block: accumulate it on the first
    # k-block pass and reuse the scratch for the rest.
    @pl.when(kb == 0)
    def _xnorm():
        xn_ref[...] += row(jnp.sum(x * x, axis=1, keepdims=True))

    @pl.when(j == nj - 1)
    def _merge():
        d = jnp.maximum(acc_ref[...] + cn_ref[...] + xn_ref[...], 0.0)
        bval, bidx = first_min(d, 0)
        # Strict < keeps the earlier k-block on ties; within a block
        # first_min picks the first — together: smallest global index.
        better = bval < val_ref[...]
        idx_ref[...] = jnp.where(better, kb * bk + bidx, idx_ref[...])
        val_ref[...] = jnp.where(better, bval, val_ref[...])


def _tiles(n: int, d: int, k: int, bn: int, bd: int, bk: int):
    """The dispatch's tile arithmetic, shared with :func:`block_plan`.
    ``bn`` rounds up to whole lane tiles (the (1, bn) output rows);
    ``bd`` and ``bk`` shrink to the data, 128-aligned, so a narrow
    feature dim or a small center set never pads out to a default-width
    tile (single-tile reductions are unchanged bitwise)."""
    bn = round_up(bn, LANE)
    bd = min(bd, round_up(d, LANE))
    bk = min(round_up(bk, LANE), round_up(k, LANE))
    return bn, bd, round_up(d, bd), bk, round_up(round_up(k, LANE), bk)


@functools.partial(jax.jit, static_argnames=("bn", "bd", "bk", "interpret"))
def _pairwise_argmin(x, c, c_mask, *, bn: int, bd: int, bk: int,
                     interpret: bool):
    n, d = x.shape
    k = c.shape[0]
    bn, bd, dp, bk, kp = _tiles(n, d, k, bn, bd, bk)

    cp = jnp.zeros((kp, dp), c.dtype).at[:k, :d].set(c)
    cn = jnp.sum(cp.astype(jnp.float32) ** 2, axis=1, keepdims=True)
    valid = jnp.arange(kp) < k
    if c_mask is not None:
        valid = valid & jnp.pad(c_mask, (0, kp - k), constant_values=False)
    cn = jnp.where(valid[:, None], cn, MASKED_DIST)              # (kp, 1)

    def call(xp):
        np_ = xp.shape[0]
        grid = (np_ // bn, kp // bk, dp // bd)  # d innermost: acc stays hot
        idx, val = pl.pallas_call(
            _kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bn, bd), lambda i, kb, j: (i, j)),  # x tile
                pl.BlockSpec((bk, bd), lambda i, kb, j: (kb, j)),  # centers
                pl.BlockSpec((bk, 1), lambda i, kb, j: (kb, 0)),  # norms
            ],
            out_specs=[
                pl.BlockSpec((1, bn), lambda i, kb, j: (0, i)),
                pl.BlockSpec((1, bn), lambda i, kb, j: (0, i)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((1, np_), jnp.int32),
                jax.ShapeDtypeStruct((1, np_), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((bk, bn), jnp.float32),
                pltpu.VMEM((1, bn), jnp.float32),
            ],
            interpret=interpret,
        )(xp, cp, cn)
        return idx[0], val[0]

    def pad_d(xs):
        if d == dp:
            return xs
        return jnp.zeros((xs.shape[0], dp), x.dtype).at[:, :d].set(xs)

    # Row padding: ONLY the ragged tail block (if any) is copied into a
    # zero-padded (bn, dp) buffer. The aligned prefix streams through
    # the kernel as-is — never a full (np_, dp) duplicate of x, which
    # doubled peak memory on exactly the million-point inputs the
    # chunked dispatcher exists to bound. (A d-pad copy still happens
    # when d is ragged vs the 128-lane tile; rows are independent, so
    # the split is bitwise-invisible.)
    nfull = (n // bn) * bn
    if nfull == n:
        return call(pad_d(x))
    tail = jnp.zeros((bn, dp), x.dtype).at[:n - nfull, :d].set(x[nfull:])
    ti, tv = call(tail)
    if not nfull:
        return ti[:n], tv[:n]
    idx, val = call(pad_d(x[:nfull]))
    return (jnp.concatenate([idx, ti[:n - nfull]]),
            jnp.concatenate([val, tv[:n - nfull]]))


def block_plan(n: int, d: int, k: int, *, bn: int = 128, bd: int = 512,
               bk: int = 512, dtype: str = "f32") -> dict:
    """Static BlockSpec/grid metadata of :func:`_pairwise_argmin` for
    the §15 kernel checker — the same tile arithmetic as the dispatch
    above, including the (bk, bn) accumulator and (1, bn) x-norm VMEM
    scratch that bound the footprint independently of k."""
    store = "f32" if dtype == "f32" else "bf16"
    bn, bd, dp, bk, kp = _tiles(n, d, k, bn, bd, bk)
    np_ = round_up(n, bn)
    blk = [
        dict(name="x", shape=(bn, bd), dtype=store, kind="in",
             resident=False, array_shape=(np_, dp)),
        dict(name="centers", shape=(bk, bd), dtype=store, kind="in",
             resident=False, array_shape=(kp, dp)),
        dict(name="center_norms", shape=(bk, 1), dtype="f32", kind="in",
             resident=False, array_shape=(kp, 1)),
        dict(name="idx", shape=(1, bn), dtype="i32", kind="out",
             resident=False, array_shape=(1, np_)),
        dict(name="val", shape=(1, bn), dtype="f32", kind="out",
             resident=False, array_shape=(1, np_)),
        dict(name="acc", shape=(bk, bn), dtype="f32", kind="scratch",
             resident=True, array_shape=(bk, bn)),
        dict(name="xn", shape=(1, bn), dtype="f32", kind="scratch",
             resident=True, array_shape=(1, bn)),
    ]
    return dict(kernel="pdist_argmin",
                grid=(np_ // bn, kp // bk, dp // bd), storage=store,
                accum="f32", blocks=blk)


def pairwise_argmin(x: jax.Array, c: jax.Array,
                    c_mask: jax.Array | None = None,
                    *, bn: int = 128, bd: int = 512, bk: int = 512,
                    interpret: bool | None = None):
    """Fused nearest-center assignment. x: (n, d), c: (k, d).

    Returns (idx (n,) int32, min_sq_dist (n,) f32). Matches
    ``ref.assign_argmin`` (masked centers excluded via an additive
    MASKED_DIST on their norm term). ``bk`` tiles the center axis so
    VMEM stays fixed for large k. ``interpret=None`` uses the same
    platform auto-detection as ``kernels.ops`` (compiled on TPU,
    interpret elsewhere) instead of silently interpreting on TPU.
    """
    from repro.kernels import ops
    return _pairwise_argmin(x, c, c_mask, bn=bn, bd=bd, bk=bk,
                            interpret=ops.resolve_interpret(interpret))
