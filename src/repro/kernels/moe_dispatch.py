"""MoE dispatch / combine Pallas TPU kernels (scalar-prefetch gather).

The §Perf deepseek/mixtral profiles put the residual cost of the MoE
layer in the dispatch data movement: building the (E, C, d) expert
queues from routed tokens and re-assembling token outputs. On GPU this
is a warp-level shuffle/scatter; the TPU-native mechanism is a
**scalar-prefetched DMA gather** — the routing indices are prefetched to
SMEM before the grid runs, and each grid step's BlockSpec *index_map*
uses them to point the DMA engine at the right source row, so tokens
stream HBM->VMEM exactly once, already in queue order. No scatter, no
(E, C, d) read-modify-write.

  dispatch:  queue[s, :] = x[src[s], :] * valid[s]         s in [E*C)
  combine:   y[t, :]     = sum_j gates[t, j] * ybuf[slot[t, j], :]

Validated in interpret mode against the pure-jnp oracles
(ref.moe_dispatch / ref.moe_combine); see tests/test_kernels.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.layout import round_up


def _dispatch_kernel(src_ref, valid_ref, x_ref, out_ref):
    s = pl.program_id(0)
    keep = (valid_ref[s] > 0).astype(out_ref.dtype)
    out_ref[...] = x_ref[...] * keep


def moe_dispatch(x: jax.Array, src: jax.Array, valid: jax.Array,
                 *, bd: int = 512, interpret: bool | None = None):
    """Gather routed tokens into queue order.

    x: (T, d); src: (S,) int32 source row per queue slot (clipped to
    [0, T)); valid: (S,) bool. Returns (S, d) with invalid slots zeroed.
    The caller reshapes to (E, C, d). ``interpret=None`` resolves via
    the same platform auto-detection as ``kernels.ops`` (compiled on
    TPU, interpret elsewhere, ``REPRO_KERNEL_INTERPRET`` override)
    instead of a hardcoded interpret default that silently never
    compiles.
    """
    from repro.kernels import ops
    return _moe_dispatch(x, src, valid, bd=bd,
                         interpret=ops.resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("bd", "interpret"))
def _moe_dispatch(x: jax.Array, src: jax.Array, valid: jax.Array,
                  *, bd: int, interpret: bool):
    T, d = x.shape
    S = src.shape[0]
    dp = round_up(d, bd)
    xp = jnp.zeros((T, 1, dp), x.dtype).at[:, 0, :d].set(x)
    src_c = jnp.clip(src, 0, T - 1).astype(jnp.int32)
    val_i = valid.astype(jnp.int32)

    grid = (S, dp // bd)
    out = pl.pallas_call(
        _dispatch_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                # one source row per grid step, chosen by the prefetched
                # routing index — the DMA gather
                pl.BlockSpec((None, 1, bd),
                             lambda s, j, src, val: (src[s], 0, j)),
            ],
            out_specs=pl.BlockSpec((None, 1, bd),
                                   lambda s, j, src, val: (s, 0, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((S, 1, dp), x.dtype),
        interpret=interpret,
    )(src_c, val_i, xp)
    return out[:, 0, :d]


def dispatch_block_plan(T: int, d: int, S: int, *, bd: int = 512,
                        dtype: str = "f32") -> dict:
    """Static BlockSpec/grid metadata of :func:`moe_dispatch` for the
    §15 kernel checker. One row per grid step is the scalar-prefetch DMA
    gather granule, so rows travel as a (rows, 1, d) array: the block's
    last two dims (1, bd) then span the array's whole unit sublane axis,
    which the TPU tiling rule admits. Routing indices live in SMEM
    (kind="scalar")."""
    store = "f32" if dtype == "f32" else "bf16"
    dp = round_up(d, bd)
    blk = [
        dict(name="src", shape=(S,), dtype="i32", kind="scalar",
             resident=True, array_shape=(S,)),
        dict(name="valid", shape=(S,), dtype="i32", kind="scalar",
             resident=True, array_shape=(S,)),
        dict(name="x", shape=(1, 1, bd), dtype=store, kind="in",
             resident=False, array_shape=(T, 1, dp)),
        dict(name="queues", shape=(1, 1, bd), dtype=store, kind="out",
             resident=False, array_shape=(S, 1, dp)),
    ]
    return dict(kernel="moe_dispatch", grid=(S, dp // bd), storage=store,
                accum=store, blocks=blk)


def combine_block_plan(S: int, d: int, T: int, *, top_k: int = 2,
                       bd: int = 512, dtype: str = "f32") -> dict:
    """Static BlockSpec/grid metadata of :func:`moe_combine` for the
    §15 kernel checker — the gather-and-weighted-sum sibling of
    :func:`dispatch_block_plan`, always f32-accumulating."""
    store = "f32" if dtype == "f32" else "bf16"
    dp = round_up(d, bd)
    blk = [
        dict(name="slot", shape=(T * top_k,), dtype="i32", kind="scalar",
             resident=True, array_shape=(T * top_k,)),
        dict(name="gates", shape=(T * top_k,), dtype="f32",
             kind="scalar", resident=True, array_shape=(T * top_k,)),
        dict(name="ybuf", shape=(1, 1, bd), dtype=store, kind="in",
             resident=False, array_shape=(S, 1, dp)),
        dict(name="out", shape=(1, 1, bd), dtype="f32", kind="out",
             resident=False, array_shape=(T, 1, dp)),
    ]
    return dict(kernel="moe_combine", grid=(T, top_k, dp // bd),
                storage=store, accum="f32", blocks=blk)


def moe_combine(ybuf: jax.Array, slot: jax.Array, gates: jax.Array,
                *, top_k: int, bd: int = 512,
                interpret: bool | None = None):
    """Weighted re-assembly of token outputs from expert queues.

    ybuf: (S, d) flat queues; slot: (T*top_k,) int32 queue slot per
    (token, choice), already clipped, with dropped entries pointing at
    any slot; gates: (T*top_k,) f32, zero for dropped entries.
    Returns (T, d) f32. ``interpret=None`` resolves like
    :func:`moe_dispatch`.
    """
    from repro.kernels import ops
    return _moe_combine(ybuf, slot, gates, top_k=top_k, bd=bd,
                        interpret=ops.resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("top_k", "bd", "interpret"))
def _moe_combine(ybuf: jax.Array, slot: jax.Array, gates: jax.Array,
                 *, top_k: int, bd: int, interpret: bool):
    S, d = ybuf.shape
    N = slot.shape[0]
    T = N // top_k
    dp = round_up(d, bd)
    yp = jnp.zeros((S, 1, dp), ybuf.dtype).at[:, 0, :d].set(ybuf)

    def kernel(slot_ref, gate_ref, y_ref, out_ref):
        t = pl.program_id(0)
        j = pl.program_id(1)

        @pl.when(j == 0)
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)

        g = gate_ref[t * top_k + j]
        out_ref[...] += y_ref[...].astype(jnp.float32) * g

    grid = (T, top_k, dp // bd)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((None, 1, bd),
                             lambda t, j, b, slot, gate:
                             (slot[t * top_k + j], 0, b)),
            ],
            out_specs=pl.BlockSpec((None, 1, bd),
                                   lambda t, j, b, slot, gate: (t, 0, b)),
        ),
        out_shape=jax.ShapeDtypeStruct((T, 1, dp), jnp.float32),
        interpret=interpret,
    )(slot.astype(jnp.int32), gates.astype(jnp.float32), yp)
    return out[:, 0, :d]
