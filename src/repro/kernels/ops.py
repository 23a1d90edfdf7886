"""Jit'd dispatch layer over the Pallas kernels and their jnp oracles.

The framework's numerical code calls these entry points; the backend is
selected globally (``set_backend``) or per-call. Interpret mode is
auto-detected from the platform: on TPU the kernels run compiled, on any
other backend (e.g. the CPU) they run in interpret mode (the kernel body
executes in Python for correctness validation). Off the TPU,
``REPRO_KERNEL_INTERPRET=0|1`` or ``set_backend(..., interpret=)``
override it; on a TPU an interpret request is refused with
:class:`InterpretOnTPUError`, so a run on the chip never silently
measures the interpreter.
"""
from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref

_STATE = {
    "impl": os.environ.get("REPRO_KERNEL_IMPL", "ref"),  # "ref" | "pallas"
    "interpret": None,  # None = auto-detect on first kernel call
    # Row count above which assign_argmin streams fixed-size chunks
    # through the kernel instead of one monolithic call (bounds the
    # padded/intermediate footprint for million-point labeling).
    "chunk_rows": int(os.environ.get("REPRO_ASSIGN_CHUNK_ROWS", 1 << 18)),
}


class InterpretOnTPUError(RuntimeError):
    """Pallas interpret mode was requested on a TPU backend. The kernels
    compile there; interpreting them would time the interpreter, not
    the chip."""


def _refuse_on_tpu(interpret: bool, source: str) -> bool:
    if interpret and jax.default_backend() == "tpu":
        raise InterpretOnTPUError(
            f"{source} asks for Pallas interpret mode on a TPU backend; "
            f"the kernels run compiled there (unset it, or pass "
            f"interpret=None/False)")
    return interpret


def _auto_interpret() -> bool:
    env = os.environ.get("REPRO_KERNEL_INTERPRET")
    if env is not None:
        return _refuse_on_tpu(
            env.strip().lower() not in ("0", "false", "no", "off"),
            f"REPRO_KERNEL_INTERPRET={env}")
    # Compiled Pallas only on TPU; interpret everywhere else. Deferred to
    # first kernel call so importing this module never initializes a
    # backend.
    return jax.default_backend() != "tpu"


def _interpret() -> bool:
    if _STATE["interpret"] is None:
        _STATE["interpret"] = _auto_interpret()
    return _STATE["interpret"]


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Per-call override -> resolved interpret flag. Kernel modules call
    this so a direct kernel invocation (bypassing the dispatchers below)
    still gets the platform auto-detection instead of a hardcoded
    default; an explicit ``True`` on a TPU is refused."""
    if interpret is None:
        return _interpret()
    return _refuse_on_tpu(interpret, "interpret=True")


def set_backend(impl: str, interpret: Optional[bool] = None,
                chunk_rows: Optional[int] = None) -> None:
    """Select the kernel implementation. ``interpret=None`` re-enables
    platform auto-detection (compiled on TPU, interpret elsewhere);
    ``interpret=True`` on a TPU raises :class:`InterpretOnTPUError`.
    ``chunk_rows`` sets the auto-chunking threshold of
    :func:`assign_argmin` (0 disables)."""
    assert impl in ("ref", "pallas"), impl
    if interpret is not None:
        _refuse_on_tpu(interpret, "set_backend(interpret=True)")
    _STATE["impl"] = impl
    _STATE["interpret"] = interpret
    if chunk_rows is not None:
        _STATE["chunk_rows"] = chunk_rows
    # A jitted caller bakes the dispatch in when it is traced: drop every
    # compiled program so the next call traces under the new backend.
    jax.clear_caches()


def get_backend() -> str:
    return _STATE["impl"]


def pairwise_sq_dists(x: jax.Array, c: jax.Array) -> jax.Array:
    # Full distance matrix is only used by analysis paths; always jnp.
    return _ref.pairwise_sq_dists(x, c)


def _assign_argmin_one(x: jax.Array, c: jax.Array,
                       c_mask: Optional[jax.Array] = None):
    if _STATE["impl"] == "pallas":
        from repro.kernels.pdist_argmin import pairwise_argmin
        return pairwise_argmin(x, c, c_mask, interpret=_interpret())
    return _ref.assign_argmin(x, c, c_mask)


def assign_argmin_chunked(x: jax.Array, c: jax.Array,
                          c_mask: Optional[jax.Array] = None,
                          *, chunk: int = 1 << 18):
    """Streaming nearest-center assignment: rows of ``x`` are processed
    in fixed ``chunk``-size tiles (``lax.map`` — one kernel launch per
    tile, sequential), so the working set stays O(chunk * d) no matter
    how many points are labeled. Same (idx, min_sq_dist) contract as
    :func:`assign_argmin`."""
    n, d = x.shape
    if n <= chunk:
        return _assign_argmin_one(x, c, c_mask)
    # Whole chunks stream through lax.map; the ragged tail gets its own
    # call — no full zero-padded copy of x (that would double peak
    # memory on exactly the inputs chunking exists to bound).
    nfull = (n // chunk) * chunk
    idx, val = jax.lax.map(
        lambda xb: _assign_argmin_one(xb, c, c_mask),
        x[:nfull].reshape(-1, chunk, d))
    idx, val = idx.reshape(-1), val.reshape(-1)
    if nfull < n:
        ti, tv = _assign_argmin_one(x[nfull:], c, c_mask)
        idx = jnp.concatenate([idx, ti])
        val = jnp.concatenate([val, tv])
    return idx, val


def assign_argmin(x: jax.Array, c: jax.Array,
                  c_mask: Optional[jax.Array] = None):
    chunk = _STATE["chunk_rows"]
    if chunk and x.shape[0] > chunk:
        return assign_argmin_chunked(x, c, c_mask, chunk=chunk)
    return _assign_argmin_one(x, c, c_mask)


# Floor of the per-shard chunk budget: below this the per-launch
# overhead of lax.map tiles dominates any footprint saving.
_MIN_CHUNK_ROWS = 4096


def plan_chunk_rows(n_shards: int = 1) -> int:
    """Row-chunk budget for shard-parallel callers (the serve plane,
    DESIGN.md §11): ``n_shards`` concurrent shards each streaming
    assignment chunks should divide the global ``chunk_rows`` threshold
    between them, so the AGGREGATE in-flight footprint stays bounded by
    one single-host chunk no matter how wide the mesh. Floored at
    ``_MIN_CHUNK_ROWS`` so tiny per-shard batches never degenerate into
    per-row kernel launches."""
    base = _STATE["chunk_rows"] or (1 << 18)
    return max(_MIN_CHUNK_ROWS, base // max(1, int(n_shards)))


def solve_attach(x: jax.Array, centers0: jax.Array, tau: jax.Array,
                 center_mask: Optional[jax.Array] = None,
                 point_mask: Optional[jax.Array] = None,
                 *, max_iters: int = 100, dtype: str = "f32"):
    """Fused serve-step primitive (DESIGN.md §13): bounded Lloyd local
    solve + Theorem 3.2 attach against ``tau`` + Definition 3.3 induced
    labels for a (B, n, d) request batch, in one dispatch. ``dtype``:
    "f32" (bitwise vs the staged composition) or "bf16" (bf16 storage,
    f32 accumulation). Returns (labels, min_sq_dist, centers,
    center_labels)."""
    if _STATE["impl"] == "pallas":
        from repro.kernels.solve_attach import solve_attach_fused
        return solve_attach_fused(x, centers0, tau, center_mask,
                                  point_mask, max_iters=max_iters,
                                  dtype=dtype, interpret=_interpret())
    return _ref.solve_attach(x, centers0, tau, center_mask, point_mask,
                             max_iters=max_iters, dtype=dtype)


def kmeans_update(x: jax.Array, assign: jax.Array, k: int,
                  weights: Optional[jax.Array] = None):
    if _STATE["impl"] == "pallas":
        from repro.kernels.kmeans_update import kmeans_update as _pk
        return _pk(x, assign, k, weights, interpret=_interpret())
    return _ref.kmeans_update(x, assign, k, weights)


def swa_decode_attention(q, kw, vw, bias, scale):
    if _STATE["impl"] == "pallas":
        from repro.kernels.swa_decode import swa_decode_attention as _pk
        return _pk(q, kw, vw, bias, scale, interpret=_interpret())
    return _ref.swa_decode_attention(q, kw, vw, bias, scale)


def moe_dispatch(x, src, valid):
    """Queue-order row gather for MoE-style dispatch (scalar-prefetch
    DMA gather on TPU). The serve plane's routed personalization step
    (DESIGN.md §16) rides this with clusters as the experts: whole
    requests gather into per-cluster head queues, no (k, C, d)
    scatter."""
    if _STATE["impl"] == "pallas":
        from repro.kernels.moe_dispatch import moe_dispatch as _pd
        return _pd(x, src, valid, interpret=_interpret())
    return _ref.moe_dispatch(x, src, valid)


def moe_combine(ybuf, slot, gates, top_k: int):
    """Weighted queue->request re-assembly, the combine sibling of
    :func:`moe_dispatch` (routed serving uses top_k=1 with the keep
    mask as gates, so overflowed requests combine to zero)."""
    if _STATE["impl"] == "pallas":
        from repro.kernels.moe_dispatch import moe_combine as _pc
        return _pc(ybuf, slot, gates, top_k=top_k,
                   interpret=_interpret())
    return _ref.moe_combine(ybuf, slot, gates, top_k)
