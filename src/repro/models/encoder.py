"""Latent-space ingestion encoder for the serve plane (DESIGN.md §17).

The paper's separation analysis (Theorem 3.2, Definition 3.3) is
agnostic to WHERE the geometry lives; raw pixel/token space rarely
satisfies center separation, so related federated-clustering work
clusters clients on learned embeddings instead. This module is the
ingestion-side bridge from the model zoo (``models/`` blocks +
``configs/`` architecture registry) to the serve plane — the sibling of
``models/heads.py`` (the serving-output side), sharing its block/init/
apply conventions:

  * ``resolve_encoder_spec`` maps a plan's ``encoder`` name to an
    :class:`EncoderSpec`: any registered zoo config name
    (``configs.list_archs()``) contributes its REDUCED variant's
    activation, FFN expansion ratio, head counts and layer count,
    re-dimensioned to the plan's feature width ``d`` — the encoder
    operates at the clustering feature width, not the config's
    ``d_model`` (the ``heads.py`` re-dimensioning rule).
  * ``init_encoder`` builds one parameter set (layers stacked on a
    leading axis) through the zoo initializers (``models.ffn.init_ffn``,
    ``models.attention.init_gqa``, ``models.common.init_norm``) from one
    deterministic key.
  * ``apply_encoder`` runs every item's raw token/patch sequence
    through ``n_layers`` pre-norm blocks (non-causal masked
    self-attention over the sequence + the FFN block — a token sequence
    is ordered, but positions arrive as part of the stub-frontend
    embeddings, matching the repo's precomputed-embedding convention)
    and masked-mean pools over the VALID tokens to one ``(d,)``
    embedding per item. ``encode_dtype="bf16"`` casts storage to
    bfloat16 while every matmul accumulates in f32
    (``preferred_element_type``), mirroring the fused solve+attach
    precision contract (§13).

Inputs follow the stub-frontend rule (``configs.base.EncoderConfig``):
raw images/audio/text arrive as precomputed token/patch embeddings of
width ``d`` — each submitted point is a ``(seq, d)`` sequence, the
encoder maps it to latent space, and the unchanged solve+attach
machinery clusters the embeddings.

``block_plan`` publishes the §15 kernel-checker metadata of the encoder
forward: the VMEM feasibility certificate of a fused per-item encoder
block kernel (items on the grid's major axis, the FFN hidden dimension
tiled on the minor axis so wide ``d_ff`` never exceeds the per-core
budget), evaluated by ``analysis/kernels.py`` across the registered
ladder exactly like the Pallas kernels' plans.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.models.attention import plain_attention, init_gqa
from repro.models.common import init_norm, rms_norm
from repro.models.ffn import init_ffn
from repro.models.heads import _AttnDims, _dot, _ffn_apply

__all__ = ["ENCODE_DTYPES", "EncoderConfigError", "EncoderSpec",
           "apply_encoder", "block_plan", "encoder_param_count",
           "init_encoder", "resolve_encoder_spec"]

ENCODE_DTYPES = ("f32", "bf16")


class EncoderConfigError(ValueError):
    """An encoder/encode_dtype selection failed validation (named, with
    the accepted values) — raised at plan construction, never in
    tracing."""


class EncoderSpec(NamedTuple):
    """Static shape/flavor of the ingestion encoder (all fields
    hashable so the spec can ride jit static arguments)."""
    name: str           # a registered configs.* name
    d: int              # feature width (the plan's d; also the token width)
    d_ff: int           # FFN hidden width (ratio-scaled from the config)
    activation: str     # swiglu | gelu | relu2
    n_layers: int       # stacked pre-norm blocks (the REDUCED depth)
    n_heads: int
    n_kv_heads: int


def resolve_encoder_spec(name: str, d: int) -> EncoderSpec:
    """Validate + resolve a plan's ``encoder`` selection into an
    :class:`EncoderSpec`. Raises :class:`EncoderConfigError` naming the
    accepted values (``StreamConfig`` re-raises field-named)."""
    from repro.configs import get_config, list_archs
    try:
        cfg = get_config(name, reduced=True)
    except KeyError:
        raise EncoderConfigError(
            f"encoder={name!r} is invalid: accepted values are 'off' or "
            f"a registered model config {list_archs()}") from None
    # Re-dimension the REDUCED config to the clustering feature width:
    # keep its FFN expansion ratio, activation, head counts and depth,
    # floor d_ff at d (the heads.py rule).
    d_ff = max(int(d), int(round(d * cfg.d_ff / cfg.d_model)))
    n_heads, n_kv = int(cfg.n_heads), int(cfg.n_kv_heads)
    if d % n_heads:
        raise EncoderConfigError(
            f"encoder={name!r} is invalid for d={d}: the config's "
            f"n_heads={n_heads} must divide the plan's feature "
            f"dimension (pick a different config or d)")
    n_layers = max(1, min(2, int(cfg.n_layers)))
    return EncoderSpec(str(name), int(d), d_ff, str(cfg.activation),
                       n_layers, n_heads, n_kv)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _attn_dims(spec: EncoderSpec) -> _AttnDims:
    return _AttnDims(d_model=spec.d, n_heads=spec.n_heads,
                     n_kv_heads=spec.n_kv_heads,
                     hd=spec.d // spec.n_heads, qkv_bias=False)


def _init_layer(key, spec: EncoderSpec, dtype):
    ks = jax.random.split(key, 2)
    return {"norm1": init_norm("rmsnorm", spec.d, dtype),
            "attn": init_gqa(ks[0], _attn_dims(spec), dtype),
            "norm2": init_norm("rmsnorm", spec.d, dtype),
            "ffn": init_ffn(ks[1], spec.d, spec.d_ff, spec.activation,
                            dtype)}


def init_encoder(key, spec: EncoderSpec, dtype=jnp.float32):
    """The encoder parameter tree from one key: ``n_layers`` pre-norm
    blocks stacked on a leading layer axis (leaf shapes
    ``(n_layers, ...)`` — the layout checkpoint schema v6 stores) plus
    the final norm."""
    lk, _ = jax.random.split(key)
    layers = jax.vmap(lambda kk: _init_layer(kk, spec, dtype))(
        jax.random.split(lk, spec.n_layers))
    return {"layers": layers,
            "norm_f": init_norm("rmsnorm", spec.d, dtype)}


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _attn_apply(p, x, tmask, spec: EncoderSpec):
    """Non-causal masked self-attention over each item's token
    sequence. x: (R, S, d) storage dtype; tmask: (R, S) bool. Returns
    (R, S, d) f32."""
    R, S, d = x.shape
    H, KVH, hd = spec.n_heads, spec.n_kv_heads, d // spec.n_heads
    q = _dot(x, p["wq"]).reshape(R, S, H, hd).astype(x.dtype)
    kk = _dot(x, p["wk"]).reshape(R, S, KVH, hd).astype(x.dtype)
    v = _dot(x, p["wv"]).reshape(R, S, KVH, hd).astype(x.dtype)
    o = plain_attention(q, kk, v, kv_mask=tmask)
    return _dot(o.reshape(R, S, H * hd), p["wo"])


def _block_fwd(p, h, tmask, spec: EncoderSpec, store):
    """One pre-norm block (attention + FFN, residual). h: (R, S, d)
    f32; returns (R, S, d) f32."""
    a = rms_norm(h, p["norm1"]["w"].astype(jnp.float32)).astype(store)
    h = h + _attn_apply(p["attn"], a, tmask, spec)
    f = rms_norm(h, p["norm2"]["w"].astype(jnp.float32)).astype(store)
    return h + _ffn_apply(p["ffn"], f, spec.activation)


def apply_encoder(params, x, tmask, spec: EncoderSpec,
                  encode_dtype: str = "f32"):
    """Encode raw token/patch sequences into latent points.

    ``x``: (..., S, d) float token embeddings; ``tmask``: (..., S) bool
    token validity (per-item ragged lengths, padded to the bucket's
    ``S``). Returns (..., d) f32 embeddings — the masked mean of the
    final-norm token states over each item's VALID tokens; items with
    no valid tokens (padding rows) embed to exactly zero, so the serve
    step's point mask stays the single source of validity.
    ``encode_dtype`` selects f32 or bf16 storage with f32 accumulation
    (§13 contract)."""
    store = jnp.bfloat16 if encode_dtype == "bf16" else jnp.float32
    lead = x.shape[:-2]
    S, d = x.shape[-2], x.shape[-1]
    xr = x.reshape((-1, S, d)).astype(store)
    mr = tmask.reshape((-1, S))
    ps = jax.tree.map(lambda a: a.astype(store), params)
    h = xr.astype(jnp.float32)
    for i in range(spec.n_layers):
        layer = jax.tree.map(lambda a: a[i], ps["layers"])
        h = _block_fwd(layer, h, mr, spec, store)
    h = rms_norm(h, ps["norm_f"]["w"].astype(jnp.float32))
    mf = mr.astype(jnp.float32)
    tot = jnp.maximum(jnp.sum(mf, axis=-1, keepdims=True), 1.0)
    pooled = jnp.einsum("rsd,rs->rd", h, mf) / tot
    pooled = jnp.where(mr.any(axis=-1, keepdims=True), pooled, 0.0)
    return pooled.reshape(lead + (d,))


def encoder_param_count(spec: EncoderSpec) -> int:
    """Static parameter count (stats/docs)."""
    d, ff, hd = spec.d, spec.d_ff, spec.d // spec.n_heads
    per = 2 * d                                    # norm1 + norm2
    per += (3 * d * ff if spec.activation == "swiglu"
            else 2 * d * ff + ff + d)
    per += d * spec.n_heads * hd + 2 * d * spec.n_kv_heads * hd \
        + spec.n_heads * hd * d
    return spec.n_layers * per + d                 # + final norm


# ---------------------------------------------------------------------------
# §15 kernel-checker block plan
# ---------------------------------------------------------------------------


def _ff_tile(d_ff: int) -> int:
    """FFN hidden-axis tile: whole when it fits one 512-lane window,
    else 512 (a multiple of the 128-lane tile, so a partitioned d_ff
    never relayouts)."""
    return d_ff if d_ff <= 512 else 512


def block_plan(items: int, S: int, d: int, d_ff: int, n_heads: int,
               dtype: str = "f32") -> dict:
    """Static BlockSpec/grid metadata of the fused per-item encoder
    block for the §15 kernel checker: grid major axis = items (one
    (S, d) sequence per step), minor axis tiles the FFN hidden width so
    the streamed weight tiles — not the full (d, d_ff) matrices — bound
    the VMEM footprint. Attention weights are grid-constant (resident,
    single-buffered); the token block and weight tiles stream
    (double-buffered). Mirrors ``apply_encoder``'s shapes exactly —
    the checker evaluates this plan across the registered ladder."""
    store = "f32" if dtype == "f32" else "bf16"
    ft = _ff_tile(d_ff)
    blk = [
        dict(name="x", shape=(1, S, d), dtype=store, kind="in",
             resident=False, array_shape=(items, S, d)),
        # per-item vectors travel as (items, 1, m) rows, whose unit
        # sublane axis a (1, 1, m) block spans whole (the TPU tiling rule)
        dict(name="tmask", shape=(1, 1, S), dtype="i32", kind="in",
             resident=False, array_shape=(items, 1, S)),
        dict(name="wq", shape=(d, d), dtype=store, kind="in",
             resident=True, array_shape=(d, d)),
        dict(name="wk", shape=(d, d), dtype=store, kind="in",
             resident=True, array_shape=(d, d)),
        dict(name="wv", shape=(d, d), dtype=store, kind="in",
             resident=True, array_shape=(d, d)),
        dict(name="wo", shape=(d, d), dtype=store, kind="in",
             resident=True, array_shape=(d, d)),
        dict(name="scores", shape=(n_heads, S, S), dtype="f32",
             kind="scratch", resident=True,
             array_shape=(n_heads, S, S)),
        dict(name="w1", shape=(d, ft), dtype=store, kind="in",
             resident=False, array_shape=(d, d_ff)),
        dict(name="w3", shape=(d, ft), dtype=store, kind="in",
             resident=False, array_shape=(d, d_ff)),
        dict(name="w2", shape=(ft, d), dtype=store, kind="in",
             resident=False, array_shape=(d_ff, d)),
        dict(name="hidden", shape=(S, ft), dtype="f32", kind="scratch",
             resident=True, array_shape=(S, d_ff)),
        dict(name="out", shape=(1, 1, d), dtype="f32", kind="out",
             resident=False, array_shape=(items, 1, d)),
    ]
    return dict(kernel="encoder_fwd", grid=(items, d_ff // ft),
                storage=store, accum="f32", blocks=blk)
