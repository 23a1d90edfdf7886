"""Pure-jnp oracles for every Pallas kernel in this package.

These are the *reference semantics*: each Pallas kernel in
``pdist_argmin.py`` / ``kmeans_update.py`` / ``swa_decode.py`` must match
the corresponding function here (see tests/test_kernels.py, which sweeps
shapes and dtypes and asserts allclose in interpret mode).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

MASKED_DIST = 1e30  # additive "infinity" that survives f32 matmul paths
# f32 contractions run at full f32 precision on every backend: the TPU
# default would round f32 operands to bf16 (no-op on the CPU).
HIGHEST = jax.lax.Precision.HIGHEST


def pairwise_sq_dists(x: jax.Array, c: jax.Array) -> jax.Array:
    """Squared euclidean distances. x: (n, d), c: (k, d) -> (n, k)."""
    x = x.astype(jnp.float32)
    c = c.astype(jnp.float32)
    x2 = jnp.sum(x * x, axis=-1, keepdims=True)
    c2 = jnp.sum(c * c, axis=-1)
    # HIGH (three bf16 passes on a TPU, plain f32 elsewhere), not
    # HIGHEST: inside a vmapped Lloyd loop (B=64, n=1024, d=784) a TPU
    # v5e returned wrong nearest centers for the HIGHEST form of this
    # matmul, and right ones for HIGH and DEFAULT.
    xc = jnp.matmul(x, c.T, precision=jax.lax.Precision.HIGH)
    return jnp.maximum(x2 - 2.0 * xc + c2[None, :], 0.0)


def assign_argmin(x: jax.Array, c: jax.Array, c_mask: jax.Array | None = None):
    """Nearest-center assignment. Returns (idx (n,) int32, min_sq_dist (n,))."""
    d = pairwise_sq_dists(x, c)
    if c_mask is not None:
        d = jnp.where(c_mask[None, :], d, MASKED_DIST)
    return jnp.argmin(d, axis=1).astype(jnp.int32), jnp.min(d, axis=1)


def kmeans_update(x: jax.Array, assign: jax.Array, k: int,
                  weights: jax.Array | None = None):
    """Per-cluster sums and counts.

    ``assign`` entries equal to -1 (padded / invalid points) contribute
    nothing. Returns (sums (k, d) f32, counts (k,) f32).
    """
    oh = jax.nn.one_hot(assign, k, dtype=jnp.float32)  # -1 rows are all-zero
    if weights is not None:
        oh = oh * weights[:, None].astype(jnp.float32)
    sums = jnp.matmul(oh.T, x.astype(jnp.float32), precision=HIGHEST)
    counts = jnp.sum(oh, axis=0)
    return sums, counts


SOLVE_ATTACH_DTYPES = ("f32", "bf16")


def solve_attach(x: jax.Array, centers0: jax.Array, tau: jax.Array,
                 center_mask: jax.Array | None = None,
                 point_mask: jax.Array | None = None,
                 *, max_iters: int = 100, dtype: str = "f32"):
    """Oracle for ``kernels/solve_attach.solve_attach_fused`` — the FUSED
    serve step (DESIGN.md §13): bounded Lloyd local solve (Algorithm 1
    step 4) + Theorem 3.2 attach of the converged local centers against
    ``tau`` + Definition 3.3 induced point labels, as one primitive.

    x: (B, n, d); centers0: (B, k', d); tau: (k, d) — shared across the
    batch; center_mask: (B, k') bool; point_mask: (B, n) bool.
    Returns (labels (B, n) i32, min_sq_dist (B, n) f32,
    centers (B, k', d) f32, center_labels (B, k') i32).

    ``dtype="f32"`` is bitwise-identical to the staged composition
    ``core.lloyd.lloyd`` -> ``server.assign_new_device`` ->
    ``server.induced_labels`` on this backend (same primitives, same
    order). ``dtype="bf16"`` stores x / centers / tau in bfloat16
    between iterations and accumulates every distance and center-sum
    contraction in f32 (tolerance-bounded against the f32 oracle; see
    tests/test_solve_attach.py).
    """
    assert dtype in SOLVE_ATTACH_DTYPES, dtype
    store = jnp.float32 if dtype == "f32" else jnp.bfloat16
    B, n, _ = x.shape
    kp = centers0.shape[1]
    cm = jnp.ones((B, kp), bool) if center_mask is None else center_mask
    pm = jnp.ones((B, n), bool) if point_mask is None else point_mask
    taus = tau.astype(store)

    def one(x1, c0, cm1, pm1):
        def assign(centers):
            idx, mind = assign_argmin(x1, centers, cm1)
            return jnp.where(pm1, idx, -1), jnp.where(pm1, mind, 0.0)

        def cond(state):
            _, _, it, done = state
            return (~done) & (it < max_iters)

        def body(state):
            centers, prev, it, _ = state
            a, _ = assign(centers)
            sums, cnt = kmeans_update(x1, a, kp)
            new = sums / jnp.maximum(cnt, 1.0)[:, None]
            new = jnp.where((cnt > 0)[:, None], new,
                            centers.astype(jnp.float32))
            return (new.astype(centers.dtype), a, it + 1,
                    jnp.all(a == prev))

        a0 = jnp.full((x1.shape[0],), -2, jnp.int32)
        centers, _, _, _ = jax.lax.while_loop(
            cond, body, (c0, a0, jnp.int32(0), jnp.bool_(False)))
        a, mind = assign(centers)
        ctr, _ = assign_argmin(centers, taus)
        ctr = jnp.where(cm1, ctr, -1)
        safe = jnp.clip(a, 0, kp - 1)
        lbl = jnp.where(a >= 0, ctr[safe], -1)
        return lbl, mind, centers.astype(jnp.float32), ctr

    return jax.vmap(one)(x.astype(store), centers0.astype(store), cm, pm)


def swa_decode_attention(q: jax.Array, kw: jax.Array, vw: jax.Array,
                         bias: jax.Array, scale: float) -> jax.Array:
    """Sliding-window decode attention (one query token per sequence).

    q: (b, h, dh); kw/vw: (b, W, kvh, dh) -- the *windowed* KV slice;
    bias: (b, W) additive mask (0 valid / -inf invalid).
    Returns (b, h, dh).
    """
    b, h, dh = q.shape
    kvh = kw.shape[2]
    g = h // kvh
    qg = q.reshape(b, kvh, g, dh).astype(jnp.float32)
    kf = kw.astype(jnp.float32)
    vf = vw.astype(jnp.float32)
    s = jnp.einsum("bkgd,bwkd->bkgw", qg, kf) * scale
    s = s + bias[:, None, None, :]
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgw,bwkd->bkgd", p, vf)
    return o.reshape(b, h, dh).astype(q.dtype)


def moe_dispatch(x: jax.Array, src: jax.Array, valid: jax.Array):
    """Oracle for kernels/moe_dispatch.moe_dispatch: queue slot s pulls
    token row src[s] (zeroed when invalid). x: (T, d); src/valid: (S,)."""
    rows = x[jnp.clip(src, 0, x.shape[0] - 1)]
    return jnp.where(valid[:, None], rows, 0).astype(x.dtype)


def moe_combine(ybuf: jax.Array, slot: jax.Array, gates: jax.Array,
                top_k: int):
    """Oracle for kernels/moe_dispatch.moe_combine. ybuf: (S, d);
    slot/gates: (T*top_k,). Returns (T, d) f32."""
    rows = ybuf[jnp.clip(slot, 0, ybuf.shape[0] - 1)].astype(jnp.float32)
    w = gates.astype(jnp.float32)[:, None]
    T = slot.shape[0] // top_k
    return jnp.sum((rows * w).reshape(T, top_k, -1), axis=1)
