"""Fused solve+attach Pallas TPU kernel (DESIGN.md §13).

One kernel invocation per request runs the ENTIRE serve hot path that
used to be three separate dispatches round-tripping HBM every Lloyd
iteration:

    bounded Lloyd local solve (Algorithm 1 step 4)
      -> Theorem 3.2 attach of the converged local centers against tau
      -> Definition 3.3 induced point labels

The request's points, the evolving (k', d) centers, the per-iteration
assignments, and the (k', n) distance block all stay resident in VMEM
across the whole while loop — x is read from HBM exactly once and the
only HBM writes are the four outputs. The legacy staged path re-read x
twice per Lloyd iteration (once for the assignment kernel, once for the
center update) and spilled the (n,) assignment each round; see
:func:`hbm_bytes` / :func:`hbm_bytes_legacy` for the exact
kernel-boundary traffic model the roofline perf-gate pins.

Mixed precision: ``dtype="bf16"`` stores points / centers / tau in
bfloat16 (halving the resident bytes and the MXU input width) while
every distance and center-sum contraction accumulates in f32 via
``preferred_element_type``; ``dtype="f32"`` executes the oracle's
arithmetic (``kernels.ref.solve_attach``) in the oracle's order — the
only deviation is float reduction order across the zero-padded lane
axis of the dots, so labels / centers / center-labels match the oracle
exactly on the parity sweeps and min-dists to reduction-order
tolerance (tests/test_solve_attach.py). The serve plane's §9/§11
bitwise-replay contract is carried by the default ref backend, where
``ops.solve_attach`` IS the oracle.

Capacity: everything for one request lives in VMEM at once, so the
kernel targets serve-bucket shapes — (n=1024, d=1024) f32 is ~6 MB,
comfortably under the ~16 MB/core budget. Million-point inputs go
through the chunked ``ops.assign_argmin`` path, not this kernel.
Padding: tau / theta pad k and k' up to 128 lanes and d up to 128;
``x`` is only copied when d % 128 != 0 (or n is not sublane-aligned —
never true for the power-of-two serve buckets).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.layout import col, first_min, precision, round_up, row
from repro.kernels.ref import MASKED_DIST, SOLVE_ATTACH_DTYPES


def _kernel(x_ref, c0_ref, tau_ref, cm_ref, pm_ref,
            lbl_ref, mind_ref, ctr_ref, clbl_ref,
            *, max_iters: int, k_real: int):
    # Every vector is 2-D (kernels/layout.py): per-point quantities are
    # (1, n_p) rows, per-center ones (kp_p, 1) columns, and distances a
    # (kp_p, n_p) block with centers on sublanes and points on lanes.
    x = x_ref[0]                                  # (n_p, d_p) store dtype
    xf = x.astype(jnp.float32)
    xn = row(jnp.sum(xf * xf, axis=1, keepdims=True))         # (1, n_p)
    cm = col(cm_ref[0]) != 0                                  # (kp_p, 1)
    pm = pm_ref[0] != 0                                       # (1, n_p)
    taus = tau_ref[...]                           # (k_p, d_p) store dtype
    n_p, kp_p = x.shape[0], c0_ref.shape[1]
    prec = precision(x.dtype)

    def dot_t(a, b):                              # a @ b.T, f32 result
        return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                                   precision=prec,
                                   preferred_element_type=jnp.float32)

    def onehot(a):                                # (kp_p, n_p) bool
        return a == jax.lax.broadcasted_iota(jnp.int32, (kp_p, n_p), 0)

    def assign(centers):
        # ref.assign_argmin's expression in its order, transposed: the
        # bf16 dot with preferred f32 equals the oracle's upcast-then-dot.
        cf = centers.astype(jnp.float32)
        cn = jnp.sum(cf * cf, axis=1, keepdims=True)          # (kp_p, 1)
        d = jnp.maximum(xn - 2.0 * dot_t(centers, x) + cn, 0.0)
        d = jnp.where(cm, d, MASKED_DIST)
        mind, idx = first_min(d, 0)
        return jnp.where(pm, idx, -1), jnp.where(pm, mind, 0.0)

    def cond(state):
        _, _, it, done = state
        return (~done) & (it < max_iters)

    def body(state):
        centers, prev, it, _ = state
        a, _ = assign(centers)
        # one_hot(-1) is all-zero, exactly like ref.kmeans_update.
        oh = onehot(a)
        sums = jax.lax.dot_general(
            oh.astype(x.dtype), x, (((1,), (0,)), ((), ())),
            precision=prec, preferred_element_type=jnp.float32)
        cnt = jnp.sum(oh.astype(jnp.float32), axis=1, keepdims=True)
        new = sums / jnp.maximum(cnt, 1.0)
        new = jnp.where(cnt > 0, new, centers.astype(jnp.float32))
        return (new.astype(centers.dtype), a, it + 1,
                jnp.all(a == prev))

    a0 = jnp.full((1, n_p), -2, jnp.int32)
    centers, _, _, _ = jax.lax.while_loop(
        cond, body, (c0_ref[0], a0, jnp.int32(0), jnp.bool_(False)))
    a, mind = assign(centers)

    # Theorem 3.2 attach: nearest tau center per converged local center.
    # Padded tau columns (>= k_real) are a layout artifact the oracle
    # never sees — mask them out; real columns are bitwise identical.
    cf = centers.astype(jnp.float32)
    tf = taus.astype(jnp.float32)
    dt = (jnp.sum(cf * cf, axis=1, keepdims=True) - 2.0 * dot_t(centers, taus)
          + row(jnp.sum(tf * tf, axis=1, keepdims=True)))    # (kp_p, k_p)
    dt = jnp.maximum(dt, 0.0)
    dt = jnp.where(jax.lax.broadcasted_iota(jnp.int32, dt.shape, 1) < k_real,
                   dt, MASKED_DIST)
    ctr = jnp.where(cm, first_min(dt, 1)[1], -1)              # (kp_p, 1)

    # Definition 3.3 induced labels: ctr[clip(a, 0, k'-1)] as an exact
    # one-hot integer select (vector gather is MXU-hostile on TPU).
    lbl = jnp.sum(jnp.where(onehot(jnp.clip(a, 0, kp_p - 1)), ctr, 0),
                  axis=0, keepdims=True)                      # (1, n_p)

    lbl_ref[0] = jnp.where(a >= 0, lbl, -1).astype(jnp.int32)
    mind_ref[0] = mind
    ctr_ref[0] = centers.astype(jnp.float32)
    clbl_ref[0] = row(ctr)


@functools.partial(jax.jit,
                   static_argnames=("max_iters", "dtype", "interpret"))
def _solve_attach(x, c0, tau, cm, pm, *, max_iters: int, dtype: str,
                  interpret: bool):
    B, n, d = x.shape
    kp = c0.shape[1]
    k = tau.shape[0]
    store = jnp.float32 if dtype == "f32" else jnp.bfloat16
    n_p, d_p, kp_p, k_p = _padded(n, d, kp, k, dtype)

    xs = x.astype(store)
    if (n_p, d_p) != (n, d):
        xs = jnp.zeros((B, n_p, d_p), store).at[:, :n, :d].set(xs)
    cs = c0.astype(store)
    if (kp_p, d_p) != (kp, d):
        cs = jnp.zeros((B, kp_p, d_p), store).at[:, :kp, :d].set(cs)
    ts = tau.astype(store)
    if (k_p, d_p) != (k, d):
        ts = jnp.zeros((k_p, d_p), store).at[:k, :d].set(ts)
    # Per-request vectors travel as (B, 1, m) rows: a (1, m) block equals
    # the array's last two dims, which the TPU tiling rule admits.
    cmi = jnp.zeros((B, 1, kp_p), jnp.int32).at[:, 0, :kp].set(
        cm.astype(jnp.int32))
    pmi = jnp.zeros((B, 1, n_p), jnp.int32).at[:, 0, :n].set(
        pm.astype(jnp.int32))

    def per_request(*tail):
        return pl.BlockSpec((1, *tail), lambda b: (b, 0, 0))

    lbl, mind, ctr, clbl = pl.pallas_call(
        functools.partial(_kernel, max_iters=max_iters, k_real=k),
        grid=(B,),
        in_specs=[
            per_request(n_p, d_p),                         # x
            per_request(kp_p, d_p),                        # theta0
            pl.BlockSpec((k_p, d_p), lambda b: (0, 0)),    # tau (resident)
            per_request(1, kp_p),                          # center mask
            per_request(1, n_p),                           # point mask
        ],
        out_specs=[per_request(1, n_p), per_request(1, n_p),
                   per_request(kp_p, d_p), per_request(1, kp_p)],
        out_shape=[
            jax.ShapeDtypeStruct((B, 1, n_p), jnp.int32),
            jax.ShapeDtypeStruct((B, 1, n_p), jnp.float32),
            jax.ShapeDtypeStruct((B, kp_p, d_p), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, kp_p), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit(n_p, d_p)),
        interpret=interpret,
    )(xs, cs, ts, cmi, pmi)
    return (lbl[:, 0, :n], mind[:, 0, :n], ctr[:, :kp, :d],
            clbl[:, 0, :kp])


def solve_attach_fused(x: jax.Array, centers0: jax.Array, tau: jax.Array,
                       center_mask: jax.Array | None = None,
                       point_mask: jax.Array | None = None,
                       *, max_iters: int = 100, dtype: str = "f32",
                       interpret: bool | None = None):
    """Fused serve step. Same contract as ``ref.solve_attach``:
    x (B, n, d), centers0 (B, k', d), tau (k, d) ->
    (labels (B, n) i32, min_sq_dist (B, n) f32, centers (B, k', d) f32,
    center_labels (B, k') i32). ``interpret=None`` uses the
    ``kernels.ops`` platform auto-detection."""
    from repro.kernels import ops
    assert dtype in SOLVE_ATTACH_DTYPES, dtype
    B, n, _ = x.shape
    kp = centers0.shape[1]
    cm = (jnp.ones((B, kp), bool) if center_mask is None else center_mask)
    pm = jnp.ones((B, n), bool) if point_mask is None else point_mask
    return _solve_attach(x, centers0, tau, cm, pm,
                         max_iters=int(max_iters), dtype=dtype,
                         interpret=ops.resolve_interpret(interpret))


# ---------------------------------------------------------------------------
# Analytic kernel-boundary HBM traffic model (the roofline perf-gate's
# deterministic "bytes accessed per attached point" source — see
# benchmarks/bench_roofline.py and DESIGN.md §13). Pure arithmetic over
# the padded shapes above: no compilation, no hardware, no noise.
# ---------------------------------------------------------------------------

def _padded(n, d, k_prime, k, dtype):
    sub = 8 if dtype == "f32" else 16
    return (round_up(n, sub), round_up(d, 128),
            round_up(k_prime, 128), round_up(k, 128))


# The kernel's working set is dominated by one request's points: the
# double-buffered x block plus its f32 working copies (the full-precision
# f32 contraction splits each operand into bf16 pieces). Eight f32 copies
# of x bound what the compiler allocates at every serve bucket, so the
# scoped VMEM limit follows x rather than the compiler's 16 MiB default,
# which the n=1024, d=784 bucket (~26 MB) exceeds.
_VMEM_FLOOR = 16 * 2 ** 20


def vmem_limit(n_p: int, d_p: int) -> int:
    """Scoped VMEM limit (bytes) for one (n_p, d_p) request block."""
    return max(_VMEM_FLOOR, 8 * n_p * d_p * 4 + 2 * 2 ** 20)


def block_plan(B: int, n: int, d: int, k_prime: int, k: int,
               dtype: str = "f32") -> dict:
    """Static BlockSpec/grid metadata of :func:`_solve_attach` for the
    §15 kernel checker: every VMEM-resident block with its shape,
    dtype, and whether its index map is grid-constant (resident blocks
    are single-buffered; streaming blocks double-buffer). Mirrors the
    padding arithmetic of the pallas_call above exactly — changing one
    without the other trips the checker's hand-computed footprints."""
    store = "f32" if dtype == "f32" else "bf16"
    n_p, d_p, kp_p, k_p = _padded(n, d, k_prime, k, dtype)
    blk = [
        dict(name="x", shape=(1, n_p, d_p), dtype=store, kind="in",
             resident=False, array_shape=(B, n_p, d_p)),
        dict(name="theta0", shape=(1, kp_p, d_p), dtype=store, kind="in",
             resident=False, array_shape=(B, kp_p, d_p)),
        # tau's index map is (0, 0) for every grid step: fetched once,
        # resident for the whole grid.
        dict(name="tau", shape=(k_p, d_p), dtype=store, kind="in",
             resident=True, array_shape=(k_p, d_p)),
        dict(name="center_mask", shape=(1, 1, kp_p), dtype="i32",
             kind="in", resident=False, array_shape=(B, 1, kp_p)),
        dict(name="point_mask", shape=(1, 1, n_p), dtype="i32", kind="in",
             resident=False, array_shape=(B, 1, n_p)),
        dict(name="labels", shape=(1, 1, n_p), dtype="i32", kind="out",
             resident=False, array_shape=(B, 1, n_p)),
        dict(name="min_dists", shape=(1, 1, n_p), dtype="f32", kind="out",
             resident=False, array_shape=(B, 1, n_p)),
        dict(name="centers", shape=(1, kp_p, d_p), dtype="f32",
             kind="out", resident=False, array_shape=(B, kp_p, d_p)),
        dict(name="center_labels", shape=(1, 1, kp_p), dtype="i32",
             kind="out", resident=False, array_shape=(B, 1, kp_p)),
    ]
    return dict(kernel="solve_attach", grid=(B,), storage=store,
                accum="f32", blocks=blk)


def hbm_bytes(B: int, n: int, d: int, k_prime: int, k: int,
              dtype: str = "f32") -> int:
    """HBM traffic of the FUSED kernel for one (B, n, d) serve batch:
    every input block is fetched once (tau's block index is constant
    across the grid, so it stays resident and is fetched once total),
    every output written once. Independent of the Lloyd iteration count
    — that is the entire point of the fusion."""
    store = 2 if dtype == "bf16" else 4
    n_p, d_p, kp_p, k_p = _padded(n, d, k_prime, k, dtype)
    reads = B * (n_p * d_p * store        # x: ONE read, ever
                 + kp_p * d_p * store     # theta0
                 + kp_p * 4 + n_p * 4)    # masks (i32)
    reads += k_p * d_p * store            # tau: resident constant block
    writes = B * (n_p * 4                 # labels
                  + n_p * 4               # min dists
                  + kp_p * d_p * 4        # converged centers (f32)
                  + kp_p * 4)             # center labels
    return reads + writes


def hbm_bytes_legacy(B: int, n: int, d: int, k_prime: int, k: int,
                     max_iters: int, dtype: str = "f32") -> int:
    """Kernel-boundary HBM traffic of the PRE-FUSION three-dispatch
    serve path for the same batch, at its Lloyd iteration bound: each
    iteration the assignment kernel re-reads x + centers and writes the
    (n,) assignment and min-dist, then the update kernel re-reads x and
    the assignment and writes (k', d) sums + counts, then the
    elementwise center step round-trips the centers again. After the
    loop: one final assignment, the (k', k) attach, and the
    induced-label gather. ``max_iters`` (not the data-dependent actual
    trip count) keeps the model deterministic; it is the same bound the
    fused kernel's while loop carries."""
    store = 2 if dtype == "bf16" else 4
    n_p, d_p, kp_p, k_p = _padded(n, d, k_prime, k, dtype)
    x_bytes = n_p * d_p * store
    c_bytes = kp_p * d_p * 4
    assign_rw = (x_bytes + c_bytes        # assignment kernel reads
                 + n_p * 4 + n_p * 4)     # writes idx + min-dist
    update_rw = (x_bytes + n_p * 4        # update kernel reads x, assign
                 + c_bytes + kp_p * 4)    # writes sums + counts
    center_step = 2 * c_bytes + kp_p * 4  # read sums+old, write new
    per_iter = assign_rw + update_rw + center_step
    final_assign = assign_rw
    attach = c_bytes + k_p * d_p * store + kp_p * 4       # (k', k) argmin
    induced = n_p * 4 + kp_p * 4 + n_p * 4                # gather in/out
    return B * (max_iters * per_iter + final_assign + attach + induced)


def kernel_flops(B: int, n: int, d: int, k_prime: int, k: int,
                 max_iters: int, dtype: str = "f32") -> int:
    """MXU contraction FLOPs for one serve batch at the iteration bound
    (identical for fused and legacy — fusion changes traffic, not math):
    per iteration one (n, d) x (d, k') distance dot and one (k', n) x
    (n, d) center-sum dot, plus the final assignment and the (k', k)
    attach dot. Elementwise/argmin FLOPs are excluded (sub-percent)."""
    n_p, d_p, kp_p, k_p = _padded(n, d, k_prime, k, dtype)
    per_iter = 2 * n_p * d_p * kp_p + 2 * kp_p * n_p * d_p
    final = 2 * n_p * d_p * kp_p
    attach = 2 * kp_p * d_p * k_p
    return B * (max_iters * per_iter + final + attach + 2 * n_p * d_p)
