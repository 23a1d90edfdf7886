"""Fused solve+attach serve step (kernels/solve_attach, DESIGN.md §13):

- ref oracle vs the pre-fusion staged composition: BITWISE in f32 over
  shape/mask sweeps (the §9/§11 replay contract).
- the full serve-step body (fed.plane._make_step) vs the legacy
  three-stage body: bitwise on all four outputs.
- Pallas kernel (interpret mode) vs the oracle: labels / centers /
  center-labels exact, min-dists to reduction-order tolerance.
- bf16 storage mode: tolerance-bounded against the f32 oracle.
- serve_dtype config plumbing + the analytic HBM traffic model.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import server
from repro.core.local_kmeans import (batched_local_kmeans, local_kmeans,
                                     local_prepare, split_local_kw)
from repro.core.lloyd import assign_points, lloyd, lloyd_attach
from repro.fed.plane import _make_step
from repro.fed.stream import StreamConfig, StreamConfigError
from repro.kernels import ref
from repro.kernels.solve_attach import (hbm_bytes, hbm_bytes_legacy,
                                        kernel_flops, solve_attach_fused)


def _request_batch(seed, B, n, d, kp, k):
    rng = np.random.default_rng(seed)
    tau = jnp.asarray(rng.normal(size=(k, d)) * 4, jnp.float32)
    x = jnp.asarray(rng.normal(size=(B, n, d)) * 3, jnp.float32)
    c0 = jnp.asarray(rng.normal(size=(B, kp, d)) * 3, jnp.float32)
    cm = jnp.asarray(rng.random((B, kp)) < 0.8).at[:, 0].set(True)
    pm = jnp.asarray(rng.random((B, n)) < 0.9)
    return tau, x, c0, cm, pm


def _staged_solve_attach(x, c0, tau, cm, pm, max_iters):
    """The pre-fusion composition the oracle must replicate bitwise:
    core.lloyd.lloyd -> server.assign_new_device ->
    server.induced_labels (plus the final assignment's min-dists)."""
    def one(x1, c1, cm1, pm1):
        res = lloyd(x1, c1, center_mask=cm1, point_mask=pm1,
                    max_iters=max_iters)
        _, mind = assign_points(x1, res.centers, cm1, pm1)
        return res.centers, res.assign, mind

    centers, assign, mind = jax.vmap(one)(x, c0, cm, pm)
    ctr = jax.vmap(lambda c, m: server.assign_new_device(c, m, tau))(
        centers, cm)
    labels = server.induced_labels(ctr, assign)
    return labels, mind, centers, ctr


# ------------------------------------------------------ f32 bitwise ----

@pytest.mark.parametrize("B,n,d,kp,k,iters", [
    (1, 16, 3, 2, 4, 100),    # single request, tiny dims
    (4, 33, 7, 3, 7, 9),      # ragged n, tight iteration bound
    (3, 40, 37, 5, 9, 7),     # d not lane-aligned
    (2, 64, 24, 4, 16, 1),    # single Lloyd step
])
def test_oracle_matches_staged_bitwise(B, n, d, kp, k, iters):
    tau, x, c0, cm, pm = _request_batch(B * 7 + n, B, n, d, kp, k)
    got = ref.solve_attach(x, c0, tau, cm, pm, max_iters=iters)
    want = _staged_solve_attach(x, c0, tau, cm, pm, iters)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_oracle_default_masks_bitwise():
    tau, x, c0, _, _ = _request_batch(11, 2, 24, 5, 3, 6)
    B, n = x.shape[:2]
    full_cm = jnp.ones((B, 3), bool)
    full_pm = jnp.ones((B, n), bool)
    got = ref.solve_attach(x, c0, tau, max_iters=5)
    want = ref.solve_attach(x, c0, tau, full_cm, full_pm, max_iters=5)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("B,n,kp", [(1, 64, 4), (8, 64, 4), (5, 33, 3)])
def test_serve_step_matches_legacy_staged_step_bitwise(B, n, kp):
    """THE acceptance property: the plane's fused step body reproduces
    the pre-fusion three-stage body bitwise — labels, centers, center
    mask, and core weights — on heterogeneous k^(z) request batches.
    (The mesh CI job re-runs the sharded equivalent in test_plane.py at
    2 and 8 forced devices.)"""
    k, d = 9, 11
    cfg = StreamConfig(k=k, k_prime=kp, d=d, capacity=64, batch_size=B,
                       bucket_sizes=(n,),
                       local_kw={"approx_iters": 2, "max_iters": 9})

    def legacy(tau, keys, data, point_mask, k_valid):
        loc = batched_local_kmeans(keys, data, k_max=cfg.k_prime,
                                   k_valid=k_valid, point_mask=point_mask,
                                   **cfg.local_kw)
        ctr = jax.vmap(lambda c, m: server.assign_new_device(c, m, tau))(
            loc.centers, loc.center_mask)
        labels = server.induced_labels(ctr, loc.assign)
        return (labels, loc.centers, loc.center_mask,
                server.core_weights(loc.core_counts))

    rng = np.random.default_rng(B * 31 + n)
    tau = jnp.asarray(rng.normal(size=(k, d)) * 4, jnp.float32)
    data = jnp.asarray(rng.normal(size=(B, n, d)) * 3, jnp.float32)
    pm = jnp.asarray(rng.random((B, n)) < 0.9)
    kv = jnp.asarray(rng.integers(1, kp + 1, size=(B,)), jnp.int32)
    keys = jax.vmap(jax.random.fold_in, (None, 0))(
        jax.random.PRNGKey(3), jnp.arange(B))

    got = jax.jit(_make_step(cfg))(tau, keys, data, pm, kv)
    want = jax.jit(legacy)(tau, keys, data, pm, kv)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_local_kmeans_split_is_bitwise():
    """local_kmeans == local_prepare + step-4 lloyd, factored not
    changed: same centers/assign/core_counts bitwise."""
    key = jax.random.PRNGKey(5)
    A = jax.random.normal(jax.random.PRNGKey(1), (50, 6)) * 3
    pm = jnp.arange(50) < 44
    whole = local_kmeans(key, A, k_max=4, k_valid=3, point_mask=pm,
                         approx_iters=3, max_iters=20)
    prep = local_prepare(key, A, k_max=4, k_valid=3, point_mask=pm,
                         approx_iters=3)
    res = lloyd(A.astype(jnp.float32), prep.theta,
                center_mask=prep.center_mask, point_mask=pm, max_iters=20)
    np.testing.assert_array_equal(np.asarray(whole.centers),
                                  np.asarray(res.centers))
    np.testing.assert_array_equal(np.asarray(whole.assign),
                                  np.asarray(res.assign))
    np.testing.assert_array_equal(np.asarray(whole.core_counts),
                                  np.asarray(prep.core_counts))
    np.testing.assert_array_equal(np.asarray(whole.center_mask),
                                  np.asarray(prep.center_mask))


def test_split_local_kw():
    prep_kw, iters = split_local_kw({"approx_iters": 3, "max_iters": 17})
    assert prep_kw == {"approx_iters": 3} and iters == 17
    prep_kw, iters = split_local_kw({})
    assert prep_kw == {} and iters == 100  # the local_kmeans default


# ----------------------------------------------- Pallas kernel parity --

KERNEL_SHAPES = [
    (1, 16, 8, 2, 4),     # minimal
    (3, 40, 37, 5, 9),    # ragged everything
    (2, 64, 128, 4, 16),  # lane-aligned d (no x copy in the dispatcher)
    (4, 24, 7, 3, 140),   # k above one lane tile
]


@pytest.mark.parametrize("B,n,d,kp,k", KERNEL_SHAPES)
@pytest.mark.parametrize("dtype", ["f32", "bf16"])
def test_kernel_matches_oracle(B, n, d, kp, k, dtype):
    """Interpret-mode kernel vs oracle: integer outputs and centers
    exact (fixed seeds), min-dists to the reduction-order tolerance of
    the zero-padded lane axis. A min-dist is the expansion
    ||x||^2 - 2 x.c + ||c||^2, whose rounding grows with ||x||^2 and
    cancels to ~0 for a point on its center, so its tolerance scales
    with the point's squared norm: 32 eps ||x||^2, which also covers the
    kernel compiled for a TPU v5e (15 eps ||x||^2 from the CPU oracle at
    d=784)."""
    tau, x, c0, cm, pm = _request_batch(n * 13 + k, B, n, d, kp, k)
    ref_out = ref.solve_attach(x, c0, tau, cm, pm, max_iters=7,
                               dtype=dtype)
    pal_out = solve_attach_fused(x, c0, tau, cm, pm, max_iters=7,
                                 dtype=dtype, interpret=True)
    np.testing.assert_array_equal(np.asarray(pal_out[0]),
                                  np.asarray(ref_out[0]))       # labels
    xs = np.asarray(x.astype(jnp.bfloat16) if dtype == "bf16" else x,
                    np.float32)
    sq = np.sum(xs * xs, axis=-1)
    eps = np.finfo(np.float32).eps
    got, want = np.asarray(pal_out[1]), np.asarray(ref_out[1])
    assert np.all(np.abs(got - want)
                  <= 1e-4 + 1e-4 * np.abs(want) + 32 * eps * sq)  # min-dist
    np.testing.assert_allclose(np.asarray(pal_out[2]),
                               np.asarray(ref_out[2]),
                               rtol=1e-4, atol=1e-4)            # centers
    np.testing.assert_array_equal(np.asarray(pal_out[3]),
                                  np.asarray(ref_out[3]))       # ctr lbls


def test_kernel_default_masks():
    tau, x, c0, _, _ = _request_batch(2, 2, 16, 5, 3, 6)
    got = solve_attach_fused(x, c0, tau, max_iters=5, interpret=True)
    want = ref.solve_attach(x, c0, tau, max_iters=5)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[3]), np.asarray(want[3]))


def test_ops_dispatch_solve_attach(monkeypatch):
    """ops.solve_attach routes ref | pallas like every other kernel."""
    from repro.kernels import ops
    tau, x, c0, cm, pm = _request_batch(3, 2, 16, 3, 2, 5)
    want = ref.solve_attach(x, c0, tau, cm, pm, max_iters=4)
    for impl in ("ref", "pallas"):
        monkeypatch.setitem(ops._STATE, "impl", impl)
        got = ops.solve_attach(x, c0, tau, cm, pm, max_iters=4)
        np.testing.assert_array_equal(np.asarray(got[0]),
                                      np.asarray(want[0]))


# ------------------------------------------------------- bf16 bounds ---

def test_bf16_within_tolerance_of_f32_oracle():
    """On separated clusters (the regime the paper's guarantees cover),
    bf16 storage must not move a single induced label, and centers stay
    within bf16 rounding of the f32 oracle."""
    rng = np.random.default_rng(0)
    k, kp, d, B, n = 8, 4, 16, 4, 64
    means = jnp.asarray(rng.normal(size=(k, d)) * 20, jnp.float32)
    comp = rng.integers(0, k, size=(B, n))
    x = means[comp] + jnp.asarray(rng.normal(size=(B, n, d)),
                                  jnp.float32)
    c0 = means[rng.integers(0, k, size=(B, kp))] + 0.5
    f32 = ref.solve_attach(x, c0, means, max_iters=20, dtype="f32")
    b16 = ref.solve_attach(x, c0, means, max_iters=20, dtype="bf16")
    np.testing.assert_array_equal(np.asarray(b16[0]), np.asarray(f32[0]))
    np.testing.assert_array_equal(np.asarray(b16[3]), np.asarray(f32[3]))
    np.testing.assert_allclose(np.asarray(b16[2]), np.asarray(f32[2]),
                               rtol=2e-2, atol=2e-1)
    assert b16[2].dtype == jnp.float32  # outputs stay f32 (fold schema)


def test_serve_dtype_bf16_step_runs():
    cfg = StreamConfig(k=6, k_prime=3, d=5, capacity=8, batch_size=2,
                       bucket_sizes=(32,), serve_dtype="bf16",
                       local_kw={"approx_iters": 2, "max_iters": 5})
    rng = np.random.default_rng(7)
    tau = jnp.asarray(rng.normal(size=(6, 5)) * 4, jnp.float32)
    data = jnp.asarray(rng.normal(size=(2, 32, 5)), jnp.float32)
    pm = jnp.ones((2, 32), bool)
    kv = jnp.full((2,), 3, jnp.int32)
    keys = jax.vmap(jax.random.fold_in, (None, 0))(
        jax.random.PRNGKey(0), jnp.arange(2))
    labels, centers, cmask, w, iters = jax.jit(_make_step(cfg))(
        tau, keys, data, pm, kv)
    assert labels.shape == (2, 32) and labels.dtype == jnp.int32
    assert centers.dtype == jnp.float32
    assert np.all((np.asarray(labels) >= 0) & (np.asarray(labels) < 6))


# ------------------------------------------------- config validation ---

def test_serve_dtype_validation():
    with pytest.raises(StreamConfigError, match="serve_dtype"):
        StreamConfig(k=4, k_prime=2, d=3, capacity=8, serve_dtype="f16")
    from repro.fed.api import FederationPlan, PlanError
    with pytest.raises(PlanError, match="FederationPlan.serve_dtype"):
        FederationPlan(k=4, k_prime=2, d=3, serve_dtype="fp8")
    assert FederationPlan(k=4, k_prime=2, d=3,
                          serve_dtype="bf16").stream_config().serve_dtype \
        == "bf16"


# -------------------------------------------- analytic traffic model ---

def test_traffic_model_fusion_gain():
    """The model the roofline gate pins: the fused kernel's HBM bytes
    are iteration-free and >= 25% below the legacy loop's on every
    serve bucket (already at a single Lloyd iteration)."""
    for n in (64, 256, 1024):
        fused = hbm_bytes(8, n, 64, 4, 16)
        assert fused == hbm_bytes(8, n, 64, 4, 16)  # deterministic
        for iters in (1, 8, 100):
            legacy = hbm_bytes_legacy(8, n, 64, 4, 16, iters)
            assert 1.0 - fused / legacy >= 0.25, (n, iters)
    # fused traffic does not depend on the iteration bound; legacy grows.
    assert (hbm_bytes_legacy(8, 256, 64, 4, 16, 100)
            > hbm_bytes_legacy(8, 256, 64, 4, 16, 1))
    # bf16 storage strictly shrinks the fused footprint.
    assert hbm_bytes(8, 256, 64, 4, 16, "bf16") < hbm_bytes(8, 256, 64, 4, 16)
    assert kernel_flops(8, 256, 64, 4, 16, 8) > 0
