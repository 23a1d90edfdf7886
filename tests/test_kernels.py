"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracle,
swept over shapes and dtypes, plus hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import given, settings, st

from repro.kernels import ref
from repro.kernels.kmeans_update import kmeans_update as pk_update
from repro.kernels.pdist_argmin import pairwise_argmin as pk_argmin
from repro.kernels.swa_decode import swa_decode_attention as pk_swa

SHAPES = [(16, 8, 3), (100, 33, 7), (256, 128, 130), (70, 260, 5),
          (130, 513, 129)]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("n,d,k", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_pairwise_argmin_matches_ref(n, d, k, dtype):
    kx, kc = jax.random.split(jax.random.PRNGKey(n + d + k))
    x = (jax.random.normal(kx, (n, d)) * 3).astype(dtype)
    c = (jax.random.normal(kc, (k, d)) * 3).astype(dtype)
    idx, val = pk_argmin(x, c, bn=32, bd=128, interpret=True)
    ridx, rval = ref.assign_argmin(x, c)
    # Argmin ties can differ legally; compare distances at chosen indices.
    rd = np.asarray(ref.pairwise_sq_dists(x, c))
    np.testing.assert_allclose(rd[np.arange(n), np.asarray(idx)],
                               rd[np.arange(n), np.asarray(ridx)],
                               rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(val), np.asarray(rval),
                               rtol=2e-2, atol=2e-2)


def test_pairwise_argmin_center_mask():
    x = jnp.zeros((4, 6))
    c = jnp.stack([jnp.zeros(6), jnp.ones(6) * 0.1, jnp.ones(6)])
    cm = jnp.array([False, True, True])
    idx, _ = pk_argmin(x, c, cm, bn=32, bd=128, interpret=True)
    assert np.all(np.asarray(idx) == 1)


@pytest.mark.parametrize("n,d,k", SHAPES)
def test_kmeans_update_matches_ref(n, d, k):
    key = jax.random.PRNGKey(n * 7 + k)
    x = jax.random.normal(key, (n, d))
    assign = jax.random.randint(jax.random.PRNGKey(1), (n,), -1, k)
    sums, cnt = pk_update(x, assign.astype(jnp.int32), k, bn=64,
                          interpret=True)
    rsums, rcnt = ref.kmeans_update(x, assign, k)
    np.testing.assert_allclose(np.asarray(sums), np.asarray(rsums),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(cnt), np.asarray(rcnt))


@pytest.mark.parametrize("n,d,k", SHAPES)
def test_kmeans_update_weighted_matches_ref(n, d, k):
    """The weighted center update (server Lloyd round with core-set
    weights) through the Pallas kernel vs the oracle."""
    key = jax.random.PRNGKey(n * 11 + k)
    kx, kw = jax.random.split(key)
    x = jax.random.normal(kx, (n, d))
    assign = jax.random.randint(jax.random.PRNGKey(2), (n,), -1, k)
    w = jax.random.uniform(kw, (n,), jnp.float32, 0.0, 5.0)
    sums, cnt = pk_update(x, assign.astype(jnp.int32), k, w, bn=64,
                          interpret=True)
    rsums, rcnt = ref.kmeans_update(x, assign, k, w)
    np.testing.assert_allclose(np.asarray(sums), np.asarray(rsums),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(cnt), np.asarray(rcnt),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("b,h,kvh,dh,W", [(2, 8, 2, 64, 128),
                                          (1, 4, 4, 32, 200),
                                          (3, 8, 1, 128, 384)])
@pytest.mark.parametrize("dtype", DTYPES)
def test_swa_decode_matches_ref(b, h, kvh, dh, W, dtype):
    keys = jax.random.split(jax.random.PRNGKey(b + W), 4)
    q = (jax.random.normal(keys[0], (b, h, dh)) * 0.5).astype(dtype)
    kw = (jax.random.normal(keys[1], (b, W, kvh, dh)) * 0.5).astype(dtype)
    vw = (jax.random.normal(keys[2], (b, W, kvh, dh)) * 0.5).astype(dtype)
    # Ragged validity: device i has valid window min(W, 17*i+30).
    lens = np.minimum(W, 17 * np.arange(b) + 30)
    bias = np.zeros((b, W), np.float32)
    for i, L in enumerate(lens):
        bias[i, L:] = -1e30
    bias = jnp.asarray(bias)
    scale = 1.0 / np.sqrt(dh)
    out = pk_swa(q, kw, vw, bias, scale, bw=64, interpret=True)
    want = ref.swa_decode_attention(q, kw, vw, bias, scale)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-3
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


# -------- pairwise_argmin edge shapes: ragged n/d, k-tiling, masks ------

from repro.kernels import ops

EDGE_SHAPES = [
    (37, 5, 7),      # n % bn != 0, d far below bd
    (64, 130, 7),    # d above bd, non-multiple
    (50, 33, 129),   # k > 128: two k-blocks at bk=128
    (100, 70, 300),  # k > 256: three k-blocks
]


@pytest.mark.parametrize("n,d,k", EDGE_SHAPES)
def test_pairwise_argmin_edge_shapes_match_ref(n, d, k):
    kx, kc, km = jax.random.split(jax.random.PRNGKey(n * 3 + k), 3)
    x = jax.random.normal(kx, (n, d)) * 3
    c = jax.random.normal(kc, (k, d)) * 3
    cm = jax.random.bernoulli(km, 0.8, (k,)).at[0].set(True)
    idx, val = pk_argmin(x, c, cm, bn=32, bd=64, bk=128, interpret=True)
    ridx, rval = ref.assign_argmin(x, c, cm)
    rd = np.asarray(jnp.where(cm[None, :], ref.pairwise_sq_dists(x, c),
                              ref.MASKED_DIST))
    np.testing.assert_allclose(rd[np.arange(n), np.asarray(idx)],
                               rd[np.arange(n), np.asarray(ridx)],
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(val), np.asarray(rval),
                               rtol=1e-4, atol=1e-4)
    assert np.all(np.asarray(cm)[np.asarray(idx)])  # never a masked center


def test_pairwise_argmin_single_valid_center_k_tiled():
    """One valid center living in the SECOND k-block: every point must
    find it across the block-merge."""
    n, d, k, only = 40, 9, 200, 137
    x = jax.random.normal(jax.random.PRNGKey(0), (n, d))
    c = jax.random.normal(jax.random.PRNGKey(1), (k, d))
    cm = jnp.zeros((k,), bool).at[only].set(True)
    idx, val = pk_argmin(x, c, cm, bn=32, bd=64, bk=128, interpret=True)
    assert np.all(np.asarray(idx) == only)
    want = np.asarray(ref.pairwise_sq_dists(x, c))[:, only]
    np.testing.assert_allclose(np.asarray(val), want, rtol=1e-4, atol=1e-4)


def test_pairwise_argmin_interpret_autodetect():
    """The interpret default routes through ops' platform auto-detect
    (compiled on TPU, interpret elsewhere) instead of hardcoding True."""
    assert ops.resolve_interpret(None) == (jax.default_backend() != "tpu")
    assert ops.resolve_interpret(True) is True
    assert ops.resolve_interpret(False) is False
    x = jax.random.normal(jax.random.PRNGKey(0), (17, 6))
    c = jax.random.normal(jax.random.PRNGKey(1), (5, 6))
    idx, _ = pk_argmin(x, c)  # no interpret kwarg: auto-detected path
    ridx, _ = ref.assign_argmin(x, c)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ridx))


def _on_tpu(monkeypatch):
    """Pretend the default backend is a TPU (nothing is compiled)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setitem(ops._STATE, "interpret", None)


@pytest.mark.parametrize("request_interpret", [
    lambda: ops._interpret(),                      # env var, first call
    lambda: ops.set_backend("pallas", interpret=True),
    lambda: ops.resolve_interpret(True),
])
def test_interpret_refused_on_tpu(monkeypatch, request_interpret):
    """No fallback that hides the device: every way of asking for
    interpret mode fails by name on a TPU backend."""
    _on_tpu(monkeypatch)
    monkeypatch.setenv("REPRO_KERNEL_INTERPRET", "1")
    before = dict(ops._STATE)
    with pytest.raises(ops.InterpretOnTPUError):
        request_interpret()
    assert ops._STATE == before


def test_tpu_default_compiles(monkeypatch):
    """Without an override a TPU backend resolves to compiled kernels,
    and REPRO_KERNEL_INTERPRET=0 is still honoured there."""
    _on_tpu(monkeypatch)
    monkeypatch.delenv("REPRO_KERNEL_INTERPRET", raising=False)
    assert ops.resolve_interpret(None) is False
    monkeypatch.setitem(ops._STATE, "interpret", None)
    monkeypatch.setenv("REPRO_KERNEL_INTERPRET", "0")
    assert ops.resolve_interpret(None) is False


def test_kmeans_update_interpret_autodetect():
    """kmeans_update's interpret default resolves through ops like the
    other kernels (interpret off the TPU, compiled on it)."""
    x = jax.random.normal(jax.random.PRNGKey(4), (40, 6))
    assign = jax.random.randint(jax.random.PRNGKey(5), (40,), -1, 3)
    sums, cnt = pk_update(x, assign.astype(jnp.int32), 3)
    rsums, rcnt = ref.kmeans_update(x, assign, 3)
    np.testing.assert_allclose(np.asarray(sums), np.asarray(rsums),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(rcnt))


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_assign_argmin_chunked_matches_monolithic(impl):
    """The streaming driver (fixed-size row tiles) is exact vs the
    one-call path, on both backends, ragged final chunk included."""
    x = jax.random.normal(jax.random.PRNGKey(2), (777, 10))
    c = jax.random.normal(jax.random.PRNGKey(3), (9, 10))
    cm = jnp.arange(9) != 4
    prev_impl, prev_interp = ops.get_backend(), ops._STATE["interpret"]
    try:
        ops.set_backend(impl)
        ci, cv = ops.assign_argmin_chunked(x, c, cm, chunk=100)
        mi, mv = ops.assign_argmin(x, c, cm)
    finally:
        ops.set_backend(prev_impl, prev_interp)
    np.testing.assert_array_equal(np.asarray(ci), np.asarray(mi))
    np.testing.assert_allclose(np.asarray(cv), np.asarray(mv),
                               rtol=1e-5, atol=1e-5)


# ---------------- hypothesis property tests ----------------

@settings(max_examples=15, deadline=None)
@given(n=st.integers(2, 80), d=st.integers(1, 40), k=st.integers(1, 20),
       seed=st.integers(0, 2 ** 16))
def test_property_argmin_is_true_min(n, d, k, seed):
    kx, kc = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(kx, (n, d))
    c = jax.random.normal(kc, (k, d))
    idx, val = pk_argmin(x, c, bn=32, bd=64, interpret=True)
    d2 = np.asarray(ref.pairwise_sq_dists(x, c))
    np.testing.assert_allclose(np.asarray(val), d2.min(1), rtol=1e-4,
                               atol=1e-4)


@settings(max_examples=15, deadline=None)
@given(n=st.integers(1, 100), k=st.integers(1, 10),
       seed=st.integers(0, 2 ** 16))
def test_property_update_conserves_mass(n, k, seed):
    """sum of per-cluster sums == sum of valid points (mass conservation)."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (n, 5))
    assign = jax.random.randint(jax.random.PRNGKey(seed + 1), (n,), -1, k)
    sums, cnt = pk_update(x, assign.astype(jnp.int32), k, bn=32,
                          interpret=True)
    valid = np.asarray(assign) >= 0
    np.testing.assert_allclose(np.asarray(sums).sum(0),
                               np.asarray(x)[valid].sum(0), rtol=1e-4,
                               atol=1e-4)
    assert np.asarray(cnt).sum() == valid.sum()


# ---------------------------------------------------------------- moe --
from repro.kernels.moe_dispatch import moe_combine as pk_combine
from repro.kernels.moe_dispatch import moe_dispatch as pk_dispatch

MOE_SHAPES = [(32, 8, 24), (100, 130, 48), (64, 256, 16)]  # (T, d, S)


@pytest.mark.parametrize("T,d,S", MOE_SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_moe_dispatch_matches_ref(T, d, S, dtype):
    key = jax.random.PRNGKey(T + d + S)
    kx, ks, kv = jax.random.split(key, 3)
    x = (jax.random.normal(kx, (T, d)) * 2).astype(dtype)
    src = jax.random.randint(ks, (S,), 0, T)
    valid = jax.random.bernoulli(kv, 0.8, (S,))
    out = pk_dispatch(x, src, valid, bd=128, interpret=True)
    rout = ref.moe_dispatch(x, src, valid)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(rout, np.float32),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("T,d,S", MOE_SHAPES)
@pytest.mark.parametrize("top_k", [1, 2, 4])
def test_moe_combine_matches_ref(T, d, S, top_k):
    key = jax.random.PRNGKey(T * top_k)
    ky, ks, kg = jax.random.split(key, 3)
    ybuf = (jax.random.normal(ky, (S, d)) * 2).astype(jnp.bfloat16)
    slot = jax.random.randint(ks, (T * top_k,), 0, S)
    gates = jax.random.uniform(kg, (T * top_k,), jnp.float32)
    out = pk_combine(ybuf, slot, gates, top_k=top_k, bd=128, interpret=True)
    rout = ref.moe_combine(ybuf, slot, gates, top_k)
    np.testing.assert_allclose(np.asarray(out), np.asarray(rout),
                               rtol=2e-2, atol=2e-2)


@given(T=st.integers(4, 40), d=st.integers(1, 70),
       frac=st.floats(0.0, 1.0))
@settings(max_examples=20, deadline=None)
def test_moe_dispatch_property_zero_invalid(T, d, frac):
    """Invalid slots are exactly zero; valid slots bit-equal their row."""
    S = 2 * T
    key = jax.random.PRNGKey(T * d + 1)
    kx, ks, kv = jax.random.split(key, 3)
    x = jax.random.normal(kx, (T, d), jnp.float32)
    src = jax.random.randint(ks, (S,), 0, T)
    valid = jax.random.bernoulli(kv, frac, (S,))
    out = np.asarray(pk_dispatch(x, src, valid, bd=128, interpret=True))
    xv = np.asarray(x)
    for s in range(S):
        if bool(valid[s]):
            np.testing.assert_array_equal(out[s], xv[int(src[s])])
        else:
            assert (out[s] == 0).all()
