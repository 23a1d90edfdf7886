"""Latent-space ingestion front-end (DESIGN.md §17): the encoder stage
ahead of the local solve.

With ``FederationPlan.encoder=<config>`` devices submit raw (n, seq, d)
token/patch sequences; the serve plane encodes them (bf16-storage or
f32, f32-accumulate, masked-mean pooled to d) and runs the UNCHANGED
fused solve+attach — fold, drift, autoscale, and routed heads all
operate on the embeddings. Covers: end-to-end determinism, (n, seq)
bucketing and compile-count bounds, checkpoint schema v6 (round-trip,
tag-mismatch refusal, pre-v6 restore with a fresh deterministic
encoder), submit/plan validation, the encoder+heads combination, and
single-host vs sharded bitwise parity (the CI mesh leg runs this file
at 2 and 8 forced host devices).
"""
import numpy as np
import pytest

import jax

from repro.fed.api import FederationPlan, PlanError, Session
from repro.fed.plane import ServePlaneError
from repro.fed.stream import StreamConfigError
from repro.utils.compat import make_mesh

K, KP, D = 8, 3, 16
ENC = "qwen1.5-0.5b"
SEQ = 16
NDEV = jax.device_count()


def _plan(**kw):
    base = dict(k=K, k_prime=KP, d=D, capacity=128, batch_size=2,
                bucket_sizes=(16, 32), encoder=ENC, encode_seq_len=SEQ)
    base.update(kw)
    return FederationPlan(**base)


def _tau(seed=0):
    rng = np.random.default_rng(seed)
    return np.asarray(rng.normal(size=(K, D)) * 4, np.float32)


def _token_requests(count, seed, n_range=(4, 14), s_range=(2, SEQ)):
    """``count`` raw-sequence requests with varied (n, seq) shapes."""
    rng = np.random.default_rng(seed)
    reqs = []
    for _ in range(count):
        n = int(rng.integers(*n_range))
        s = int(rng.integers(s_range[0], s_range[1] + 1))
        reqs.append(np.asarray(rng.normal(size=(n, s, D)), np.float32))
    return reqs


# ------------------------------------------------- end-to-end serve --


def test_encoded_serve_end_to_end_deterministic():
    """Two identical sessions over the same raw-sequence stream agree
    bitwise on every label, version, and fold-state leaf; the encoder
    counters advance."""
    reqs = _token_requests(7, seed=1)
    outs, states = [], []
    for _ in range(2):
        sess = Session.from_tau(_plan(), _tau())
        outs.append(sess.serve_versioned(reqs))
        states.append(sess.service.state)
        st = sess.stats()["encoder"]
        assert st["mode"] == ENC and st["seq_len"] == SEQ
        assert st["encoded_points"] == sum(r.shape[0] for r in reqs)
    for (la, va), (lb, vb) in zip(outs[0], outs[1]):
        np.testing.assert_array_equal(la, lb)
        assert va == vb
        assert la.dtype == np.int32
    for x, y in zip(jax.tree.leaves(states[0]), jax.tree.leaves(states[1])):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    for r, (lbl, _) in zip(reqs, outs[0]):
        assert lbl.shape == (r.shape[0],)
        assert set(np.unique(lbl)) <= set(range(K))


def test_bucketing_over_n_and_seq_bounds_compiles():
    """Requests group by (n_pad, seq_rung): same-rung shapes share one
    compiled encode signature, a new seq rung adds exactly one, and
    replaying the same shapes adds none."""
    sess = Session.from_tau(_plan(), _tau())
    svc = sess.service
    rng = np.random.default_rng(3)

    def req(n, s):
        return np.asarray(rng.normal(size=(n, s, D)), np.float32)

    assert svc._bucket_key(req(5, 5)) == (16, 8)
    assert svc._bucket_key(req(7, 8)) == (16, 8)
    assert svc._bucket_key(req(5, 9)) == (16, 16)  # next pow2 rung
    assert svc._bucket_key(req(20, 3)) == (32, 8)

    sess.serve([req(5, 5), req(7, 8)])        # one (16, 8) group
    c1 = svc.plane.compile_count
    sess.serve([req(6, 6), req(4, 7)])        # same rung: no new sig
    assert svc.plane.compile_count == c1
    sess.serve([req(5, 12)])                  # new seq rung
    assert svc.plane.compile_count == c1 + 1


def test_submit_rejects_overlong_and_empty_sequences():
    sess = Session.from_tau(_plan(), _tau())
    rng = np.random.default_rng(5)
    with pytest.raises(StreamConfigError, match="encode_seq_len"):
        sess.submit(np.asarray(rng.normal(size=(4, SEQ + 1, D)),
                               np.float32))
    with pytest.raises(StreamConfigError, match="encode_seq_len"):
        sess.submit(np.asarray(rng.normal(size=(4, 0, D)), np.float32))


def test_plan_validation_named_errors():
    with pytest.raises(PlanError, match="FederationPlan.encoder"):
        _plan(encoder="not-a-config")
    with pytest.raises(PlanError, match="FederationPlan.encode_dtype"):
        _plan(encode_dtype="f16")
    with pytest.raises(PlanError, match="FederationPlan.encode_seq_len"):
        _plan(encode_seq_len=0)
    # The plane's one step refuses a call that leaves out the encoder
    # params and token mask of an encoder-on plan.
    plane = Session.from_tau(_plan(), _tau()).service.plane
    with pytest.raises(ServePlaneError, match="encoder"):
        plane.step(_tau(), None, np.zeros((2, 16, SEQ, D)), None, None)


# ------------------------------------------------ checkpoint schema --


def test_v6_checkpoint_roundtrip_bitwise(tmp_path):
    """Encoder params and counters ride the v6 checkpoint: restore +
    serve is bitwise identical to the uninterrupted session."""
    plan = _plan(encode_dtype="bf16")
    live = Session.from_tau(plan, _tau())
    reqs = _token_requests(5, seed=7)
    live.serve(reqs[:3])
    path = str(tmp_path / "v6.npz")
    live.save(path)
    replica = Session.restore(path, plan)
    for a, b in zip(jax.tree.leaves(live.service.encoder),
                    jax.tree.leaves(replica.service.encoder)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert (replica.stats()["encoder"]["encoded_points"]
            == live.stats()["encoder"]["encoded_points"])
    out_a = live.serve_versioned(reqs[3:])
    out_b = replica.serve_versioned(reqs[3:])
    for (la, va), (lb, vb) in zip(out_a, out_b):
        np.testing.assert_array_equal(la, lb)
        assert va == vb


def test_v6_checkpoint_tag_mismatch_refuses(tmp_path):
    """A checkpoint written under one encoder config refuses to load
    under another, naming both tags."""
    live = Session.from_tau(_plan(), _tau())
    live.serve(_token_requests(2, seed=9))
    path = str(tmp_path / "tag.npz")
    live.save(path)
    with pytest.raises(StreamConfigError, match="encoder"):
        Session.restore(path, _plan(encode_seq_len=32))
    with pytest.raises(StreamConfigError, match="encoder"):
        Session.restore(path, _plan(encode_dtype="bf16"))


def test_pre_v6_checkpoint_restores_fresh_deterministic_encoder(tmp_path):
    """A checkpoint written before the encode stage existed (encoder
    off) restores into an encoder-on plan: tau and fold state load,
    the encoder comes up fresh and DETERMINISTIC — two replicas of the
    same old checkpoint serve bitwise-identically."""
    old = Session.from_tau(FederationPlan(k=K, k_prime=KP, d=D,
                                          capacity=128), _tau())
    rng = np.random.default_rng(11)
    old.serve([np.asarray(rng.normal(size=(6, D)), np.float32)])
    path = str(tmp_path / "pre_v6.npz")
    old.save(path)
    ra = Session.restore(path, _plan())
    rb = Session.restore(path, _plan())
    np.testing.assert_array_equal(np.asarray(ra.tau_centers),
                                  np.asarray(old.tau_centers))
    assert ra.stats()["encoder"]["encoded_points"] == 0
    for a, b in zip(jax.tree.leaves(ra.service.encoder),
                    jax.tree.leaves(rb.service.encoder)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    reqs = _token_requests(3, seed=13)
    for a, b in zip(ra.serve(reqs), rb.serve(reqs)):
        np.testing.assert_array_equal(a, b)


# --------------------------------------------------- encoder + heads --


def test_encoder_with_routed_heads():
    """The routed personalization step runs on the embeddings: every
    request gets a prediction in latent space (d-wide), the labels
    match the un-routed encode path bitwise, and the majority-vote
    cluster is a real tau index."""
    reqs = _token_requests(5, seed=15)
    plain = Session.from_tau(_plan(), _tau())
    routed = Session.from_tau(_plan(heads="linear"), _tau())
    base = plain.serve(reqs)
    preds = routed.serve_predict(reqs)
    assert len(preds) == len(reqs)
    for r, lbl, p in zip(reqs, base, preds):
        np.testing.assert_array_equal(p.labels, lbl)
        assert 0 <= int(p.cluster) < K
        assert p.prediction.shape == (D,)
        assert np.all(np.isfinite(p.prediction))


# ------------------------------------------------------ sharded plane --


@pytest.mark.skipif(NDEV < 2, reason="needs >= 2 devices (CI mesh leg)")
def test_sharded_encoded_serve_bitwise_matches_single_host():
    """§17 acceptance: the shard_mapped encode+serve plane is bitwise
    identical to the single-host plane — labels, fold state, and the
    encoder-counter stats all match, with encoder params riding
    replicated like tau."""
    plan_kw = dict(batch_size=2 * NDEV)
    reqs = _token_requests(3 * NDEV + 1, seed=17)
    single = Session.from_tau(_plan(**plan_kw), _tau())
    shard = Session.from_tau(_plan(**plan_kw, serve_axes=("data",)),
                             _tau(), mesh=make_mesh((NDEV,), ("data",)))
    out_a = single.serve_versioned(reqs)
    out_b = shard.serve_versioned(reqs)
    for (la, va), (lb, vb) in zip(out_a, out_b):
        np.testing.assert_array_equal(la, lb)
        assert va == vb
    for x, y in zip(jax.tree.leaves(single.service.state),
                    jax.tree.leaves(shard.service.state)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert shard.service.stats()["serve_shards"] == NDEV
    assert (shard.stats()["encoder"]["encoded_points"]
            == single.stats()["encoder"]["encoded_points"])
