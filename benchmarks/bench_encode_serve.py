"""Ingestion-encoder serving benchmark (DESIGN.md §17): the fused
batched encode+solve+attach step vs the naive front-end that encodes
each request in its own dispatch and only then runs the serve step.
Both paths run the SAME encoder forward and the SAME fused solve+attach
on identical inputs, so the measured gap is the batching win of folding
the encode stage into the one jitted serve dispatch: 1 call per batch
instead of B encode calls + 1 serve call.

Rows:
  * ``encode_step_fused`` / ``encode_step_unbatched`` — median us per
    batch on identical inputs, with pts_per_s derived.
  * ``encode_speedup`` — unbatched_us / fused_us, asserted >= 3.0
    in-row (the PR's acceptance bar, bench_route idiom: a regression
    errors the bench into zero rows and the CI ``--require encode_``
    gate fails).
  * ``encode_session`` — end-to-end encoded serving through the
    streaming stack (submit raw (n, seq, d) sequences, bucketed over
    (n_pad, seq_rung)), with the steady-state recompile count across
    the post-warmup waves asserted zero in-row.

The speedup row is compared against the committed baseline
(``benchmarks/baselines/BENCH_encode_ci.json``) by the CI perf gate.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import row, time_call
from repro.fed import plane as plane_mod
from repro.fed.api import FederationPlan, Session
from repro.fed.stream import StreamConfig
from repro.models.encoder import apply_encoder, init_encoder

# Small per-request shapes: the unbatched baseline pays one dispatch +
# jit-call sync per request, which is exactly the overhead the fused
# plane amortizes — the realistic serving regime for many small
# devices, not one giant batch.
K, KP, D = 16, 3, 16
B, N, S = 32, 4, 8
ENC = "qwen1.5-0.5b"


def _cfg():
    return StreamConfig(k=K, k_prime=KP, d=D, capacity=64, batch_size=B,
                        bucket_sizes=(N,), encoder=ENC,
                        encode_seq_len=S)


def _step_inputs(cfg):
    key = jax.random.PRNGKey(0)
    kt, kd, ke, kk = jax.random.split(key, 4)
    tau = jax.random.normal(kt, (K, D), jnp.float32) * 8.0
    data = jax.random.normal(kd, (B, N, S, D), jnp.float32)
    pmask = jnp.ones((B, N), jnp.bool_)
    tmask = jnp.ones((B, N, S), jnp.bool_)
    keys = jax.random.split(kk, B).astype(jnp.uint32).reshape(B, 2)
    kv = jnp.full((B,), KP, jnp.int32)
    enc = init_encoder(ke, cfg.encoder_spec())
    # The plan's composed step signature (fed/plane._serve_step).
    return (tau, enc, None), keys, data, pmask, kv, tmask


def _unbatched(cfg):
    """The naive front-end: B separate jitted encode dispatches, then
    the identical serve step on the stacked embeddings."""
    spec = cfg.encoder_spec()
    enc_fn = jax.jit(lambda p, x, m: apply_encoder(
        p, x, m, spec, encode_dtype=cfg.encode_dtype))
    serve = jax.jit(plane_mod._make_step(cfg))

    def step(params, keys, data, pmask, kv, tmask):
        tau, enc, _ = params
        embs = [enc_fn(enc, data[i], tmask[i]) for i in range(B)]
        return serve(tau, keys, jnp.stack(embs), pmask, kv)

    return step


def _session_leg(full: bool):
    """End-to-end encoded serving through the streaming stack; returns
    (pts_per_s, steady-state recompiles past wave 1, tau_version)."""
    waves = 6 if full else 3
    rng = np.random.default_rng(0)
    tau = np.asarray(rng.normal(size=(K, D)) * 8, np.float32)
    plan = FederationPlan(k=K, k_prime=KP, d=D, capacity=256,
                          batch_size=B, bucket_sizes=(N,), encoder=ENC,
                          encode_seq_len=S)
    sess = Session.from_tau(plan, tau)
    reqs = [np.asarray(rng.normal(size=(N, S, D)), np.float32)
            for _ in range(waves * B)]
    sess.serve(reqs[:B])                               # compile warmup
    warm = sess.stats()["plane_compiles"]
    served, t0 = 0, time.perf_counter()
    for lo in range(B, waves * B, B):
        out = sess.serve(reqs[lo:lo + B])
        served += sum(lbl.shape[0] for lbl in out)
    dt = time.perf_counter() - t0
    steady = sess.stats()["plane_compiles"] - warm
    return served / dt, steady, sess.tau_version


def run(full: bool):
    cfg = _cfg()
    args = _step_inputs(cfg)
    repeats = 11 if full else 5
    fused = jax.jit(plane_mod._serve_step(cfg))
    unbatched = _unbatched(cfg)
    pts = B * N
    rows = []
    us = {}
    for name, fn in (("fused", fused), ("unbatched", unbatched)):
        u, out = time_call(fn, *args, repeats=repeats, warmup=2)
        us[name] = u
        labels = np.asarray(out[0])
        rows.append(row(f"encode_step_{name}", u,
                        f"pts_per_s={pts / (u / 1e6):.0f};"
                        f"labels_in_k={int((labels < K).all())}"))
    # Both paths must be the same computation — the speedup is pure
    # dispatch amortization, not a different answer.
    np.testing.assert_array_equal(
        np.asarray(fused(*args)[0]), np.asarray(unbatched(*args)[0]))
    speedup = us["unbatched"] / us["fused"]
    # §17 acceptance bar: the fused encode+serve pipeline >= 3x the
    # per-request unbatched front-end's points/sec on identical inputs.
    assert speedup >= 3.0, (speedup, us)
    rows.append(row("encode_speedup", 0.0,
                    f"speedup={speedup:.2f};B={B};N={N};S={S};d={D};"
                    f"enc={ENC}"))
    pps, steady, tv = _session_leg(full)
    assert steady == 0, f"steady-state recompiles: {steady}"
    rows.append(row("encode_session", 0.0,
                    f"pts_per_s={pps:.0f};steady_recompiles={steady};"
                    f"tau_version={tv}"))
    return rows
