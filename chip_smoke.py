"""Smoke test of the k-FED main path on a TPU.

One one-shot k-FED round, then attach-serving of late devices with fold
and refresh, all through ``FederationPlan`` + ``Session``, at a
FEMNIST-shaped size (LEAF, Caldas et al. arXiv:1812.01097: ~3,550
writers of ~227 samples, 28x28 = 784 features):

  * round: ``structured_devices`` with k=64, k'=8, m0=444 -> Z=3,552
    devices x n=224 points x d=784 (~2.5 GB f32 on the device);
  * serving: ``Session.from_round`` then ``serve_versioned`` on 512
    late-device requests of 16-400 points, batch 64, buckets
    (64, 256, 1024), capacity 4096, refresh every 128 folds;
  * kernels: the same requests again on the compiled Pallas kernels,
    which must give the jnp path's labels request by request, plus the
    fused solve+attach kernel against its f32 oracle at each bucket.

Accuracy against the generating components must reach 0.98 on the round
and on the served requests. With ``--chips 4`` the script runs only the
sharded paths, each against the same computation on one chip: the
sharded round (``topology="sharded"``) against the simulated one, and
the serve plane sharded over four chips (``serve_axes``) against the
single-host plane, bitwise, with every steady batch spread over all
four devices.

  python chip_smoke.py             # one chip
  python chip_smoke.py --chips 4   # the sharded round and serve plane

The last line of stdout is one JSON object naming the device; the exit
code is non-zero, and that line is not printed, when no TPU is found or
any phase fails. Everything runs in this one process.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

# The kernel check's f32 oracle runs on the host's CPU backend, next to
# the TPU, which stays the default: have JAX bring up both.
if "cpu" not in os.environ.get("JAX_PLATFORMS", "cpu").split(","):
    os.environ["JAX_PLATFORMS"] += ",cpu"

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.data.gaussian import late_device_stream, structured_devices
from repro.fed.api import FederationPlan, Session
from repro.kernels import ops, ref
from repro.kernels.solve_attach import solve_attach_fused
from repro.utils.cache import use_compile_cache
from repro.utils.compat import make_mesh
from repro.utils.metrics import clustering_accuracy

# FEMNIST-shaped round; k is 64 rather than 62 so k/k' groups divide.
K, K_PRIME, D = 64, 8, 784
M0, N_PER_COMP = 444, 28            # Z = (K / K_PRIME) * M0, n = 8 * 28
SEP = 60.0
REQUESTS, N_RANGE = 512, (16, 400)
SERVE = dict(batch_size=64, bucket_sizes=(64, 256, 1024), capacity=4096,
             refresh_every=128)
MIN_ACCURACY = 0.98


def log(msg: str) -> None:
    print(msg, flush=True)


def make_round_data():
    return structured_devices(jax.random.PRNGKey(0), k=K, d=D,
                              k_prime=K_PRIME, m0=M0,
                              n_per_comp_dev=N_PER_COMP, sep=SEP)


def make_requests(means, seed: int):
    return late_device_stream(means, K_PRIME, REQUESTS, seed,
                              n_range=N_RANGE)


def round_fn(plan, mesh=None):
    """The one-shot round as one jitted program (``Session.run`` is
    documented as jittable): one compile, and a memory plan XLA sees
    whole."""

    def run(key, data):
        out = Session(plan, mesh=mesh).run(key, data)
        return out.labels, out.tau_centers, out.detail
    return jax.jit(run)


def served_accuracy(out, reqs) -> float:
    """Accuracy of the served labels against the generating components,
    one label matching per tau version (a refresh may renumber tau)."""
    hits = total = 0
    for v in sorted({ver for _, ver in out}):
        pred = np.concatenate([l for l, ver in out if ver == v])
        true = np.concatenate([r[1] for (_, ver), r in zip(out, reqs)
                               if ver == v])
        hits += clustering_accuracy(pred, true, K) * pred.size
        total += pred.size
    return hits / total


FAILURES = []


def check(cond, what: str) -> bool:
    """Record a failed check and go on, so one run reports every phase;
    the script exits non-zero if any check failed."""
    if not cond:
        FAILURES.append(what)
        log(f"FAIL: {what}")
    return bool(cond)


def timed(fn, *args):
    t0 = time.perf_counter()
    out = fn(*args)
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0


# --------------------------------------------------------------- phases --

def round_phase(fm):
    """Simulated one-shot round on one chip; returns its RoundResult."""
    plan = FederationPlan(k=K, k_prime=K_PRIME, d=D)
    (labels, tau, detail), secs = timed(round_fn(plan),
                                        jax.random.PRNGKey(1), fm.data)
    acc = clustering_accuracy(np.asarray(labels), np.asarray(fm.labels), K)
    log(f"round: Z={fm.data.shape[0]} n={fm.data.shape[1]} "
        f"d={fm.data.shape[2]} k={K} k'={K_PRIME}: accuracy {acc:.4f}, "
        f"compile + first call {secs:.1f}s, tau {tuple(tau.shape)}")
    check(acc >= MIN_ACCURACY, f"round accuracy {acc} < {MIN_ACCURACY}")
    return detail


def serve(rr, reqs, tag: str):
    """Serve ``reqs`` through a fresh session seeded from the round."""
    plan = FederationPlan(k=K, k_prime=K_PRIME, d=D, **SERVE)
    sess = Session.from_round(plan, rr)
    t0 = time.perf_counter()
    out = sess.serve_versioned([r[0] for r in reqs], [r[2] for r in reqs])
    secs = time.perf_counter() - t0
    acc = served_accuracy(out, reqs)
    st = sess.stats()
    log(f"serve[{tag}]: {len(reqs)} requests, {st['folded']} folded, tau "
        f"versions {sorted({v for _, v in out})}, accuracy {acc:.4f}, "
        f"compile + first call {secs:.1f}s, plane_compiles "
        f"{st['plane_compiles']}")
    check(acc >= MIN_ACCURACY, f"serve[{tag}] accuracy {acc}")
    check(st["tau_version"] >= 1, f"serve[{tag}]: no refresh ran")
    return sess, out


def steady_rate(sess, reqs, tag: str) -> None:
    """Informational: devices/s of a second, already-compiled pass."""
    t0 = time.perf_counter()
    sess.serve_versioned([r[0] for r in reqs], [r[2] for r in reqs])
    secs = time.perf_counter() - t0
    log(f"serve[{tag}] steady: {len(reqs) / secs:.1f} devices/s "
        f"(informational, host clock), plane_compiles "
        f"{sess.stats()['plane_compiles']}")


def label_mismatches(out, ref_out):
    """Requests whose labels differ between two serve runs. Within a tau
    version the runs share tau's centers, but a refresh re-runs
    Algorithm 2's max-min seeding on folded centers that differ in
    rounding between the two paths, and may number the same centers in
    another order. So each version's ids are matched one to one (largest
    overlaps first) before requests are compared. Returns the differing
    requests and the versions that needed renumbering."""
    bad, renumbered = [], []
    for v in sorted({ver for _, ver in ref_out}):
        idx = [i for i, (_, ver) in enumerate(ref_out) if ver == v]
        a = np.concatenate([ref_out[i][0] for i in idx])
        b = np.concatenate([out[i][0] for i in idx])
        pairs, counts = np.unique(np.stack([a, b]), axis=1,
                                  return_counts=True)
        ids = np.full(K + 1, -2, np.int64)          # index -1 is label -1
        used = set()
        for j in np.argsort(-counts, kind="stable"):
            la, lb = (int(t) for t in pairs[:, j])
            if ids[la] == -2 and lb not in used:
                ids[la] = lb
                used.add(lb)
        if np.any(ids[np.unique(a)] != np.unique(a)):
            renumbered.append(v)
        bad += [i for i in idx
                if not np.array_equal(ids[ref_out[i][0]], out[i][0])]
    return sorted(bad), renumbered


def padded_batch(reqs, n_pad: int, B: int):
    """The first ``B`` requests that fall in the ``n_pad`` bucket, padded
    as the serve plane pads them."""
    ladder = (0,) + tuple(SERVE["bucket_sizes"])
    lo = ladder[ladder.index(n_pad) - 1]
    rows = [r for r in reqs if lo < r[0].shape[0] <= n_pad][:B]
    x = np.zeros((B, n_pad, D), np.float32)
    pm = np.zeros((B, n_pad), bool)
    for i in range(B):
        data = rows[min(i, len(rows) - 1)][0]
        x[i, :data.shape[0]] = data
        pm[i, :data.shape[0]] = True
    return x, pm


def kernel_phase(rr, reqs, ref_out, timing_reqs):
    """The serve stream again on the compiled Pallas kernels."""

    ops.set_backend("pallas", interpret=False)
    try:
        sess, out = serve(rr, reqs, "pallas")
        # The step the plane compiled really holds the Mosaic kernels.
        step = sess.service.plane._plane_for(1)[0]
        B, n_pad = SERVE["batch_size"], SERVE["bucket_sizes"][0]
        hlo = step.lower(
            jax.ShapeDtypeStruct((K, D), jnp.float32),
            jax.ShapeDtypeStruct((B, 2), jnp.uint32),
            jax.ShapeDtypeStruct((B, n_pad, D), jnp.float32),
            jax.ShapeDtypeStruct((B, n_pad), jnp.bool_),
            jax.ShapeDtypeStruct((B,), jnp.int32)).compile().as_text()
        check("tpu_custom_call" in hlo,
              "compiled serve step holds no tpu_custom_call")
        bad_v = [i for i, ((_, vp), (_, vr)) in enumerate(zip(out, ref_out))
                 if vp != vr]
        check(not bad_v, f"pallas tau versions differ on requests {bad_v}")
        bad_l, renumbered = label_mismatches(out, ref_out)
        check(not bad_l, f"pallas labels differ from the jnp path on "
                         f"{len(bad_l)} requests: {bad_l[:10]}")
        check(0 not in renumbered,
              "the round's tau (version 0) labels differ in numbering")
        log(f"kernels: the compiled serve step holds tpu_custom_call: "
            f"{'tpu_custom_call' in hlo}; labels equal the jnp path's on "
            f"{len(out) - len(bad_l)}/{len(out)} requests (tau versions "
            f"whose center ids the two runs numbered differently: "
            f"{renumbered})")

        # The fused solve+attach kernel against its f32 oracle at every
        # bucket: labels exact, min-dists to the tests' tolerance. The
        # oracle runs where the tests run it, on the CPU, in full f32.
        tau = np.asarray(sess.tau_centers)
        oracle = jax.jit(ref.solve_attach)
        eps = float(np.finfo(np.float32).eps)
        for n_pad in SERVE["bucket_sizes"]:
            x, pm = padded_batch(reqs, n_pad, B)
            c0 = x[:, :K_PRIME]
            got = solve_attach_fused(x, c0, tau, None, pm, interpret=False)
            with jax.default_device(jax.devices("cpu")[0]):
                want = oracle(x, c0, tau, None, pm)
            got, want = jax.device_get((got, want))
            check(np.array_equal(got[0], want[0]),
                  f"solve_attach n={n_pad}: labels differ from the oracle")
            check(np.array_equal(got[3], want[3]),
                  f"solve_attach n={n_pad}: center labels differ")
            sq = np.sum(x * x, axis=-1)
            err = np.abs(got[1] - want[1])
            tol = 1e-4 + 1e-4 * np.abs(want[1]) + 32 * eps * sq
            check(np.all(err <= tol),
                  f"solve_attach n={n_pad}: min-dist off by "
                  f"{float(err.max())}")
            log(f"kernels: solve_attach B={B} n={n_pad} d={D} matches the "
                f"f32 oracle (max min-dist error {float(err.max()):.3g})")
        steady_rate(sess, timing_reqs, "pallas")
    finally:
        ops.set_backend("ref")


def one_chip() -> None:
    fm = make_round_data()
    rr = round_phase(fm)
    reqs = make_requests(fm.means, 7)
    timing_reqs = make_requests(fm.means, 23)
    log(f"serve stream: {len(reqs)} requests, n "
        f"{min(r[0].shape[0] for r in reqs)}-"
        f"{max(r[0].shape[0] for r in reqs)}, d={D}")
    sess, ref_out = serve(rr, reqs, "jnp")
    steady_rate(sess, timing_reqs, "jnp")
    kernel_phase(rr, reqs, ref_out, timing_reqs)
    stats = jax.devices()[0].memory_stats() or {}
    log(f"peak device memory: "
        f"{stats.get('peak_bytes_in_use', 'not reported')} bytes")


def four_chips() -> None:
    """The sharded round and serve plane against one chip, bitwise."""

    mesh = make_mesh((4,), ("data",))
    fm = make_round_data()
    rr = round_phase(fm)
    plan = FederationPlan(k=K, k_prime=K_PRIME, d=D, topology="sharded")
    data = jax.device_put(fm.data, NamedSharding(mesh, P("data")))
    (labels, tau, _), secs = timed(round_fn(plan, mesh),
                                   jax.random.PRNGKey(1), data)
    labels, tau = np.asarray(labels), np.asarray(tau)
    same_l = np.array_equal(labels, np.asarray(rr.labels))
    same_t = np.array_equal(tau, np.asarray(rr.agg.tau_centers))
    log(f"round[sharded, 4 chips, {fm.data.shape[0] // 4} devices each]: "
        f"labels bitwise equal to the one-chip round: {same_l} "
        f"({int(np.sum(labels != np.asarray(rr.labels)))} differ); tau "
        f"bitwise equal: {same_t} (max |diff| "
        f"{float(np.max(np.abs(tau - np.asarray(rr.agg.tau_centers))))}); "
        f"compile + first call {secs:.1f}s")
    check(same_l, "sharded round labels differ from the simulated round")
    check(same_t, "sharded round tau differs from the simulated round")

    reqs = make_requests(fm.means, 7)
    one = serve_chunks(rr, reqs, "single-host")
    spread = []
    many = serve_chunks(rr, reqs, "serve_axes=data", mesh=mesh,
                        serve_axes=("data",), spy=spread)
    for w, ((o_out, o_state, o_tau), (m_out, m_state, m_tau)) in \
            enumerate(zip(one, many)):
        check([v for _, v in o_out] == [v for _, v in m_out],
              f"window {w}: sharded plane tau versions differ")
        check(all(np.array_equal(lo, lm)
                  for (lo, _), (lm, _) in zip(o_out, m_out)),
              f"window {w}: sharded plane labels differ")
        check(all(np.array_equal(a, b) for a, b in zip(o_state, m_state)),
              f"window {w}: sharded plane fold state differs")
        check(np.array_equal(o_tau, m_tau),
              f"window {w}: sharded plane tau differs")
    check(spread and all(n == 4 for n in spread),
          f"steady batches did not span 4 devices: {spread}")
    log(f"serve[4 chips]: {len(one)} refresh windows compared with the "
        f"single-host plane (labels, tau versions, tau, fold state); "
        f"{len(spread)} batches placed on {sorted(set(spread))} devices")


def serve_chunks(rr, reqs, tag, mesh=None, spy=None, **plan_kw):
    """Serve ``reqs`` one refresh window at a time; after each window
    record (labels + versions, fold state, tau)."""
    plan = FederationPlan(k=K, k_prime=K_PRIME, d=D, **SERVE, **plan_kw)
    sess = Session.from_round(plan, rr, mesh=mesh)
    if spy is not None:
        plane = sess.service.plane
        step = plane.step

        def counting_step(*a, **kw):
            out = step(*a, **kw)
            spy.append(len(out[0].sharding.device_set))
            return out
        plane.step = counting_step
    every = SERVE["refresh_every"]
    windows = []
    for lo in range(0, len(reqs), every):
        part = reqs[lo:lo + every]
        out = sess.serve_versioned([r[0] for r in part],
                                   [r[2] for r in part])
        state = [np.asarray(x) for x in
                 jax.tree.leaves(sess.service.state)]
        windows.append((out, state, np.asarray(sess.tau_centers)))
    acc = served_accuracy([o for w in windows for o in w[0]], reqs)
    log(f"serve[{tag}]: {len(reqs)} requests in windows of {every}, "
        f"tau version {sess.tau_version}, accuracy {acc:.4f}")
    check(acc >= MIN_ACCURACY, f"serve[{tag}] accuracy {acc}")
    return windows


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: the main path on one chip; 4: only the "
                         "sharded round and serve plane, each against "
                         "one chip")
    args = ap.parse_args()


    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"error: no TPU (JAX found {devices[0].platform}); this "
              f"smoke test runs on the chip only", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"error: --chips {args.chips} needs {args.chips} TPU "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 1
    use_compile_cache()
    dev = devices[0]
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}, "
        f"jax {jax.__version__}")
    (four_chips if args.chips == 4 else one_chip)()
    if FAILURES:
        print(f"error: {len(FAILURES)} check(s) failed:", file=sys.stderr)
        for what in FAILURES:
            print(f"  {what}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
