"""Attachment-service throughput: devices/sec and points/sec of the
streaming post-round serving path (``fed.api.Session.serve``) over a
batch-size sweep, the checkpoint -> restore -> serve bitwise round-trip
the crash-recovery story depends on, the sharded serve plane
(DESIGN.md §11): points/sec vs shard count and sync-vs-async tau
refresh, measured in a subprocess with 8 forced host-platform devices
(the embarrassingly-parallel local solves split across shards), and the
§12 load-adaptive autoscaler: a ramp/burst/trickle load-shape sweep
(``autoscale_*`` rows) pitting the controller against both static
(shards, batch) extremes — repeat-padding rows are real compute, so a
static-large plan burns points/sec on shallow flushes while a
static-small plan fragments deep ones; the controller's steady-state
recompile count is asserted to be zero in-row. The ``autoscale_*`` and
``attach_bs*`` points/sec rows are what the CI perf gate
(``benchmarks/compare.py``) compares against the committed baseline."""
from __future__ import annotations

import os
import subprocess
import sys
import tempfile
import time

import jax
import numpy as np

from benchmarks.common import row
from repro.data.gaussian import late_device_stream, structured_devices
from repro.fed.api import FederationPlan, Session

_PLANE_DEVICES = 8

# Runs under XLA_FLAGS=--xla_force_host_platform_device_count=8 (set by
# the parent): single-host baseline vs the serve plane sharded over all
# devices, sync vs async refresh, same request stream throughout.
_PLANE_CHILD = r"""
import time
import jax
import numpy as np
from repro.utils.compat import make_mesh
from repro.data.gaussian import late_device_stream, structured_devices
from repro.fed.api import FederationPlan, Session

B, n, requests, passes = {B}, {n}, {requests}, {passes}
k, kp, d = 16, 4, 24
fm = structured_devices(jax.random.PRNGKey(0), k=k, d=d, k_prime=kp,
                        m0=4, n_per_comp_dev=25, sep=60.0)
rr = Session(FederationPlan(k=k, k_prime=kp, d=d)).run(
    jax.random.PRNGKey(1), fm.data).detail
mesh = make_mesh((jax.device_count(),), ("data",))

def reqs(seed):
    # Heterogeneous k^(z) in [1, k'] — the paper's workload. The spread
    # in per-request convergence is exactly what batch-axis sharding
    # exploits: a vmapped solve iterates until the slowest request in
    # the WHOLE batch converges, a shard only until its own slice does.
    s = late_device_stream(fm.means, kp, requests, seed,
                           n_range=(n, n + 1))
    return [r[0] for r in s], [r[2] for r in s]

S = jax.device_count()
sessions = []
for name, serve_axes, refresh, every in (
        ("shards1_sync", None, "sync", 0),
        ("shards%d_sync" % S, ("data",), "sync", 0),
        ("shards%d_refresh_sync" % S, ("data",), "sync", B),
        ("shards%d_refresh_async" % S, ("data",), "async", B)):
    plan = FederationPlan(k=k, k_prime=kp, d=d, capacity=1024,
                          batch_size=B, bucket_sizes=(n,),
                          refresh_every=every, refresh=refresh,
                          serve_axes=serve_axes)
    sess = Session.from_round(plan, rr, mesh=mesh if serve_axes else None)
    wd, wkv = reqs(99)
    sess.serve(wd[:B], wkv[:B])                    # compile warmup
    sessions.append([name, sess, float("inf")])
batch, kvs = reqs(7)
# Interleave timing passes across configs (best-of) so machine drift
# lands on every config equally instead of biasing whichever ran last.
for _ in range(passes):
    for rec in sessions:
        t0 = time.perf_counter()
        rec[1].serve(batch, kvs)
        # a staged async re-finalization may still be in flight; block
        # on both tau buffers so every mode pays its full cost.
        jax.block_until_ready(rec[1].service._taubuf.bufs)
        rec[2] = min(rec[2], time.perf_counter() - t0)
pts = {{}}
for name, sess, best in sessions:
    pts[name] = requests * n / best
    print("ROW plane_%s,%.3f,dev_per_s=%.1f;pts_per_s=%.0f;version=%d"
          % (name, best / requests * 1e6, requests / best, pts[name],
             sess.tau_version))
base = pts["shards1_sync"]
for name, v in pts.items():
    if name != "shards1_sync":
        print("ROW plane_speedup_%s,0,x_vs_single_shard=%.2f"
              % (name, v / base))
"""


# Runs under XLA_FLAGS=--xla_force_host_platform_device_count=8: the
# load-shape sweep. Each flush submits `depth` requests then flushes —
# ramp (1 -> 64 doubling), burst (alternating 64/1), and trickle (all
# singletons) — against the controller and both static extremes on the
# same request pool. pts_per_s counts REAL points only, so padding
# waste shows up as lost throughput.
_AUTOSCALE_CHILD = r"""
import time
import jax
import numpy as np
from repro.utils.compat import make_mesh
from repro.data.gaussian import late_device_stream, structured_devices
from repro.fed.api import FederationPlan, Session

n, passes = {n}, {passes}
k, kp, d = 16, 4, 24
fm = structured_devices(jax.random.PRNGKey(0), k=k, d=d, k_prime=kp,
                        m0=4, n_per_comp_dev=25, sep=60.0)
rr = Session(FederationPlan(k=k, k_prime=kp, d=d)).run(
    jax.random.PRNGKey(1), fm.data).detail
mesh = make_mesh((jax.device_count(),), ("data",))

SHAPES = {{
    "ramp": [1, 2, 4, 8, 16, 32, 64],
    "burst": [64, 1, 64, 1, 64, 1],
    "trickle": [1] * 12,
}}
CONFIGS = (
    ("static_b8", dict(batch_size=8)),
    ("static_b64", dict(batch_size=64)),
    ("auto_latency", dict(batch_size=64, autoscale="latency")),
    ("auto_throughput", dict(batch_size=64, autoscale="throughput")),
)
stream = late_device_stream(fm.means, kp, 256, 7, n_range=(n, n + 1))
pool = [(r[0], r[2]) for r in stream]

def run_shape(sess, depths):
    i = 0
    t0 = time.perf_counter()
    for q in depths:
        for _ in range(q):
            data, kv = pool[i % len(pool)]
            sess.submit(data, kv)
            i += 1
        sess.flush()
    return time.perf_counter() - t0, i

pts = {{}}
for name, kw in CONFIGS:
    plan = FederationPlan(k=k, k_prime=kp, d=d, capacity=65536,
                          bucket_sizes=(n,), serve_axes=("data",), **kw)
    sess = Session.from_round(plan, rr, mesh=mesh)
    for depths in SHAPES.values():                  # compile warmup
        run_shape(sess, depths)
    warm = sess.stats()["plane_compiles"]
    for shape, depths in SHAPES.items():
        best, reqs = min((run_shape(sess, depths) for _ in range(passes)),
                         key=lambda r: r[0])
        key = (shape, name)
        pts[key] = reqs * n / best
        steady = sess.stats()["plane_compiles"] - warm
        print("ROW autoscale_%s_%s,%.3f,pts_per_s=%.0f;dev_per_s=%.1f;"
              "steady_recompiles=%d"
              % (shape, name, best / reqs * 1e6, pts[key], reqs / best,
                 steady))
        assert steady == 0, (name, shape, steady)
for shape in SHAPES:
    best_static = max(pts[(shape, "static_b8")], pts[(shape, "static_b64")])
    print("ROW autoscale_%s_margin,0,auto_latency_vs_best_static=%.2f"
          % (shape, pts[(shape, "auto_latency")] / best_static))
"""


def _forced_device_child(src: str, timeout: int):
    """Run a bench child under XLA_FLAGS forced host devices (the flag
    must precede jax backend init, hence the subprocess). The child
    measures the CPU plane by design, so it is held to the CPU: on a TPU
    host the parent holds the chip, and a child reaching for it would
    fail or hang."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count="
                          f"{_PLANE_DEVICES}")
    env["PYTHONPATH"] = (os.path.join(root, "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    return subprocess.run([sys.executable, "-c", src], env=env,
                          capture_output=True, text=True, timeout=timeout)


def _autoscale_rows(full: bool):
    """The §12 controller vs the static extremes, per load shape."""
    n, passes = (256, 3) if full else (128, 2)
    out = _forced_device_child(
        _AUTOSCALE_CHILD.format(n=n, passes=passes), timeout=1800)
    if out.returncode != 0:
        return [row("autoscale_sweep", 0, f"ERROR:{out.stderr[-200:]!r}")]
    return [line[4:] for line in out.stdout.splitlines()
            if line.startswith("ROW ")]


def _plane_rows(full: bool):
    """The static serve-plane sweep (shard count x refresh mode)."""
    B, n, requests, passes = ((64, 256, 256, 5) if full
                              else (64, 256, 128, 3))
    out = _forced_device_child(
        _PLANE_CHILD.format(B=B, n=n, requests=requests, passes=passes),
        timeout=1800)
    if out.returncode != 0:
        return [row("plane_sweep", 0,
                    f"ERROR:{out.stderr[-200:]!r}")]
    return [line[4:] for line in out.stdout.splitlines()
            if line.startswith("ROW ")]


def _stream(means, k_prime, requests, n, seed):
    """Fixed-shape requests (one bucket) so the sweep times pure serve."""
    return [r[0] for r in late_device_stream(
        means, k_prime, requests, seed, n_range=(n, n + 1),
        kv_min=k_prime)]


def run(full: bool = False):
    k, kp, d = 16, 4, 24
    n = 128 if full else 64
    requests = 32 if full else 8
    batch_sizes = (1, 8, 32) if full else (1, 8)

    fm = structured_devices(jax.random.PRNGKey(0), k=k, d=d, k_prime=kp,
                            m0=4, n_per_comp_dev=25, sep=60.0)
    # ONE round shared across every streaming plan in the sweep.
    rr = Session(FederationPlan(k=k, k_prime=kp, d=d)).run(
        jax.random.PRNGKey(1), fm.data).detail

    def session(B):
        plan = FederationPlan(k=k, k_prime=kp, d=d, capacity=4096,
                              batch_size=B, bucket_sizes=(n,))
        return Session.from_round(plan, rr)

    rows = []
    for B in batch_sizes:
        sess = session(B)
        sess.serve(_stream(fm.means, kp, B, n, seed=99))  # compile warmup
        reqs = _stream(fm.means, kp, requests, n, seed=7)
        t0 = time.perf_counter()
        sess.serve(reqs)
        dt = time.perf_counter() - t0
        pts = requests * n
        rows.append(row(f"attach_bs{B}_n{n}", dt / requests * 1e6,
                        f"dev_per_s={requests / dt:.1f};"
                        f"pts_per_s={pts / dt:.0f}"))

    # Crash recovery: checkpoint mid-stream, restore, serve the rest —
    # must be bitwise identical to the uninterrupted session.
    live = session(batch_sizes[-1])
    reqs = _stream(fm.means, kp, requests, n, seed=11)
    half = len(reqs) // 2
    live.serve(reqs[:half])
    path = os.path.join(tempfile.mkdtemp(), "attach_ck.npz")
    t0 = time.perf_counter()
    live.save(path)
    restored = Session.restore(path, live.plan)
    us_ck = (time.perf_counter() - t0) * 1e6
    same = all(np.array_equal(a, b)
               for a, b in zip(live.serve(reqs[half:]),
                               restored.serve(reqs[half:])))
    rows.append(row("attach_ckpt_roundtrip", us_ck, f"bitwise={same}"))

    rows.extend(_plane_rows(full))
    rows.extend(_autoscale_rows(full))
    return rows
