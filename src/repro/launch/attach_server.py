"""Attachment-server entry point: run one k-FED round, then serve a
stream of late-joining devices — all through one declarative
``FederationPlan`` + ``Session`` (DESIGN.md §10–§11).

Demonstrates the full post-round serving vertical — batched/bucketed
Theorem 3.2 attachment, incremental folding with an online refresh
cadence and a pluggable fold-slot admission policy, checkpointed crash
recovery (the restored session replays the remaining stream
bitwise-identically), and the sharded serve plane: ``--serve-axes``
shard_maps the request batch over a mesh while ``--refresh async``
double-buffers the tau swap so re-finalization overlaps serving.

  PYTHONPATH=src python -m repro.launch.attach_server \
      --requests 48 --batch-size 8 --refresh-every 16 \
      --fold-policy lru --checkpoint /tmp/attach.npz

  # sharded plane over 8 forced host devices, async tau refresh
  PYTHONPATH=src python -m repro.launch.attach_server \
      --force-host-devices 8 --serve-axes data --refresh async

  # cluster-routed personalization serving (DESIGN.md §16): every
  # request is labeled, majority-voted to its cluster and answered by
  # that cluster's head in ONE fused step
  PYTHONPATH=src python -m repro.launch.attach_server \
      --heads qwen1.5-0.5b --head-arch ffn --head-capacity 1.25
"""
from __future__ import annotations

import argparse
import os
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--k", type=int, default=16)
    ap.add_argument("--k-prime", type=int, default=4)
    ap.add_argument("--d", type=int, default=24)
    ap.add_argument("--devices-per-group", type=int, default=4)
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--refresh-every", type=int, default=16)
    ap.add_argument("--refresh", default="sync",
                    choices=("sync", "async"),
                    help="tau swap mode: sync swaps between batches; "
                         "async double-buffers and commits the "
                         "versioned swap at the next flush boundary")
    # literal choices (not imported from fed.autoscale) so argparse
    # rejects typos BEFORE jax loads; AUTOSCALE_POLICIES is the source.
    ap.add_argument("--autoscale", default="off",
                    choices=("off", "latency", "throughput"),
                    help="load-adaptive serve plane (DESIGN.md §12): "
                         "re-select active shards / batch size / "
                         "bucket ladder from queue depth at flush "
                         "boundaries (latency tracks the queue both "
                         "ways; throughput holds full batches across "
                         "single-flush dips); --batch-size becomes "
                         "the ceiling and --serve-axes the shard grant")
    ap.add_argument("--serve-axes", default=None, metavar="AXES",
                    help="comma-separated mesh axes to shard the serve "
                         "plane's request batch over (e.g. 'data'); "
                         "default: single-host serving")
    ap.add_argument("--force-host-devices", type=int, default=0,
                    metavar="N",
                    help="force N XLA host-platform devices (must be "
                         "set before the first jax computation; use "
                         "with --serve-axes to shard on CPU)")
    ap.add_argument("--capacity", type=int, default=4096)
    # literal choices (not imported from fed.policy) so argparse rejects
    # typos BEFORE jax loads; fed/policy.py POLICIES is the source.
    ap.add_argument("--fold-policy", default="drop",
                    choices=("drop", "lru", "weighted_reservoir"),
                    help="fold-slot admission: drop (served-not-folded "
                         "past capacity), lru, or weighted_reservoir")
    ap.add_argument("--heads", default="off", metavar="NAME",
                    help="cluster-routed personalization serving "
                         "(DESIGN.md §16): 'off', 'linear', or a "
                         "registered model-config name (e.g. "
                         "'qwen1.5-0.5b') — each cluster gets its own "
                         "head and requests route to it by majority "
                         "vote; bad names fail with a named config "
                         "error listing the registry")
    # literal choices (not imported from models.heads) so argparse
    # rejects typos BEFORE jax loads; HEAD_ARCHS is the source.
    ap.add_argument("--head-arch", default="ffn",
                    choices=("ffn", "transformer"),
                    help="per-cluster head block: the config's FFN, or "
                         "the flag-gated attention+FFN transformer "
                         "block")
    ap.add_argument("--head-capacity", type=float, default=1.25,
                    metavar="F",
                    help="dispatch queue depth factor: each cluster "
                         "gets ceil(batch * F / k) slots per step; "
                         "overflowing requests still get labels, just "
                         "no prediction")
    ap.add_argument("--checkpoint", default=None, metavar="PATH",
                    help="checkpoint mid-stream and verify the restored "
                         "session serves the remainder bitwise identically")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if args.force_host_devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count="
            f"{args.force_host_devices}")

    # jax is imported (and its backend initialized) only AFTER the
    # forced-device flag is in the environment.
    import jax
    import numpy as np

    from repro.data.gaussian import late_device_stream, structured_devices
    from repro.fed.api import FederationPlan, Session
    from repro.utils.cache import use_compile_cache
    from repro.utils.compat import make_mesh
    from repro.utils.metrics import clustering_accuracy

    use_compile_cache()
    k, kp, d = args.k, args.k_prime, args.d
    fm = structured_devices(jax.random.PRNGKey(args.seed), k=k, d=d,
                            k_prime=kp, m0=args.devices_per_group,
                            n_per_comp_dev=25, sep=60.0)
    serve_axes = (tuple(args.serve_axes.split(","))
                  if args.serve_axes else None)
    # The mesh takes its axis names FROM --serve-axes (all devices on
    # the first named axis), so any axis name the user picks works.
    mesh = (make_mesh((jax.device_count(),)
                      + (1,) * (len(serve_axes) - 1), serve_axes)
            if serve_axes else None)
    plan = FederationPlan(k=k, k_prime=kp, d=d, capacity=args.capacity,
                          batch_size=args.batch_size,
                          refresh_every=args.refresh_every,
                          refresh=args.refresh, serve_axes=serve_axes,
                          autoscale=args.autoscale,
                          fold_policy=args.fold_policy,
                          heads=args.heads, head_arch=args.head_arch,
                          head_capacity=args.head_capacity,
                          checkpoint=args.checkpoint)
    sess = Session(plan, mesh=mesh)
    rr = sess.run(jax.random.PRNGKey(args.seed + 1), fm.data)
    Z = fm.data.shape[0]
    acc0 = clustering_accuracy(np.asarray(rr.labels),
                               np.asarray(fm.labels), k)
    print(f"round: Z={Z} devices, k={k}, k'={kp}, "
          f"accuracy {100 * acc0:.2f}%")

    stream = late_device_stream(fm.means, kp, args.requests, args.seed + 2)

    half = len(stream) // 2
    t0 = time.perf_counter()
    if args.heads != "off":
        preds = sess.serve_predict([r[0] for r in stream[:half]],
                                   [r[2] for r in stream[:half]])
        out = [(p.labels, p.tau_version) for p in preds]
    else:
        out = sess.serve_versioned([r[0] for r in stream[:half]],
                                   [r[2] for r in stream[:half]])
    dt = time.perf_counter() - t0
    pts = sum(r[0].shape[0] for r in stream[:half])
    accs = [clustering_accuracy(lbl, r[1], k)
            for (lbl, _), r in zip(out, stream[:half])]
    st = sess.stats()
    versions = sorted({v for _, v in out})
    print(f"served {half} devices / {pts} points in {dt:.2f}s "
          f"({half / dt:.1f} dev/s, {pts / dt:.0f} pts/s) on "
          f"{st['serve_shards']} serve shard(s), "
          f"tau versions {versions}, "
          f"mean accuracy {100 * float(np.mean(accs)):.2f}%")
    if args.heads != "off":
        h = st["heads"]
        routed = [p for p in preds if p.routed]
        clusters = sorted({p.cluster for p in routed})
        print(f"heads[{h['mode']}/{h['arch']}]: routed "
              f"{len(routed)}/{half} requests over {len(clusters)} "
              f"cluster head(s) ({h['params_per_head']} params/head, "
              f"{h['queue_capacity']} queue slots/cluster, "
              f"{h['overflowed']} overflowed), mean |prediction| "
              f"{float(np.mean([np.abs(p.prediction).mean() for p in routed])):.3f}")

    if args.checkpoint:
        sess.save()
        restored = Session.restore(args.checkpoint, plan, mesh=mesh)
        rest_live = sess.serve_versioned([r[0] for r in stream[half:]],
                                         [r[2] for r in stream[half:]])
        rest_ck = restored.serve_versioned([r[0] for r in stream[half:]],
                                           [r[2] for r in stream[half:]])
        same = all(np.array_equal(a, b) and va == vb
                   for (a, va), (b, vb) in zip(rest_live, rest_ck))
        print(f"checkpoint -> restore -> serve: bitwise identical "
              f"labels AND tau versions vs uninterrupted session: {same}")
        assert same
    else:
        sess.serve([r[0] for r in stream[half:]],
                   [r[2] for r in stream[half:]])

    st = sess.stats()
    print(f"stats: {st['served_devices']} served, {st['folded']} folded "
          f"(capacity {st['capacity']}, policy {st['fold_policy']}), "
          f"refresh cadence {args.refresh_every} ({args.refresh}), "
          f"final tau version {st['tau_version']}")
    a, f = st["autoscale"], st["flush"]
    print(f"autoscale[{a['policy']}]: active shards {a['shards']}/"
          f"{a['granted_shards']}, batch {a['batch_size']}/"
          f"{a['max_batch']}, ladder {a['ladder']}, "
          f"{a['decisions']} decisions, "
          f"{st['plane_compiles']} compiled signatures")
    print(f"flush: {f['flushes']} flushes, {f['batches']} batches, "
          f"{f['rows_stepped']} rows ({f['points_stepped']} points) "
          f"stepped, {f['refreshes']} refreshes; host s: prep "
          f"{f['prep_s']:.3f}, step {f['step_s']:.3f}, fold "
          f"{f['fold_s']:.3f}, refresh {f['refresh_s']:.3f}, deliver "
          f"{f['deliver_s']:.3f}")


if __name__ == "__main__":
    main()
