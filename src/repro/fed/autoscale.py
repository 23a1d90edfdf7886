"""Load-adaptive serve-plane autoscaling (DESIGN.md §12).

PR 4 left the serve plane statically configured: `serve_axes` grants a
shard count and ``batch_size`` fixes the step shape no matter what the
request queue looks like. This module is the deterministic controller
that closes ROADMAP's serve-plane-autoscaling item: at flush boundaries
only, it re-selects

  * the **active shard count** — within the devices the plan's
    ``serve_axes`` granted (a shallow queue runs on one device instead
    of paying the mesh dispatch for a near-empty batch);
  * the **serve batch size** — a power-of-two rung within the plan's
    ``batch_size`` ceiling (a flush with 3 queued requests pads to 4,
    not to 64 — repeat-padding rows are real compute);
  * the **active bucket ladder** — under oversized load the queued
    above-ladder requests are RE-BUCKETED into one coalesced pad rung
    instead of fragmenting across the geometric doubling ladder (fewer,
    fuller batches and fewer distinct jit shapes).

Determinism/replay contract (the property tests/test_autoscale.py
pins): a decision is a pure function of a :class:`QueueSnapshot` —
queue depth and the pending bucket histogram, both functions of the
request stream alone — plus the controller's own persisted state
(previous decision + shrink streak), which rides the schema-v3 service
checkpoint next to ``tau_meta``. Wall-clock flush telemetry
(``fed/telemetry.py``: host spans and counters of the flush path,
surfaced as ``Session.stats()["flush"]``) is deliberately EXCLUDED from
the decision inputs: wall clock does not replay, and version/fold
boundaries depend on batch shape, so a latency-driven decision would
break the bitwise restore-replay guarantee the whole streaming layer is
built on. Shard count never affects results (per-request labels are
batch-composition-independent), but it follows the same rule so the
decision *sequence* itself replays bitwise.

The serve plane caches one compiled step per (shards, batch, bucket)
triple (``fed/plane.py``), so in steady state — once the load shape's
rungs have each been seen once — scaling never recompiles
(``ServePlane.compile_count`` is asserted flat in the tests and the
``autoscale_*`` benchmark rows).
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Tuple

import numpy as np

__all__ = ["AUTOSCALE_POLICIES", "AUTOSCALE_IDS", "AutoscaleError",
           "AutoscaleController", "AutoscaleDecision", "QueueSnapshot",
           "bucket_of", "decide", "pow2_ceil", "shards_for",
           "snapshot_queue"]

AUTOSCALE_POLICIES = ("off", "latency", "throughput")

# Stable numeric codes for the v3 checkpoint schema (npz stores no
# strings): a restored service must run the SAME autoscale policy that
# wrote the decision state, or the replayed decision sequence — and with
# it the refresh/version boundaries — would diverge from the original.
AUTOSCALE_IDS = {"off": 0, "latency": 1, "throughput": 2}

# Shrink only after this many consecutive shallow flushes (throughput
# policy): one thin flush inside a burst must not collapse the batch.
SHRINK_STREAK = 2


class AutoscaleError(ValueError):
    """An autoscale configuration failed validation (named, with the
    accepted values) — raised at construction, never mid-flush."""


def bucket_of(n: int, ladder: Tuple[int, ...]) -> int:
    """THE pad-rung rule (shared by the service's bucketing and the
    controller's histogram so they can never disagree): the smallest
    ladder rung holding ``n`` points, geometric doubling above the top
    rung (O(log) distinct jit shapes instead of one per distinct n)."""
    for b in ladder:
        if n <= b:
            return int(b)
    b = int(ladder[-1])
    while b < n:
        b *= 2
    return b


class QueueSnapshot(NamedTuple):
    """The DETERMINISTIC flush-boundary telemetry decisions may read:
    a pure function of the queued request stream (depth + histogram
    over the base ladder's pad rungs), so an interrupted and an
    uninterrupted run observe identical snapshots.

    ``mass`` is the drift layer's per-center decayed fold-mass
    histogram (DESIGN.md §14) — empty when ``drift="off"``, otherwise
    a pure function of the folded stream, so it keeps the replay
    contract. Today's policies ignore it; it is the "state evolves at
    flush boundaries" hook the ROADMAP's predictive-scaling item
    needs (e.g. scale ahead of a mass-imbalance-triggered split)."""
    pending: int                              # queue depth at the boundary
    hist: Tuple[Tuple[int, int], ...]         # ascending (rung, count)
    mass: Tuple[float, ...] = ()              # per-center decayed fold mass


class AutoscaleDecision(NamedTuple):
    """One flush's scaling selection. ``seq`` counts decisions (one per
    non-empty flush) so checkpoint replay can be asserted against the
    uninterrupted run decision-by-decision."""
    shards: int                   # active serve shards (<= granted)
    batch_size: int               # active step batch (<= plan ceiling)
    ladder: Tuple[int, ...]       # active pad-bucket ladder
    seq: int


def snapshot_queue(pending_ns, base_ladder, mass=()) -> QueueSnapshot:
    """Histogram the queued point counts over the base ladder's rungs
    (geometric rungs above the top) — the controller's one view of the
    queue. ``mass``: the drift layer's per-center fold-mass histogram
    (empty outside drift mode)."""
    hist: Dict[int, int] = {}
    for n in pending_ns:
        b = bucket_of(int(n), tuple(base_ladder))
        hist[b] = hist.get(b, 0) + 1
    return QueueSnapshot(pending=len(pending_ns),
                         hist=tuple(sorted(hist.items())),
                         mass=tuple(float(m) for m in mass))


def pow2_ceil(x: int) -> int:
    return 1 << max(int(x) - 1, 0).bit_length()


def _pow2_floor(x: int) -> int:
    return 1 << (int(x).bit_length() - 1)


def shards_for(batch: int, granted: int, n_axes: int) -> int:
    """The most parallel ACTIVE shard count the batch divides over:
    the full grant when it fits; otherwise (single-axis serve planes
    only — a multi-axis grant has no canonical sub-grant) the largest
    power of two dividing both."""
    if batch % granted == 0:
        return granted
    if n_axes > 1:
        return 1
    return min(_pow2_floor(granted), batch)


def _ladder_for(policy: str, snap: QueueSnapshot, batch: int,
                base_ladder: Tuple[int, ...]) -> Tuple[int, ...]:
    """The active bucket ladder: base rungs, plus the queued oversized
    rungs — coalesced into the single largest occupied rung when the
    flush is load-heavy (throughput always; latency once the oversized
    backlog alone fills a batch), so oversized traffic re-buckets into
    fewer, fuller fixed shapes instead of climbing the geometric
    ladder one thin batch per rung."""
    top = base_ladder[-1]
    over = [(r, c) for r, c in snap.hist if r > top]
    if not over:
        return base_ladder
    if len(over) > 1 and (policy == "throughput"
                          or sum(c for _, c in over) >= batch):
        return base_ladder + (over[-1][0],)
    return base_ladder + tuple(r for r, _ in over)


def decide(policy: str, snap: QueueSnapshot, *, max_batch: int,
           granted: int, n_axes: int, base_ladder: Tuple[int, ...],
           prev: AutoscaleDecision,
           streak: int) -> Tuple[AutoscaleDecision, int]:
    """THE decision rule — a pure function of (policy, snapshot, prev
    decision, streak), nothing else (unit-tested directly).

    Called only for the ADAPTIVE policies — ``off`` never reaches the
    decision rule (:meth:`AutoscaleController.observe` short-circuits
    it to the static plan decision, seq untouched).

    * ``latency`` — the batch tracks the queue depth both ways
      (next power of two, capped at the plan ceiling): shallow flushes
      serve immediately in small steps instead of computing a
      near-empty padded batch.
    * ``throughput`` — grows exactly like ``latency`` but shrinks only
      after :data:`SHRINK_STREAK` consecutive shallow flushes, riding
      out single-flush dips inside a burst with full batches.

    The active shard count follows the batch (``shards_for``), and the
    ladder re-buckets oversized backlog (``_ladder_for``).
    """
    target = min(pow2_ceil(max(snap.pending, 1)), int(max_batch))
    if policy == "latency":
        batch, streak = target, 0
    elif target >= prev.batch_size:
        batch, streak = target, 0
    else:
        streak += 1
        if streak >= SHRINK_STREAK:
            batch, streak = target, 0
        else:
            batch = prev.batch_size
    return (AutoscaleDecision(
        shards=shards_for(batch, granted, n_axes),
        batch_size=batch,
        ladder=_ladder_for(policy, snap, batch, tuple(base_ladder)),
        seq=prev.seq + 1), streak)


class AutoscaleController:
    """Owns the decision state for one ``AttachService``: observe a
    queue snapshot at each flush boundary, emit the decision for that
    flush, and checkpoint/restore the state arrays that make the
    decision sequence replay bitwise (schema v3)."""

    def __init__(self, policy: str, *, max_batch: int, granted: int,
                 n_axes: int, base_ladder: Tuple[int, ...]):
        if policy not in AUTOSCALE_POLICIES:
            raise AutoscaleError(
                f"autoscale={policy!r} is invalid: accepted values are "
                f"{list(AUTOSCALE_POLICIES)}")
        self.policy = policy
        self.max_batch = int(max_batch)
        self.granted = int(granted)
        self.n_axes = int(n_axes)
        self.base_ladder = tuple(int(b) for b in base_ladder)
        # The pre-traffic decision IS the static plan configuration —
        # autoscale="off" never leaves it.
        self.decision = AutoscaleDecision(self.granted, self.max_batch,
                                          self.base_ladder, 0)
        self.streak = 0

    def observe(self, snap: QueueSnapshot) -> AutoscaleDecision:
        """One flush boundary: fold the snapshot into the controller
        state and return the decision the flush must execute."""
        if self.policy == "off":
            return self.decision
        self.decision, self.streak = decide(
            self.policy, snap, max_batch=self.max_batch,
            granted=self.granted, n_axes=self.n_axes,
            base_ladder=self.base_ladder, prev=self.decision,
            streak=self.streak)
        return self.decision

    # -- checkpoint plumbing (the v3 schema arrays) ---------------------
    def state_arrays(self) -> Dict[str, np.ndarray]:
        d = self.decision
        return {
            "autoscale_state": np.asarray(
                [d.shards, d.batch_size, d.seq, self.streak], np.int64),
            "autoscale_ladder": np.asarray(d.ladder, np.int64),
        }

    def load_state(self, state, ladder) -> None:
        """Adopt a v3 checkpoint's decision state, RECONCILED against
        THIS controller's configuration. The checkpoint may have been
        written under a different plan or mesh (bigger batch ceiling,
        wider shard grant): the batch rung clamps to the current
        ceiling and the shard count is recomputed from the current
        grant (shard count never affects results, so this cannot
        perturb replay — under an unchanged config every
        reconciliation is the identity and the decision sequence still
        replays bitwise). ``off`` ignores the persisted shape
        entirely: off IS the restoring plan's static configuration."""
        s = np.asarray(state, np.int64)
        seq = int(s[2])
        if self.policy == "off":
            self.decision = self.decision._replace(seq=seq)
            self.streak = 0
            return
        batch = min(int(s[1]), self.max_batch)
        self.decision = AutoscaleDecision(
            shards_for(batch, self.granted, self.n_axes), batch,
            tuple(int(b) for b in np.asarray(ladder, np.int64)), seq)
        self.streak = int(s[3])

    def stats(self) -> dict:
        d = self.decision
        return {
            "policy": self.policy,
            "shards": d.shards,
            "batch_size": d.batch_size,
            "ladder": list(d.ladder),
            "decisions": d.seq,
            "granted_shards": self.granted,
            "max_batch": self.max_batch,
        }
