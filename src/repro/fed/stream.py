"""Streaming post-round attachment service (DESIGN.md §9, §11).

Everything after the one communication round: a finalized k-FED round
leaves k tau centers, and from then on the paper's Theorem 3.2 promises
O(k'k) attachment of any late-joining device with zero extra rounds.
This module turns that promise into a serving layer:

  * **batching** — heterogeneous ``(n^(z), k^(z))`` attach requests are
    bucketed by padded point count, padded into fixed ``(B, n_pad, d)``
    shapes with point masks, and served by ONE jitted step that vmaps
    the Algorithm 1 local solve over the request batch and attaches via
    the Theorem 3.2 nearest-center rule. The step (and the fold
    scatter) execute on a ``fed/plane.ServePlane`` — single-host by
    default, shard_mapped over the plan's ``serve_axes`` mesh axes when
    set (the request batch axis is embarrassingly parallel; tau and the
    fold state stay replicated);
  * **online refresh** — each served report (Theta, mask, |S_r|) can be
    folded into the incremental server state
    (``server.aggregate_incremental``), and on a configurable cadence
    the round is re-finalized so the cached tau centers track the
    population (the membership-update problem of Holzer et al. 2023 /
    Garst & Reinders 2023), still with one uplink per device ever. tau
    is double-buffered and versioned (``fed/plane.TauBuffer``):
    ``refresh="sync"`` swaps immediately between batches, while
    ``refresh="async"`` builds the standby buffer without interrupting
    serving and commits the swap — one atomic version bump — at the
    next flush boundary. Every served label records the tau version
    that produced it;
  * **load-adaptive scaling** — at flush boundaries a deterministic
    controller (``fed/autoscale.py``, DESIGN.md §12) may re-select the
    active shard count (within the ``serve_axes`` grant), the serve
    batch size, and the active bucket ladder (re-bucketing queued
    oversized requests into one coalesced rung under load) from a
    queue-depth snapshot; every (shards, batch, bucket) triple's step
    compiles once and is cached, so scaling never recompiles in steady
    state;
  * **crash recovery** — the full service state (both tau buffers +
    version, fold state, counters, key seed, autoscale decision state)
    checkpoints through ``checkpoint/store.py``; restore + serve is
    bitwise identical to the uninterrupted service — including
    mid-refresh-window version assignments and the scaling-decision
    sequence — because request keys are derived from the persisted
    request-id counter and decisions from deterministic queue
    snapshots, never from wall clock.

Fold-slot admission is a pluggable ``FoldPolicy`` (``fed/policy.py``):
``drop`` (slot == request id, over-capacity ids served-not-folded — the
historical behavior), ``lru`` (evict the least-recently-folded slot),
or ``weighted_reservoir`` (A-ES sampling by report mass). Admission is
host-side and shard-deterministic (``FoldPolicy.admit_batch``);
eviction is a slot overwrite, so ``server.aggregate_incremental`` stays
the single fold primitive (the sharded plane runs its collective
sibling ``aggregate_incremental_sharded`` — bitwise the same state).
In-flight (submitted, unflushed) requests are NOT part of a checkpoint
— clients re-submit on failover.
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass, field
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.store import load_extras, load_pytree, save_pytree
from repro.core import server
from repro.fed.autoscale import (AUTOSCALE_IDS, AutoscaleController,
                                 AutoscaleDecision, bucket_of, pow2_ceil,
                                 shards_for, snapshot_queue)
from repro.fed.plane import ServePlane, ServePlaneError, TauBuffer
from repro.fed.policy import FoldPolicy, make_policy
from repro.fed.telemetry import FlushTelemetry
from repro.utils.deprecation import warn_legacy

REFRESH_MODES = ("sync", "async")

DRIFT_MODES = ("off", "decay", "split_merge")

# Stable numeric codes for the v4 checkpoint schema (npz stores no
# strings): a drift-enabled checkpoint's fold epochs, mass histogram
# and split/retire counters are only meaningful under the drift mode
# that wrote them.
DRIFT_IDS = {"off": 0, "decay": 1, "split_merge": 2}


class ReproPerfWarning(UserWarning):
    """A configuration is costing performance without affecting results
    (e.g. attach requests padding above the configured bucket ladder).
    Named so ``filterwarnings`` can target exactly this class — silence
    it deliberately with ``ignore::repro.fed.stream.ReproPerfWarning``
    (pytest.ini escalates it to an error in the tier-1 suites)."""


class StreamConfigError(ValueError):
    """A StreamConfig field failed validation (named, with accepted
    values) — raised at construction, never deep inside tracing."""


class ServedPrediction(NamedTuple):
    """One request's routed-serving result (DESIGN.md §16): its
    Theorem 3.2 labels + the tau version that produced them (exactly
    :meth:`AttachService.flush_versioned`'s pair), plus the per-cluster
    head's pooled prediction. ``routed=False`` marks a request that
    overflowed its cluster's dispatch queue — it still has labels and a
    majority-vote ``cluster``, but ``prediction`` is the zero vector."""
    labels: "np.ndarray"      # (n,) int32 per-point labels
    tau_version: int
    prediction: "np.ndarray"  # (d,) f32 pooled head output
    cluster: int              # majority-vote cluster (the head index)
    routed: bool              # False = dispatch-queue overflow


# Key-derivation salt separating the head-init PRNG stream from the
# per-request fold_in streams (which consume request ids).
_HEADS_SALT = 0x48454144  # "HEAD"

# Sibling salt for the ingestion-encoder init stream (DESIGN.md §17):
# distinct from the head stream so enabling one never re-keys the other.
_ENCODER_SALT = 0x454E434F  # "ENCO"

# Smallest token-axis pad rung: sequences bucket to powers of two from
# here up to ``encode_seq_len`` (the submit-time ceiling), bounding the
# distinct compiled (n_pad, seq_pad) shapes to a static grid.
_SEQ_RUNG_FLOOR = 8

# Host staging arrays kept per batch shape: a batch is prepped into the
# next slot of its shape's ring while the previous slot's step may still
# be reading the other.
STAGING_DEPTH = 2


@jax.jit
def _request_keys(base_key, rids):
    """One PRNG key per request id, ``fold_in(base_key, rid)``: compiled
    once per batch size, bitwise the keys of the eager vmap."""
    return jax.vmap(jax.random.fold_in, in_axes=(None, 0))(base_key, rids)


class _Staging:
    """One reusable set of a batch's host inputs, for one batch shape.

    Prep rewrites the arrays in place: each request's points go to the
    front of its row and only the part of the row the previous batch
    filled past them is cleared, so the arrays always equal a fresh
    ``np.zeros`` fill. Rows past the batch repeat its last request
    (pad-by-repeat). ``busy`` holds the device output of the step that
    last read the arrays; :meth:`claim` waits on it before they are
    written again (the device may read host memory asynchronously, or
    alias it on the CPU backend)."""

    def __init__(self, B: int, n_pad: int, s_pad: int, d: int):
        self.data = np.zeros((B, n_pad, s_pad, d) if s_pad
                             else (B, n_pad, d), np.float32)
        self.tmask = np.zeros((B, n_pad, s_pad), bool) if s_pad else None
        self.pmask = np.zeros((B, n_pad), bool)
        self.kv = np.zeros((B,), np.int32)
        self.rids = np.zeros((B,), np.int64)
        # (points, tokens) written per row; None after an interrupted
        # write, which makes the next write clear whole rows.
        self.rows: Optional[List[Tuple[int, int]]] = [(0, 0)] * B
        self.busy = None

    def claim(self) -> bool:
        """Wait until no dispatched step reads the arrays; True if that
        meant blocking. A step that failed frees them all the same."""
        busy, self.busy = self.busy, None
        if busy is None:
            return False
        try:
            waited = not all(x.is_ready() for x in jax.tree.leaves(busy))
            jax.block_until_ready(busy)
        except Exception:
            return False
        return waited

    def write(self, batch) -> None:
        """Fill the arrays with ``batch``, padded by repeating its last
        request."""
        B, n_pad = self.pmask.shape
        s_pad = 0 if self.tmask is None else self.tmask.shape[2]
        rows, self.rows = self.rows or [(n_pad, s_pad)] * B, None
        data, tmask, pmask = self.data, self.tmask, self.pmask
        last = len(batch) - 1
        for i, (pn, ps) in enumerate(rows):
            rid, arr, k_valid = batch[min(i, last)]
            n = arr.shape[0]
            if pn > n:
                data[i, n:pn] = 0
                pmask[i, n:pn] = False
                if tmask is not None:
                    tmask[i, n:pn] = False
            if tmask is None:
                s = 0
                data[i, :n] = arr
            else:
                s = arr.shape[1]
                if ps > s:
                    data[i, :min(n, pn), s:ps] = 0
                    tmask[i, :min(n, pn), s:ps] = False
                data[i, :n, :s] = arr
                tmask[i, :n, :s] = True
            pmask[i, :n] = True
            self.kv[i] = k_valid
            self.rids[i] = rid
            rows[i] = (n, s)
        self.rows = rows


class _ServerStateV3(NamedTuple):
    """Restore template for pre-v4 checkpoints: the fold state before
    the drift layer's epoch stamps, with the SAME field names (and so
    the same flattened "server/.<field>" key paths)."""
    centers: jax.Array
    mask: jax.Array
    weights: jax.Array
    received: jax.Array


def _bad(fieldname: str, got, accepted: str) -> None:
    raise StreamConfigError(
        f"StreamConfig.{fieldname}={got!r} is invalid: {accepted}")


@dataclass(frozen=True)
class StreamConfig:
    """Static configuration of the attachment service."""
    k: int                      # global cluster count of the round
    k_prime: int                # per-request k^(z) cap (static pad)
    d: int                      # feature dimension
    capacity: int               # fold-state slots (device ids)
    batch_size: int = 8         # requests per jitted serve step
    bucket_sizes: Tuple[int, ...] = (64, 256, 1024)  # n^(z) pad buckets
    refresh_every: int = 0      # re-finalize after this many folds; 0 = never
    refresh: str = "sync"       # tau swap: sync (immediate) | async
    autoscale: str = "off"      # serve-plane scaling: off|latency|throughput
    fold_reports: bool = True   # fold served reports into the server state
    weight_by_core_counts: bool = False
    fold_policy: str = "drop"   # admission: drop | lru | weighted_reservoir
    policy_seed: int = 0        # weighted_reservoir key seed
    serve_dtype: str = "f32"    # fused-step storage: f32 (bitwise) | bf16
    drift: str = "off"          # drift adaptation: off|decay|split_merge
    drift_half_life: int = 0    # decay half-life in REQUESTS (>= 1 on)
    drift_split_factor: float = 2.0   # split centers above this x mean mass
    drift_retire_frac: float = 0.1    # retire centers below this x mean mass
    drift_max_moves: int = 1    # split/retire moves per flush boundary
    heads: str = "off"          # per-cluster serving heads: off|linear|<config>
    head_capacity: float = 1.25  # dispatch queue slots per cluster, x B/k
    head_arch: str = "ffn"      # head architecture: ffn | transformer
    encoder: str = "off"        # ingestion encoder: off | <config name>
    encode_dtype: str = "f32"   # encoder storage: f32 | bf16 (f32 accum)
    encode_seq_len: int = 64    # token-axis pad ceiling per point
    local_kw: dict = field(default_factory=dict)  # Algorithm 1 options

    def __post_init__(self):
        from repro.fed.policy import POLICIES
        if not isinstance(self.k, int) or self.k < 1:
            _bad("k", self.k, "must be an int >= 1")
        if (not isinstance(self.k_prime, int)
                or not 1 <= self.k_prime <= self.k):
            _bad("k_prime", self.k_prime,
                 f"must satisfy 1 <= k_prime <= k (k={self.k})")
        if not isinstance(self.d, int) or self.d < 1:
            _bad("d", self.d, "must be an int >= 1")
        if self.capacity < 1:
            _bad("capacity", self.capacity, "must be an int >= 1")
        if self.batch_size < 1:
            _bad("batch_size", self.batch_size, "must be an int >= 1")
        if self.refresh_every < 0:
            _bad("refresh_every", self.refresh_every,
                 "must be >= 0 (0 disables the refresh cadence)")
        if self.refresh not in REFRESH_MODES:
            _bad("refresh", self.refresh,
                 f"accepted values are {list(REFRESH_MODES)}")
        from repro.fed.autoscale import AUTOSCALE_POLICIES
        if self.autoscale not in AUTOSCALE_POLICIES:
            _bad("autoscale", self.autoscale,
                 f"accepted values are {list(AUTOSCALE_POLICIES)}")
        if (self.autoscale != "off"
                and self.batch_size & (self.batch_size - 1)):
            _bad("batch_size", self.batch_size,
                 "must be a power of two when autoscale is enabled "
                 "(the controller re-selects power-of-two batch rungs "
                 "within it)")
        if (not self.bucket_sizes
                or any(int(b) < 1 for b in self.bucket_sizes)
                or list(self.bucket_sizes)
                != sorted(set(int(b) for b in self.bucket_sizes))):
            _bad("bucket_sizes", self.bucket_sizes,
                 "must be a non-empty strictly ascending tuple of "
                 "positive point-count pads, e.g. (64, 256, 1024)")
        if self.fold_policy not in POLICIES:
            _bad("fold_policy", self.fold_policy,
                 f"accepted values are {sorted(POLICIES)}")
        if not isinstance(self.policy_seed, int) or self.policy_seed < 0:
            _bad("policy_seed", self.policy_seed,
                 "must be a non-negative int (seeds the "
                 "weighted_reservoir keys)")
        if self.drift not in DRIFT_MODES:
            _bad("drift", self.drift,
                 f"accepted values are {list(DRIFT_MODES)}")
        if self.drift != "off" and (
                not isinstance(self.drift_half_life, int)
                or self.drift_half_life < 1):
            _bad("drift_half_life", self.drift_half_life,
                 "must be an int >= 1 (requests) when drift is enabled")
        if not float(self.drift_split_factor) > 1.0:
            _bad("drift_split_factor", self.drift_split_factor,
                 "must be > 1.0 (multiples of the mean center mass)")
        if not 0.0 <= float(self.drift_retire_frac) < 1.0:
            _bad("drift_retire_frac", self.drift_retire_frac,
                 "must be in [0.0, 1.0) (fraction of the mean mass)")
        if not isinstance(self.drift_max_moves, int) \
                or self.drift_max_moves < 1:
            _bad("drift_max_moves", self.drift_max_moves,
                 "must be an int >= 1 (split/retire moves per boundary)")
        from repro.kernels.ref import SOLVE_ATTACH_DTYPES
        if self.serve_dtype not in SOLVE_ATTACH_DTYPES:
            _bad("serve_dtype", self.serve_dtype,
                 f"accepted values are {list(SOLVE_ATTACH_DTYPES)} "
                 "(f32 keeps the fused serve step bitwise-identical to "
                 "the staged path; bf16 stores points/centers/tau in "
                 "bfloat16 with f32 accumulation — tolerance-bounded, "
                 "see DESIGN.md §13)")
        if not (isinstance(self.head_capacity, (int, float))
                and float(self.head_capacity) > 0.0):
            _bad("head_capacity", self.head_capacity,
                 "must be a float > 0 (per-cluster dispatch queue slots "
                 "as a multiple of batch_size / k; requests past a "
                 "cluster's queue are served labels without a "
                 "prediction — DESIGN.md §16)")
        if self.heads != "off":
            from repro.models import heads as heads_mod
            if self.head_arch not in heads_mod.HEAD_ARCHS:
                _bad("head_arch", self.head_arch,
                     f"accepted values are {list(heads_mod.HEAD_ARCHS)}")
            try:
                heads_mod.resolve_head_spec(self.heads, self.head_arch,
                                            self.d)
            except heads_mod.HeadConfigError as e:
                _bad("heads", self.heads, str(e))
        from repro.models.encoder import ENCODE_DTYPES
        if self.encode_dtype not in ENCODE_DTYPES:
            _bad("encode_dtype", self.encode_dtype,
                 f"accepted values are {list(ENCODE_DTYPES)} (f32 keeps "
                 "the encode stage bitwise-reproducible across restores; "
                 "bf16 stores encoder params/activations in bfloat16 "
                 "with f32 accumulation — DESIGN.md §17)")
        if self.encoder != "off":
            from repro.models import encoder as enc_mod
            if (not isinstance(self.encode_seq_len, int)
                    or self.encode_seq_len < 1):
                _bad("encode_seq_len", self.encode_seq_len,
                     "must be an int >= 1 (the per-point token-sequence "
                     "pad ceiling) when the encoder is enabled")
            try:
                enc_mod.resolve_encoder_spec(self.encoder, self.d)
            except enc_mod.EncoderConfigError as e:
                _bad("encoder", self.encoder, str(e))

    def encoder_spec(self):
        """Resolved :class:`repro.models.encoder.EncoderSpec` for this
        plan (None when the ingestion encoder is off)."""
        if self.encoder == "off":
            return None
        from repro.models import encoder as enc_mod
        return enc_mod.resolve_encoder_spec(self.encoder, self.d)

    def head_spec(self):
        """Resolved :class:`repro.models.heads.HeadSpec` for this plan
        (None when heads are off)."""
        if self.heads == "off":
            return None
        from repro.models import heads as heads_mod
        return heads_mod.resolve_head_spec(self.heads, self.head_arch,
                                           self.d)


class AttachService:
    """Serves batches of late-joining devices against a finalized round.

    Construct with :meth:`from_round` (seeds the fold state with the
    round's own reports) or :meth:`restore` (from a checkpoint). Pass
    ``mesh`` + ``serve_axes`` to run the hot path on the sharded serve
    plane (DESIGN.md §11) — per-request labels are bitwise identical to
    the single-host plane for a fixed tau version.
    """

    def __init__(self, cfg: StreamConfig, tau_centers, *,
                 state: Optional[server.ServerState] = None,
                 policy: Optional[FoldPolicy] = None,
                 seed: int = 0, next_id: int = 0,
                 since_refresh: int = 0, served_devices: int = 0,
                 served_points: int = 0, mesh=None, serve_axes=None,
                 tau_buffer: Optional[TauBuffer] = None, heads=None,
                 encoder=None):
        self.cfg = cfg
        try:
            self.plane = ServePlane(cfg, mesh=mesh, serve_axes=serve_axes)
        except ServePlaneError as e:
            raise StreamConfigError(str(e)) from None
        self._taubuf = (tau_buffer if tau_buffer is not None
                        else TauBuffer.fresh(tau_centers))
        assert self._taubuf.bufs.shape == (2, cfg.k, cfg.d), \
            self._taubuf.bufs.shape
        self.state = (server.init_state(cfg.capacity, cfg.k_prime, cfg.d)
                      if state is None
                      else jax.tree.map(jnp.asarray, state))
        self.policy = policy or make_policy(
            cfg.fold_policy, cfg.capacity, seed=cfg.policy_seed,
            half_life=(cfg.drift_half_life if cfg.drift != "off" else 0))
        # The §12 load-adaptive controller: one decision per non-empty
        # flush, against the devices serve_axes granted. With
        # autoscale="off" its (static) decision reproduces the
        # pre-controller behavior bitwise.
        self.autoscaler = AutoscaleController(
            cfg.autoscale, max_batch=cfg.batch_size,
            granted=self.plane.n_shards,
            n_axes=len(self.plane.axes) if self.plane.axes else 1,
            base_ladder=tuple(cfg.bucket_sizes))
        # Host spans and counters of the flush path (fed/telemetry.py):
        # observability only, never checkpointed.
        self.telemetry = FlushTelemetry()
        # Host staging rings, one per batch shape served: never
        # checkpointed (see _Staging).
        self._staging: Dict[tuple, Tuple[List[_Staging], int]] = {}
        self._base_seed = int(seed)
        self._base_key = jax.random.PRNGKey(self._base_seed)
        self._next_id = int(next_id)
        self._since_refresh = int(since_refresh)
        self._served_devices = int(served_devices)
        self._served_points = int(served_points)
        self._pending: List[Tuple[int, np.ndarray, int]] = []
        # served, not yet delivered: rid -> (labels, tau version,
        # (prediction, cluster, routed) | None with heads off)
        self._done: Dict[int, tuple] = {}
        # Per-cluster serving heads (DESIGN.md §16): k stacked param
        # sets, deterministically derived from the service seed on a
        # salted PRNG stream (so restores and re-inits agree), unless a
        # v5 checkpoint restore hands the folded params in. A staged
        # split/retire head re-map (``_heads_perm``) commits at the
        # SAME boundary as the tau version bump.
        self._head_spec = cfg.head_spec()
        self._heads_perm = None
        self._routed_served = 0
        self._overflowed = 0
        if self._head_spec is None:
            self.heads = None
        elif heads is not None:
            self.heads = jax.tree.map(jnp.asarray, heads)
        else:
            from repro.models import heads as heads_mod
            self.heads = heads_mod.init_heads(
                jax.random.fold_in(self._base_key, _HEADS_SALT),
                cfg.k, self._head_spec)
        # Ingestion encoder (DESIGN.md §17): one parameter set,
        # deterministically derived from the service seed on its own
        # salted stream (restores and re-inits agree), unless a v6
        # checkpoint restore hands the params in.
        self._enc_spec = cfg.encoder_spec()
        self._encoded_points = 0
        if self._enc_spec is None:
            self.encoder = None
        elif encoder is not None:
            self.encoder = jax.tree.map(jnp.asarray, encoder)
        else:
            from repro.models import encoder as enc_mod
            self.encoder = enc_mod.init_encoder(
                jax.random.fold_in(self._base_key, _ENCODER_SALT),
                self._enc_spec)
        # Warn-once latch keyed on (active ladder, rung): a global bool
        # here either re-fired every flush or went silent for a NEW
        # coalesced ladder after an autoscale switch — each distinct
        # oversized pad shape warns exactly once.
        self._oversized_warned: set = set()
        # Drift bookkeeping (schema v4): per-center decayed fold mass
        # at the last refresh, and the split/retire decision counters —
        # all pure functions of the folded stream, so they replay
        # bitwise from a checkpoint.
        self._drift_mass = np.zeros((cfg.k,), np.float32)
        self._drift_events = 0    # boundaries that moved >= 1 center
        self._drift_moves = 0     # total split/retire moves
        self._drift_last = 0      # moves at the most recent boundary

    # ------------------------------------------------------------- build --

    @classmethod
    def from_round(cls, rr, cfg: StreamConfig, *,
                   seed: int = 0) -> "AttachService":
        """Deprecated: construct a ``fed.api.Session`` and use
        ``Session.attach``/``Session.serve`` instead."""
        warn_legacy("fed.stream.AttachService.from_round",
                    "Session.attach/Session.serve")
        return cls._from_round(rr, cfg, seed=seed)

    @classmethod
    def _from_round(cls, rr, cfg: StreamConfig, *, seed: int = 0,
                    mesh=None, serve_axes=None) -> "AttachService":
        """Seed the service from a finished round result: cache its tau
        centers and fold the participating devices' reports so a later
        refresh re-finalizes over round + streamed devices."""
        Z = int(rr.device_centers.shape[0])
        if cfg.fold_policy == "drop":
            assert cfg.capacity >= Z, (cfg.capacity, Z)
        svc = cls(cfg, rr.agg.tau_centers, seed=seed, next_id=Z,
                  mesh=mesh, serve_axes=serve_axes)
        if cfg.fold_reports:
            ids = np.nonzero(np.asarray(rr.participated))[0]
            if ids.size:
                cw = server.core_weights(rr.core_counts[ids])
                dev_w = (np.asarray(jnp.sum(cw, axis=1))
                         if svc.policy.needs_weight else None)
                svc._admit_and_fold(
                    ids, dev_w, rr.device_centers[ids],
                    rr.center_mask[ids],
                    cw if cfg.weight_by_core_counts else None)
        return svc

    # ------------------------------------------------------------- serve --

    @property
    def tau(self) -> jax.Array:
        """The ACTIVE tau buffer (what the serve step reads)."""
        return self._taubuf.tau

    @property
    def tau_version(self) -> int:
        return self._taubuf.version

    def submit(self, data, k_valid: Optional[int] = None) -> int:
        """Enqueue one device's data; returns its request id (the fold
        slot, and the PRNG stream of its local solve). With the encoder
        off this is the historical (n, d) latent-point contract; with
        ``encoder=<config>`` each point is a raw token/patch sequence —
        (n, seq, d) with seq <= ``encode_seq_len`` — that the plane
        encodes ahead of the solve (DESIGN.md §17)."""
        arr = np.asarray(data, np.float32)
        if self._enc_spec is None:
            assert arr.ndim == 2 and arr.shape[1] == self.cfg.d, arr.shape
        else:
            assert arr.ndim == 3 and arr.shape[2] == self.cfg.d, arr.shape
            if arr.shape[1] < 1 or arr.shape[1] > self.cfg.encode_seq_len:
                raise StreamConfigError(
                    f"submit() got a token sequence of length "
                    f"{arr.shape[1]}: with encoder="
                    f"{self.cfg.encoder!r} every point must carry "
                    f"1 <= seq <= encode_seq_len="
                    f"{self.cfg.encode_seq_len} tokens (raise "
                    f"encode_seq_len in the plan for longer inputs)")
        kv = self.cfg.k_prime if k_valid is None else int(k_valid)
        assert 1 <= kv <= self.cfg.k_prime, kv
        rid = self._next_id
        self._next_id += 1
        self._pending.append((rid, arr, kv))
        return rid

    def _bucket(self, n: int, ladder: Optional[Tuple[int, ...]] = None
                ) -> int:
        """The pad rung for an n-point request: the flush decision's
        ACTIVE ladder when given (autoscale may have coalesced the
        oversized rungs), else the configured base ladder; geometric
        (doubling) buckets above the top rung bound the distinct jitted
        pad shapes to O(log n_max / top) instead of one recompile per
        distinct rounded-up n."""
        lad = tuple(ladder or self.cfg.bucket_sizes)
        b = bucket_of(n, lad)
        key = (lad, b)
        if n > self.cfg.bucket_sizes[-1] \
                and key not in self._oversized_warned:
            self._oversized_warned.add(key)
            warnings.warn(
                f"attach request with n={n} points exceeds the largest "
                f"configured bucket ({self.cfg.bucket_sizes[-1]}); "
                f"padding to an oversized bucket of {b}. Add larger "
                f"bucket_sizes to the plan to avoid oversized pads.",
                ReproPerfWarning, stacklevel=3)
        return b

    def _seq_rung(self, seq: int) -> int:
        """The token-axis pad rung for one request: the next power of
        two (floored at ``_SEQ_RUNG_FLOOR``), clamped to the
        ``encode_seq_len`` ceiling submit() enforced — so the compiled
        (n_pad, seq_pad) grid stays static per plan and short sequences
        never pad to the full ceiling."""
        return min(int(self.cfg.encode_seq_len),
                   max(_SEQ_RUNG_FLOOR, pow2_ceil(seq)))

    def _bucket_key(self, arr: np.ndarray,
                    ladder: Optional[Tuple[int, ...]] = None):
        """The flush-group key of one request: the point-count rung
        alone with the encoder off (the historical int key — those
        paths stay bitwise-untouched), the (n_pad, seq_pad) pair with
        it on. Keys within one flush are homogeneous, so the sorted
        group order stays deterministic either way."""
        n_pad = self._bucket(arr.shape[0], ladder)
        if self._enc_spec is None:
            return n_pad
        return (n_pad, self._seq_rung(arr.shape[1]))

    def flush(self) -> Dict[int, np.ndarray]:
        """Serve every pending request; returns {request_id: (n,) labels}.
        See :meth:`flush_versioned` for the tau version each request was
        served under."""
        return {rid: lbl
                for rid, (lbl, _) in self.flush_versioned().items()}

    def flush_versioned(self) -> Dict[int, Tuple[np.ndarray, int]]:
        """Serve every pending request; returns
        {request_id: ((n,) labels, tau_version)}. With heads enabled,
        :meth:`flush_predict` additionally returns the per-cluster head
        predictions of the same serve step."""
        return {rid: (lbl, ver)
                for rid, (lbl, ver, _) in self._flush_all().items()}

    def flush_predict(self) -> Dict[int, ServedPrediction]:
        """Serve every pending request through the routed
        personalization step; returns
        {request_id: :class:`ServedPrediction`}. Labels and tau
        versions are the ones :meth:`flush_versioned` would have
        returned (bitwise — the routed step shares the label body)."""
        if self._head_spec is None:
            raise StreamConfigError(
                "flush_predict() needs per-cluster serving heads: set "
                "StreamConfig.heads to 'linear' or a registered model "
                "config (it is 'off')")
        return {rid: ServedPrediction(lbl, ver, pred[0], pred[1],
                                      pred[2])
                for rid, (lbl, ver, pred)
                in self._flush_all().items()}

    def _flush_all(self) -> Dict[int, tuple]:
        """THE flush body: serve every pending request; returns
        {request_id: (labels, tau_version, pred)} where ``pred`` is
        ``(prediction, cluster, routed)`` with heads enabled, None
        otherwise.

        Requests are grouped by pad bucket and served in fixed
        (batch_size, n_pad, d) shapes — short batches pad by repeating
        the last real request (discarded). Served reports fold into the
        incremental server state, triggering a refresh on cadence. A
        flush boundary is where a staged async tau swap commits (and
        with it any staged split/retire head re-map — one atomic
        version bump covers both), so every request in one
        flush-and-refresh window maps to exactly one tau version.
        """
        tel = self.telemetry
        tel.flushes += 1
        with tel.phase("flush"):
            if self._taubuf.pending:
                self._taubuf = self._taubuf.commit()
                self._commit_heads_perm()
            pending, self._pending = self._pending, []
            with tel.phase("bucket"):
                buckets, decision = self._group(pending)
            out, self._done = self._done, {}  # undelivered earlier results
            # Two-phase pipeline: phase 1 DISPATCHES every batch (serve
            # step, fold scatter, staged refresh — all asynchronous,
            # chained by dataflow), phase 2 materializes labels on host.
            # The host never sits between consecutive device batches,
            # which is what keeps a sharded plane's shards saturated.
            staged: List[tuple] = []
            try:
                for bucket in sorted(buckets):
                    group = buckets[bucket]
                    B = decision.batch_size
                    for lo in range(0, len(group), B):
                        self._serve_batch(group[lo:lo + B], bucket,
                                          staged, decision)
                with tel.phase("deliver"):
                    self._deliver(staged, out)
            except BaseException:
                # A failed batch must not lose work: every dispatched
                # batch that still materializes drains into the
                # undelivered buffer; everything else (unserved, or
                # failed async) requeues by request id.
                for entry in staged:
                    if entry[0][0][0] in out:
                        continue  # already delivered before the failure
                    try:
                        self._deliver([entry], out)
                    except Exception:
                        pass  # its rids stay out of `out` -> requeued
                self._done.update(out)
                self._pending = [it for it in pending
                                 if it[0] not in out] + self._pending
                raise
        return out

    def _group(self, pending) -> Tuple[Dict, AutoscaleDecision]:
        """The flush's scaling decision and its pad-bucket groups."""
        # The flush boundary is the ONE place scaling decisions land
        # (§12): snapshot the queue (depth + base-ladder histogram —
        # deterministic functions of the request stream, so a restored
        # service replays the same decision) and let the controller
        # re-select the active (shards, batch, ladder) triple.
        decision = self.autoscaler.decision
        if pending and self.cfg.autoscale != "off":
            # "off" never reads the snapshot — skip building it so the
            # default configuration keeps the pre-controller flush cost.
            # Under drift the snapshot also carries the last refresh's
            # per-center mass histogram (deterministic — it evolves at
            # flush boundaries only), the predictive-scaling hook.
            decision = self.autoscaler.observe(snapshot_queue(
                [item[1].shape[0] for item in pending],
                self.cfg.bucket_sizes,
                mass=(tuple(float(m) for m in self._drift_mass)
                      if self.cfg.drift != "off" else ())))
        buckets: Dict = {}
        for item in pending:
            buckets.setdefault(
                self._bucket_key(item[1], decision.ladder),
                []).append(item)
        return buckets, decision

    def _deliver(self, staged, out) -> None:
        """Phase 2 of a flush: gather each dispatched batch's labels
        (and, with heads on, predictions) to host and hand them with
        their tau version to the caller; the real rows' projection
        iteration counts go to the flush counters."""
        for batch, labels_dev, version, iters_dev, *routed in staged:
            preds, cl, kept = ([np.asarray(x) for x in routed] if routed
                               else (None, None, None))
            labels = np.asarray(labels_dev)
            self.telemetry.projected(np.asarray(iters_dev)[:len(batch)])
            for i, (rid, arr, _) in enumerate(batch):
                if preds is None:
                    out[rid] = (labels[i, :arr.shape[0]], version, None)
                else:
                    routed = bool(kept[i])
                    out[rid] = (labels[i, :arr.shape[0]], version,
                                (preds[i].copy(), int(cl[i]), routed))
                    self._routed_served += int(routed)
                    self._overflowed += int(not routed)
                self._served_devices += 1
                self._served_points += arr.shape[0]

    def serve(self, datas, k_valid=None) -> List[np.ndarray]:
        """Submit + flush convenience: one labels array per input.
        Results of OTHER requests already pending stay queued for the
        next :meth:`flush`."""
        return [lbl for lbl, _ in self.serve_versioned(datas, k_valid)]

    def serve_versioned(self, datas,
                        k_valid=None) -> List[Tuple[np.ndarray, int]]:
        """Like :meth:`serve`, returning (labels, tau_version) pairs —
        the version identifies exactly which tau buffer produced each
        request's attachment."""
        return [(lbl, ver)
                for lbl, ver, _ in self._serve_all(datas, k_valid)]

    def serve_predict(self, datas, k_valid=None) -> List[ServedPrediction]:
        """Submit + flush through the per-cluster heads: one
        :class:`ServedPrediction` per input (same labels/versions as
        :meth:`serve_versioned`)."""
        if self._head_spec is None:
            raise StreamConfigError(
                "serve_predict() needs per-cluster serving heads: set "
                "StreamConfig.heads to 'linear' or a registered model "
                "config (it is 'off')")
        return [ServedPrediction(lbl, ver, pred[0], pred[1], pred[2])
                for lbl, ver, pred in self._serve_all(datas, k_valid)]

    def _serve_all(self, datas, k_valid) -> List[tuple]:
        kvs = ([None] * len(datas) if k_valid is None else list(k_valid))
        assert len(kvs) == len(datas), (len(kvs), len(datas))
        rids = [self.submit(d, kv) for d, kv in zip(datas, kvs)]
        got = self._flush_all()
        mine = [got.pop(r) for r in rids]
        self._done.update(got)
        return mine

    def _stage(self, batch, B: int, n_pad: int, s_pad: int) -> _Staging:
        """Write ``batch`` into the next slot of the staging ring of its
        shape, made (``STAGING_DEPTH`` slots) at the shape's first
        batch; waits first if that slot's last step may still read it."""
        tel = self.telemetry
        key = (B, n_pad, s_pad)
        ring, turn = self._staging.get(key, (None, 0))
        if ring is None:
            ring = [_Staging(B, n_pad, s_pad, self.cfg.d)
                    for _ in range(STAGING_DEPTH)]
            tel.staging_allocs += STAGING_DEPTH
        else:
            tel.staging_reuses += 1
        self._staging[key] = (ring, (turn + 1) % STAGING_DEPTH)
        st = ring[turn]
        tel.staging_waits += st.claim()
        st.write(batch)
        return st

    def _serve_batch(self, batch, bucket, staged,
                     decision: AutoscaleDecision) -> None:
        """Phase 1 of a flush: dispatch one batch's serve step + fold
        (+ cadence refresh) at the flush decision's (shards, batch)
        shape and stage its device-side labels. ``bucket`` is the
        ``_bucket_key`` the group was collected under — the point-count
        rung alone (encoder off) or the (n_pad, seq_pad) pair (encoder
        on, where the batch carries raw token sequences the plane
        encodes ahead of the solve). Nothing here waits on the device
        unless the admission policy needs report weights
        (``needs_weight`` policies synchronize once per batch) or the
        staging slot the batch is written into is still being read by
        the step of an earlier batch of its shape."""
        cfg = self.cfg
        encoded = self._enc_spec is not None
        n_pad, s_pad = bucket if encoded else (bucket, 0)
        B = decision.batch_size
        shards = decision.shards
        if cfg.autoscale != "off":
            # The decision's batch rung is the FLUSH ceiling; each
            # bucket group (and a group's last slice) right-sizes to
            # its own power-of-two rung so mixed-rung traffic never
            # pads one thin group up to the whole queue's depth —
            # repeat-padding rows are real compute. Deterministic (a
            # function of the group size alone), so replay holds; the
            # active shard count follows the batch down through THE
            # shard rule (a multi-axis grant has no sub-grant, so a
            # right-sized group there drops to one shard).
            B = min(B, pow2_ceil(len(batch)))
            shards = shards_for(B, shards, self.autoscaler.n_axes)
        tel = self.telemetry
        with tel.phase("prep"):
            st = self._stage(batch, B, n_pad, s_pad)
            keys = _request_keys(self._base_key, st.rids.astype(np.uint32))
            version = self._taubuf.version
        with tel.phase("step", rung=n_pad, rows=len(batch)):
            if encoded:
                self._encoded_points += sum(
                    item[1].shape[0] for item in batch)
            data, pmask, kv = (jnp.asarray(x)
                               for x in (st.data, st.pmask, st.kv))
            tmask = None if st.tmask is None else jnp.asarray(st.tmask)
            st.busy = (data, pmask, kv, tmask)
            out = self.plane.step(
                self.tau, keys, data, pmask, kv, shards=shards,
                heads=self.heads, encoder=self.encoder, token_mask=tmask)
            labels, centers, cmask, weights = out[:4]
            st.busy = labels
            # With heads on, out[4:] is (preds, cluster, kept).
            entry = (batch, labels, version,
                     self.plane.last_proj_iters) + tuple(out[4:])
        tel.stepped(B, n_pad)
        if cfg.fold_reports:
            self._fold(batch, st.rids, centers, cmask, weights,
                       shards=shards)
        staged.append(entry)

    # -------------------------------------------------------------- fold --

    def _admit_and_fold(self, rids, dev_w, centers, cmask, fold_w,
                        total: Optional[int] = None,
                        shards: Optional[int] = None) -> int:
        """THE admission step shared by round seeding and streaming:
        the batch goes through ``FoldPolicy.admit_padded`` (global
        request order, within-batch evictions suppressed, declined and
        padding entries already the out-of-capacity sentinel), and the
        granted reports scatter into their slots through the serve
        plane — ``server.aggregate_incremental`` stays the single fold
        primitive (its collective sibling on the sharded plane).
        ``total`` pads the slot vector past ``len(rids)`` (the serve
        batch's repeat-padding rows, which never fold); ``shards`` is
        the flush decision's active count. Returns the number of
        GRANTED admissions (the refresh-cadence count)."""
        slots, granted = self.policy.admit_padded(rids, dev_w,
                                                  total=total)
        if granted:
            # Stamp each admitted slot with its REQUEST id (the epoch
            # the drift decay is keyed to) — under lru/reservoir the
            # slot and the request id diverge, so the default
            # epochs=slots would mis-age recycled slots. Padding rows
            # carry sentinel slots and never scatter.
            ep = np.zeros((len(slots),), np.int64)
            ep[:len(rids)] = np.asarray(rids, np.int64)
            self.state = self.plane.fold(
                self.state, jnp.asarray(slots, jnp.int32),
                centers, cmask, weights=fold_w, shards=shards,
                epochs=jnp.asarray(ep, jnp.int32))
        return granted

    def _fold(self, batch, rids, centers, cmask, weights, shards=None):
        with self.telemetry.phase("fold"):
            dev_w = (np.asarray(jnp.sum(weights, axis=1))[:len(batch)]
                     if self.policy.needs_weight else None)
            admitted = self._admit_and_fold(
                rids[:len(batch)], dev_w, centers, cmask,
                weights if self.cfg.weight_by_core_counts else None,
                total=len(rids), shards=shards)
            if not admitted:
                return
            self._since_refresh += admitted
            if self.cfg.refresh_every and (
                    self._since_refresh >= self.cfg.refresh_every):
                if self.cfg.refresh == "sync":
                    self.refresh()
                else:
                    self._stage_refresh()

    # ----------------------------------------------------------- refresh --

    def _refinalize(self):
        """THE re-finalization shared by the sync and async refresh:
        Algorithm 2 over every folded report, with the drift layer on
        top when configured (DESIGN.md §14).

        * ``drift="off"`` — exactly the historical finalize call
          (bitwise: decay never touches the math).
        * ``drift="decay"`` — every slot's fold weight is scaled by
          2^(-age/half_life) (age = requests since its fold, from the
          slot's epoch stamp); fully-decayed slots are masked out so a
          zero mass can never divide into NaN tau. The per-center
          attached mass histogram is recomputed here — the flush
          boundary is where drift state evolves.
        * ``drift="split_merge"`` — additionally, starved centers
          (mass < retire_frac x mean) are retired and re-seeded from
          the residual reports of over-massed centers
          (mass > split_factor x mean), max-min style, followed by one
          ``server.lloyd_round`` — all deterministic, so the decision
          sequence replays bitwise from a checkpoint.

        Returns ``(agg, tau)`` — ``tau`` is what the caller commits
        through the TauBuffer (one atomic versioned bump either way).
        """
        cfg = self.cfg
        if cfg.drift == "off":
            agg = server.finalize(self.state, cfg.k,
                                  weighted=cfg.weight_by_core_counts)
            return agg, agg.tau_centers
        decay = (self._next_id, cfg.drift_half_life)
        agg = server.finalize(self.state, cfg.k, decay=decay)
        mask, w = server.decayed_evidence(self.state, *decay)
        mass = server.center_mass(agg, mask, w)
        tau = agg.tau_centers
        if cfg.drift == "split_merge":
            st = self.state
            # Same sanitization finalize applies: masked slots carry no
            # evidence, so their (possibly garbage) coordinates must
            # not reach the re-seed distances or the Lloyd round.
            flat = jnp.where(mask[..., None], st.centers,
                             jnp.zeros_like(st.centers)
                             ).reshape(-1, cfg.d).astype(jnp.float32)
            tau, take, donors, n_mv = server.split_retire(
                flat, mask.reshape(-1), agg, mass, cfg.k,
                split_factor=cfg.drift_split_factor,
                retire_frac=cfg.drift_retire_frac,
                max_moves=cfg.drift_max_moves, weights=w.reshape(-1))
            moves = int(np.asarray(n_mv))
            self._drift_events += 1 if moves else 0
            self._drift_moves += moves
            self._drift_last = moves
            if moves and self._head_spec is not None:
                # A re-seeded center splits off its donor's traffic, so
                # its head starts as a COPY of the donor's (the model
                # that was serving those requests). Staged here,
                # applied by _commit_heads_perm at the same boundary as
                # the tau version bump — labels and predictions can
                # never disagree about which center generation they
                # came from. Overwrite (not compose): donors index the
                # CURRENT slot-stable heads, and any previously staged
                # perm was committed with its own tau swap.
                perm = np.arange(cfg.k, dtype=np.int64)
                tk = np.asarray(take, bool)
                perm[tk] = np.asarray(donors, np.int64)[tk]
                self._heads_perm = perm
        self._drift_mass = np.asarray(mass, np.float32)
        return agg, tau

    def _counted_refinalize(self):
        """:meth:`_refinalize`, counted: one refresh, and one build when
        Algorithm 2's program had to be traced for it."""
        tel, traced = self.telemetry, server.traces()
        tel.refreshes += 1
        out = self._refinalize()
        tel.refresh_builds += server.traces() != traced
        return out

    def refresh(self) -> server.KFedAggregate:
        """Re-finalize Algorithm 2 over every folded report (round
        devices + streamed attachments) and swap in the new tau centers
        NOW (one atomic version bump). tau is a traced argument of the
        serve step, so no recompile."""
        with self.telemetry.phase("refresh"):
            agg, tau = self._counted_refinalize()
            self._taubuf = self._taubuf.swap_now(self.plane.localize(tau))
            self._commit_heads_perm()
            self._since_refresh = 0
        return agg

    def _stage_refresh(self) -> None:
        """The async half of the refresh: build the STANDBY tau buffer
        (jax dispatches the re-finalization asynchronously, so serving
        against the active buffer continues while it computes) and
        defer the version-bump swap to the next flush boundary."""
        with self.telemetry.phase("refresh"):
            _, tau = self._counted_refinalize()
            self._taubuf = self._taubuf.stage(self.plane.localize(tau))
            self._since_refresh = 0

    def _commit_heads_perm(self) -> None:
        """Apply a staged split/retire head re-map (§14 x §16): the
        atomic partner of the TauBuffer commit/swap that staged it."""
        if self._heads_perm is None or self._head_spec is None:
            self._heads_perm = None
            return
        perm = jnp.asarray(self._heads_perm, jnp.int32)
        self.heads = jax.tree.map(lambda p: p[perm], self.heads)
        self._heads_perm = None

    # -------------------------------------------------------- checkpoint --

    def _counters(self) -> np.ndarray:
        return np.asarray([self._next_id, self._since_refresh,
                           self._served_devices, self._served_points,
                           self._base_seed], np.int64)

    def save(self, path: str) -> str:
        """Checkpoint both tau buffers + version, fold state, counters,
        admission-policy identity/state, the autoscale controller's
        decision state (schema v3), and — schema v4 — the drift mode,
        its split/retire counters and the per-center mass histogram
        (the fold state's epoch stamps ride inside ``server``), so a
        restore replays labels, tau versions, scaling decisions AND
        split/retire decisions bitwise (npz via ``checkpoint.store``).
        Schema v5 (heads enabled) additionally rides the per-cluster
        head params, the heads/arch tag, the routed-serving counters,
        and any STAGED split/retire head re-map — so a restore
        mid-refresh-window commits the same perm at the same boundary.
        Schema v6 (encoder enabled) rides the ingestion-encoder params
        under an encoder/dtype/seq-len tag plus the encoded-point
        counter, so a restored service embeds submissions bitwise like
        the writer. Pending requests are not persisted."""
        from repro.fed.policy import POLICY_IDS
        extra = {}
        if self._head_spec is not None:
            from repro.checkpoint.store import encode_tag
            extra["heads"] = self.heads
            extra["heads_tag"] = encode_tag(
                f"{self.cfg.heads}|{self.cfg.head_arch}")
            extra["heads_counters"] = np.asarray(
                [self._routed_served, self._overflowed], np.int64)
            if self._heads_perm is not None:
                extra["heads_perm"] = np.asarray(self._heads_perm,
                                                 np.int64)
        if self._enc_spec is not None:
            from repro.checkpoint.store import encode_tag
            extra["encoder"] = self.encoder
            extra["encoder_tag"] = encode_tag(
                f"{self.cfg.encoder}|{self.cfg.encode_dtype}|"
                f"{self.cfg.encode_seq_len}")
            extra["encoder_counters"] = np.asarray(
                [self._encoded_points], np.int64)
        return save_pytree(path, {
            **extra,
            "tau_bufs": self._taubuf.bufs,
            "tau_meta": self._taubuf.meta_array(),
            "server": self.state,
            "counters": self._counters(),
            "policy_id": np.asarray(POLICY_IDS[self.policy.name],
                                    np.int64),
            "policy": self.policy.state_arrays(),
            "autoscale_id": np.asarray(AUTOSCALE_IDS[self.cfg.autoscale],
                                       np.int64),
            "drift_id": np.asarray(DRIFT_IDS[self.cfg.drift], np.int64),
            "drift_state": np.asarray(
                [self._drift_events, self._drift_moves,
                 self._drift_last], np.int64),
            "drift_mass": np.asarray(self._drift_mass, np.float32),
            **self.autoscaler.state_arrays()})

    @classmethod
    def restore(cls, path: str, cfg: StreamConfig) -> "AttachService":
        """Deprecated: use ``fed.api.Session.restore`` instead."""
        warn_legacy("fed.stream.AttachService.restore", "Session.restore")
        return cls._restore(path, cfg)

    @classmethod
    def _restore(cls, path: str, cfg: StreamConfig, *, mesh=None,
                 serve_axes=None) -> "AttachService":
        from repro.fed.policy import POLICY_IDS
        policy = make_policy(
            cfg.fold_policy, cfg.capacity, seed=cfg.policy_seed,
            half_life=(cfg.drift_half_life if cfg.drift != "off" else 0))
        # ONE open reads every generation-specific extra; presence of
        # "tau_bufs" doubles as the v1-vs-v2 schema probe,
        # "server/.epoch" (the fold state's epoch stamps) as the v4
        # server probe.
        extras = load_extras(path, ("policy_id", "autoscale_id",
                                    "autoscale_state",
                                    "autoscale_ladder", "tau_bufs",
                                    "drift_id", "drift_state",
                                    "drift_mass", "server/.epoch",
                                    "heads_tag", "heads_counters",
                                    "heads_perm", "encoder_tag",
                                    "encoder_counters"))
        # Refuse a policy mismatch up front (named error, not a bare
        # KeyError / silent state corruption): the checkpoint's slot
        # bookkeeping is only meaningful under the policy that wrote
        # it. Checkpoints from before the policy layer existed could
        # only have been written under the drop rule.
        saved = (int(extras["policy_id"]) if "policy_id" in extras
                 else POLICY_IDS["drop"])
        if saved != POLICY_IDS[cfg.fold_policy]:
            names = {v: n for n, v in POLICY_IDS.items()}
            raise StreamConfigError(
                f"StreamConfig.fold_policy={cfg.fold_policy!r} does not "
                f"match the checkpoint at {path!r}, which was saved "
                f"under fold_policy={names.get(saved, saved)!r}")
        # Schema v3 additionally carries the autoscale decision state;
        # the controller config must match what wrote it, or the
        # replayed decision sequence (and with it the refresh/version
        # boundaries) would silently diverge. v1/v2 checkpoints predate
        # the controller — any autoscale config restores them with a
        # fresh (static) decision.
        if "autoscale_id" in extras:
            saved_as = int(extras["autoscale_id"])
            if saved_as != AUTOSCALE_IDS[cfg.autoscale]:
                names = {v: n for n, v in AUTOSCALE_IDS.items()}
                raise StreamConfigError(
                    f"StreamConfig.autoscale={cfg.autoscale!r} does not "
                    f"match the checkpoint at {path!r}, which was saved "
                    f"under autoscale={names.get(saved_as, saved_as)!r}")
        # Schema v4 carries the drift mode + state. Pre-v4 checkpoints
        # restore under ANY drift config with drift state
        # default-initialized (drift is strictly additive); a v4
        # checkpoint refuses a drift-mode mismatch — the fold epochs,
        # mass histogram and split/retire counters are only meaningful
        # under the mode that wrote them.
        if "drift_id" in extras:
            saved_dr = int(extras["drift_id"])
            if saved_dr != DRIFT_IDS[cfg.drift]:
                names = {v: n for n, v in DRIFT_IDS.items()}
                raise StreamConfigError(
                    f"StreamConfig.drift={cfg.drift!r} does not match "
                    f"the checkpoint at {path!r}, which was saved under "
                    f"drift={names.get(saved_dr, saved_dr)!r}")
        # Schema v5 carries the per-cluster head params under a
        # heads/arch tag. Mismatch (including heads="off" against a v5
        # archive, or a v5 restore under a different config/arch)
        # refuses up front — the folded label/fold state replays, but
        # the predictions a caller would get could not match the ones
        # the archive's writer served. Pre-v5 archives restore under
        # ANY heads config (additive, like drift): heads start from
        # the deterministic seed-derived init.
        if "heads_tag" in extras:
            from repro.checkpoint.store import decode_tag
            tag = decode_tag(extras["heads_tag"])
            want = f"{cfg.heads}|{cfg.head_arch}"
            if tag != want:
                sv_h, sv_a = tag.split("|", 1)
                raise StreamConfigError(
                    f"StreamConfig.heads={cfg.heads!r}/"
                    f"head_arch={cfg.head_arch!r} does not match the "
                    f"checkpoint at {path!r}, which was saved under "
                    f"heads={sv_h!r}/head_arch={sv_a!r}")
        # Schema v6 carries the ingestion-encoder params under an
        # encoder/dtype/seq-len tag. Mismatch (including encoder="off"
        # against a v6 archive, or a different config/dtype/ceiling)
        # refuses up front — the writer's embeddings, and so its
        # labels, could not be reproduced. Pre-v6 archives restore
        # under ANY encoder config (additive, like heads): the encoder
        # starts from the deterministic seed-derived init.
        if "encoder_tag" in extras:
            from repro.checkpoint.store import decode_tag
            tag = decode_tag(extras["encoder_tag"])
            want = (f"{cfg.encoder}|{cfg.encode_dtype}|"
                    f"{cfg.encode_seq_len}")
            if tag != want:
                sv_e, sv_dt, sv_sl = tag.split("|", 2)
                raise StreamConfigError(
                    f"StreamConfig.encoder={cfg.encoder!r}/"
                    f"encode_dtype={cfg.encode_dtype!r}/"
                    f"encode_seq_len={cfg.encode_seq_len!r} does not "
                    f"match the checkpoint at {path!r}, which was "
                    f"saved under encoder={sv_e!r}/encode_dtype="
                    f"{sv_dt!r}/encode_seq_len={sv_sl}")
        # Schema v2 carries the double-buffered tau; v1 (pre-plane)
        # checkpoints hold one tau — restored as version 0 with both
        # buffers equal, so old checkpoints keep replaying bitwise.
        v2 = "tau_bufs" in extras
        # Pre-v4 archives hold a 4-field server state (no epoch
        # stamps): load those leaves through a template with the SAME
        # attribute key paths ("server/.centers" ...) and default the
        # epochs to zero.
        v4srv = "server/.epoch" in extras
        srv_like = server.init_state(cfg.capacity, cfg.k_prime, cfg.d)
        like = {
            "server": (srv_like if v4srv
                       else _ServerStateV3(*tuple(srv_like)[:4])),
            "counters": np.zeros((5,), np.int64),
            "policy": policy.state_like(),
        }
        if v2:
            like["tau_bufs"] = jnp.zeros((2, cfg.k, cfg.d), jnp.float32)
            like["tau_meta"] = np.zeros((3,), np.int64)
        else:
            like["tau"] = jnp.zeros((cfg.k, cfg.d), jnp.float32)
        if "policy_id" in extras:
            like["policy_id"] = np.zeros((), np.int64)
        if "heads_tag" in extras:
            # The deterministic init doubles as the exact-shape restore
            # template (same spec -> same leaf shapes by construction).
            from repro.models import heads as heads_mod
            like["heads"] = heads_mod.init_heads(
                jax.random.PRNGKey(0), cfg.k, cfg.head_spec())
            like["heads_tag"] = np.zeros_like(
                np.asarray(extras["heads_tag"]))
            like["heads_counters"] = np.zeros((2,), np.int64)
            if "heads_perm" in extras:
                like["heads_perm"] = np.zeros((cfg.k,), np.int64)
        if "encoder_tag" in extras:
            # The deterministic init doubles as the exact-shape restore
            # template (same spec -> same leaf shapes by construction).
            from repro.models import encoder as enc_mod
            like["encoder"] = enc_mod.init_encoder(
                jax.random.PRNGKey(0), cfg.encoder_spec())
            like["encoder_tag"] = np.zeros_like(
                np.asarray(extras["encoder_tag"]))
            like["encoder_counters"] = np.zeros((1,), np.int64)
        tree = load_pytree(path, like)
        if tree["policy"]:
            policy.load_state(tree["policy"])
        taubuf = (TauBuffer.from_arrays(tree["tau_bufs"], tree["tau_meta"])
                  if v2 else TauBuffer.fresh(tree["tau"]))
        srv = (tree["server"] if v4srv else server.ServerState(
            *tree["server"],
            jnp.zeros((cfg.capacity,), jnp.int32)))
        cnt = np.asarray(tree["counters"])
        svc = cls(cfg, taubuf.tau, tau_buffer=taubuf,
                  state=srv, policy=policy,
                  seed=int(cnt[4]), next_id=int(cnt[0]),
                  since_refresh=int(cnt[1]), served_devices=int(cnt[2]),
                  served_points=int(cnt[3]), mesh=mesh,
                  serve_axes=serve_axes,
                  heads=tree.get("heads"),
                  encoder=tree.get("encoder"))
        if "encoder_counters" in extras:
            ec = np.asarray(extras["encoder_counters"], np.int64)
            svc._encoded_points = int(ec[0])
        if "heads_counters" in extras:
            hc = np.asarray(extras["heads_counters"], np.int64)
            svc._routed_served = int(hc[0])
            svc._overflowed = int(hc[1])
        if "heads_perm" in extras:
            svc._heads_perm = np.asarray(extras["heads_perm"],
                                         np.int64).copy()
        if "autoscale_state" in extras:
            svc.autoscaler.load_state(extras["autoscale_state"],
                                      extras["autoscale_ladder"])
        if "drift_state" in extras:
            ds = np.asarray(extras["drift_state"], np.int64)
            svc._drift_events = int(ds[0])
            svc._drift_moves = int(ds[1])
            svc._drift_last = int(ds[2])
        if "drift_mass" in extras:
            dm = np.asarray(extras["drift_mass"], np.float32)
            if dm.shape == (cfg.k,):
                svc._drift_mass = dm.copy()
        return svc

    # ------------------------------------------------------------- stats --

    def _heads_stats(self) -> dict:
        if self._head_spec is None:
            return {"mode": "off"}
        from repro.models.heads import head_param_count
        from repro.fed.plane import route_capacity
        return {
            "mode": self.cfg.heads,
            "arch": self.cfg.head_arch,
            "capacity_factor": float(self.cfg.head_capacity),
            "queue_capacity": route_capacity(
                self.cfg.batch_size, self.cfg.k,
                self.cfg.head_capacity),
            "params_per_head": head_param_count(self._head_spec),
            "routed_served": self._routed_served,
            "overflowed": self._overflowed,
            "remap_pending": self._heads_perm is not None,
        }

    def _encoder_stats(self) -> dict:
        if self._enc_spec is None:
            return {"mode": "off"}
        from repro.models.encoder import encoder_param_count
        return {
            "mode": self.cfg.encoder,
            "dtype": self.cfg.encode_dtype,
            "seq_len": self.cfg.encode_seq_len,
            "layers": self._enc_spec.n_layers,
            "params": encoder_param_count(self._enc_spec),
            "encoded_points": self._encoded_points,
        }

    def stats(self) -> dict:
        return {
            "served_devices": self._served_devices,
            "served_points": self._served_points,
            "folded": int(np.asarray(jnp.sum(self.state.received))),
            "capacity": self.cfg.capacity,
            "fold_policy": self.policy.name,
            "pending": len(self._pending),
            "undelivered": len(self._done),
            "since_refresh": self._since_refresh,
            "tau_version": self._taubuf.version,
            "refresh_pending": self._taubuf.pending,
            "autoscale": self.autoscaler.stats(),
            "flush": self.telemetry.stats(),
            "heads": self._heads_stats(),
            "encoder": self._encoder_stats(),
            "drift": {
                "mode": self.cfg.drift,
                "half_life": self.cfg.drift_half_life,
                "events": self._drift_events,
                "moves": self._drift_moves,
                "last_moves": self._drift_last,
                "mass": [float(m) for m in self._drift_mass],
            },
            **self.plane.describe(),
        }
