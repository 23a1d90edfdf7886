"""The sharded streaming serve plane (DESIGN.md §11).

Everything the streaming layer (``fed/stream.py``, §9) executes on
device routes through this module, so ONE knob — ``serve_axes`` on the
``FederationPlan`` — decides whether the hot serving path runs on a
single host or shard_mapped over a mesh:

  * **serve step** — the jitted (batch local Algorithm 1 solve +
    Theorem 3.2 attach) over a fixed ``(batch_size, n_pad, d)`` request
    tensor. The request batch axis is embarrassingly parallel, so the
    sharded plane splits it over the ``serve_axes`` mesh axes with the
    tau centers replicated (``P()``); per-request results are bitwise
    identical to the unsharded step because every request's computation
    is a function of its own (key, data, k_valid) only.
  * **fold scatter** — the per-slot scatter of served reports into the
    replicated incremental server state. ``server.aggregate_incremental``
    stays the single fold primitive; the sharded plane runs its
    collective sibling ``server.aggregate_incremental_sharded`` (each
    shard scatters ITS slice of the batch, disjoint slots combine with
    an exact psum). Slot admission itself stays host-side in
    ``fed/policy.py`` and is shard-deterministic by contract — the plane
    only ever executes an already-decided ``(B,)`` slot vector.
  * **optional stages** — the plan's ONE serve step is composed from
    the stages it turns on (:func:`_serve_step`), one jitted dispatch
    and one compiled entry per active shard count:

    - **encode** (§17, ``encoder != "off"``), in front: devices submit
      raw ``(n, seq, d)`` token/patch sequences; a zoo encoder forward
      embeds them (masked-mean pooled to ``d``) and the unchanged
      solve+attach runs on the embeddings.
    - **routed heads** (§16, ``heads != "off"``), behind:
      majority-vote one cluster per request from its Theorem 3.2
      labels, ``moe_dispatch``-gather whole requests into per-cluster
      head queues (clusters are the experts), run each queue through
      ITS head from the ``models``/``configs`` zoo, ``moe_combine``
      back to request order.

    Stage params ride replicated like tau; the label outputs stay
    bitwise-identical to the plain step, and a plan with both stages
    off runs the plain step itself.
  * **double-buffered tau** (:class:`TauBuffer`) — serving reads
    ``bufs[active]``; a refresh builds the standby buffer while serving
    continues, and the swap is an atomic version bump. Every served
    label maps to exactly one tau version; both buffers + the version
    counter ride the §9 checkpoint so a restore mid-window replays the
    same version assignments bitwise.
  * **shard-count switching** (§12) — ``serve_axes`` GRANTS up to
    ``n_shards`` devices; the load-adaptive controller
    (``fed/autoscale.py``) may execute any flush on fewer
    (``shards=`` on :meth:`step`/:meth:`fold`), down to the single-host
    plane at 1. Each active shard count gets its own compiled
    step/fold (a sub-mesh over the first ``s`` granted devices), cached
    forever alongside every (batch, bucket) shape it serves —
    ``compile_count`` tracks first-seen (kind, shards, shape)
    signatures, so steady-state scaling provably never recompiles.

The plane is deliberately free of service bookkeeping (queues, buckets,
policies, checkpoints live in ``fed/stream.py``): it owns exactly the
two device computations of the hot path and their mesh mapping.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.core import server
from repro.core.lloyd import lloyd_attach
from repro.core.local_kmeans import batched_local_prepare, split_local_kw
from repro.kernels import ops
from repro.utils.compat import shard_map as _shard_map

__all__ = ["ServePlane", "ServePlaneError", "TauBuffer", "route_capacity"]


class ServePlaneError(ValueError):
    """A serve-plane configuration failed validation (named, with the
    accepted values) — raised at construction, never inside tracing."""


# ---------------------------------------------------------------------------
# Double-buffered, versioned tau.
# ---------------------------------------------------------------------------


class TauBuffer(NamedTuple):
    """Double-buffered tau centers with an atomic version counter.

    ``bufs[active]`` is what the serve step reads; ``bufs[1 - active]``
    is the standby a refresh writes into. ``stage`` fills the standby
    without touching serving (the async-refresh build phase); ``commit``
    is the atomic swap: active flips and ``version`` bumps by one, so a
    request's recorded version identifies exactly which tau buffer
    produced its labels. ``swap_now`` = stage + commit (the synchronous
    refresh). Immutable — every transition returns a new TauBuffer, and
    the whole triple serializes into the service checkpoint.
    """
    bufs: jax.Array      # (2, k, d) f32
    active: int          # which buffer serves
    version: int         # monotone; bumps exactly once per commit
    pending: bool        # standby staged, swap deferred to a boundary

    @classmethod
    def fresh(cls, tau) -> "TauBuffer":
        t = jnp.asarray(tau, jnp.float32)
        return cls(jnp.stack([t, t]), 0, 0, False)

    @property
    def tau(self) -> jax.Array:
        return self.bufs[self.active]

    @property
    def standby(self) -> jax.Array:
        return self.bufs[1 - self.active]

    def stage(self, new_tau) -> "TauBuffer":
        """Write the standby buffer; serving keeps reading the active
        one until :meth:`commit`."""
        t = jnp.asarray(new_tau, jnp.float32)
        bufs = jnp.stack([self.bufs[self.active], t]
                         if self.active == 0 else [t, self.bufs[self.active]])
        return TauBuffer(bufs, self.active, self.version, True)

    def commit(self) -> "TauBuffer":
        """The atomic swap: activate the standby, bump the version."""
        return TauBuffer(self.bufs, 1 - self.active, self.version + 1,
                         False)

    def swap_now(self, new_tau) -> "TauBuffer":
        return self.stage(new_tau).commit()

    # -- checkpoint plumbing (npz-able arrays) --------------------------
    def meta_array(self):
        import numpy as np
        return np.asarray([self.active, self.version, int(self.pending)],
                          np.int64)

    @classmethod
    def from_arrays(cls, bufs, meta) -> "TauBuffer":
        import numpy as np
        m = np.asarray(meta)
        return cls(jnp.asarray(bufs, jnp.float32), int(m[0]), int(m[1]),
                   bool(m[2]))


# ---------------------------------------------------------------------------
# The plane: serve step + fold scatter, single-host or shard_mapped.
# ---------------------------------------------------------------------------


def _make_step(cfg):
    """The ONE serve-step body (shared verbatim by both planes): vmapped
    Algorithm 1 steps 1-3 over the request batch, then the FUSED
    bounded-Lloyd solve + Theorem 3.2 attach against the replicated tau
    + Definition 3.3 induced labels in a single ``lloyd_attach``
    dispatch (kernels/solve_attach, DESIGN.md §13). ``cfg.serve_dtype``
    selects f32 (bitwise vs the pre-fusion staged step) or bf16 storage
    with f32 accumulation. The fifth output is each row's step-1
    projection iteration count, (B,) int32."""
    prep_kw, max_iters = split_local_kw(cfg.local_kw)

    def step(tau, keys, data, point_mask, k_valid):
        prep = batched_local_prepare(keys, data, k_max=cfg.k_prime,
                                     k_valid=k_valid,
                                     point_mask=point_mask, **prep_kw)
        labels, _, centers, _ = lloyd_attach(
            data, prep.theta, tau, center_mask=prep.center_mask,
            point_mask=point_mask, max_iters=max_iters,
            serve_dtype=cfg.serve_dtype)
        return (labels, centers, prep.center_mask,
                server.core_weights(prep.core_counts), prep.proj_iters)

    return step


def route_capacity(batch: int, k: int, factor: float) -> int:
    """Per-cluster dispatch queue depth for a ``batch``-request step:
    ``ceil(batch * factor / k)`` slots (>= 1). ``factor`` is the plan's
    ``head_capacity`` — 1.0 sizes for a perfectly uniform cluster mix;
    the default 1.25 absorbs moderate skew. Requests past a cluster's
    queue still get labels, just no prediction (DESIGN.md §16). Static
    per (batch, k, factor), so it adds no cache keys beyond the batch
    shape the plane already specializes on."""
    return max(1, int(math.ceil(batch * float(factor) / k)))


def _make_routed_step(cfg, axes=None, axis_sizes=None):
    """The fused routed personalization step (DESIGN.md §16): the SAME
    label body as :func:`_make_step` (labels/centers/fold reports stay
    bitwise-identical to the heads=off plane), then per-request majority
    vote -> ``moe_dispatch`` gather into per-cluster head queues
    (clusters are the experts; whole requests gather by scalar-prefetch
    routing indices, no (k, C, n, d) scatter materialized request-side)
    -> every queue through ITS head (``models/heads.py``, vmapped over
    the stacked params) -> ``moe_combine`` back to request order. All
    routing scatters are int/bool OVERWRITES onto unique slots, so the
    step passes the §15 determinism audit.

    ``axes``/``axis_sizes`` (set by the sharded plane): the
    keep/overflow decision must be a function of the GLOBAL batch, or
    the sharded plane would drop different requests than the
    single-host plane. Each shard all_gathers the (tiny, int32)
    cluster votes, ranks its own requests against the global
    first-come order, and keeps ``C = route_capacity(global B, ...)``
    per cluster — the one deterministic, shard-order-tiled collective
    the routed artifact's §15 contract allows (exactly the sharded
    fold's allowance). Dispatch and head forwards stay shard-local."""
    from repro.fed.personalize import majority_vote
    from repro.models import heads as heads_mod
    spec = cfg.head_spec()
    base = _make_step(cfg)
    k = cfg.k
    shards = 1
    if axes:
        for sz in axis_sizes:
            shards *= int(sz)

    def routed(tau, head_params, keys, data, point_mask, k_valid):
        labels, centers, cmask, weights, iters = base(
            tau, keys, data, point_mask, k_valid)
        B, n_pad, d = data.shape
        C = route_capacity(B * shards, k, cfg.head_capacity)
        S = k * C
        # One cluster per request — the same first-max vote as the
        # offline fed/personalize.cluster_devices assignment. A padding
        # row (no valid points) votes the out-of-range class k: its
        # one-hot is all-zero, so padding never consumes a queue slot
        # and real requests route independently of batch composition.
        cluster = majority_vote(jnp.where(point_mask, labels, -1),
                                k).astype(jnp.int32)
        req = point_mask.any(axis=1)
        eff = jnp.where(req, cluster, k)
        col = jnp.minimum(eff, k - 1)  # safe gather column for padding
        if axes is None:
            gcl, off = eff, 0
        else:
            gcl = jax.lax.all_gather(eff, axes, tiled=True)
            idx = jnp.int32(0)
            for ax, sz in zip(axes, axis_sizes):
                idx = idx * sz + jax.lax.axis_index(ax)
            off = idx * B
        # Global queue position = exclusive running count of earlier
        # same-cluster requests over the WHOLE batch, in global row
        # order; this shard's rows are the [off, off + B) slice.
        goh = jax.nn.one_hot(gcl, k, dtype=jnp.int32)
        cum = jnp.cumsum(goh, axis=0) - goh
        if axes is not None:
            cum = jax.lax.dynamic_slice_in_dim(cum, off, B, axis=0)
        kept = (cum[jnp.arange(B), col] < C) & req
        # Local slot = exclusive running count among locally-KEPT
        # same-cluster rows (a subset of the <= C globally-kept ones,
        # so it always fits; slot order never changes the math — each
        # queue entry is one whole request through one head).
        ohl = (jax.nn.one_hot(eff, k, dtype=jnp.int32)
               * kept[:, None].astype(jnp.int32))
        lpos = (jnp.cumsum(ohl, axis=0) - ohl)[jnp.arange(B), col]
        slot = cluster * C + lpos
        # Invert request->slot into the dispatch kernel's slot->request
        # routing vector. Kept slots are UNIQUE, overflow goes to the
        # dropped sentinel S: int/bool overwrite scatters, never a
        # float accumulation (§15).
        slot_s = jnp.where(kept, slot, S)
        rows = jnp.arange(B, dtype=jnp.int32)
        src = jnp.zeros((S,), jnp.int32).at[slot_s].set(rows,
                                                        mode="drop")
        valid = jnp.zeros((S,), jnp.bool_).at[slot_s].set(True,
                                                          mode="drop")
        # Whole requests gather into queue order (points + validity).
        qdata = ops.moe_dispatch(data.reshape(B, n_pad * d), src,
                                 valid).reshape(k, C, n_pad, d)
        qmask = ops.moe_dispatch(point_mask.astype(jnp.float32), src,
                                 valid).reshape(k, C, n_pad) > 0.5
        ybuf = heads_mod.apply_heads(head_params, qdata, qmask, spec,
                                     serve_dtype=cfg.serve_dtype)
        # top_k=1 with the keep mask as gates: overflowed requests
        # combine to exactly zero.
        preds = ops.moe_combine(ybuf.reshape(S, d),
                                jnp.where(kept, slot, 0),
                                kept.astype(jnp.float32), top_k=1)
        return (labels, centers, cmask, weights, preds, cluster, kept,
                iters)

    return routed


def _make_allk_step(cfg):
    """The IFCA-shaped baseline the routed step is benchmarked against:
    run EVERY cluster's head over the full batch (k forwards per
    request) and select by the vote afterwards. Same label body, same
    per-request predictions as the routed step on its kept requests —
    just k/``head_capacity``-fold more head FLOPs. Benchmark-only; the
    serving stack never calls this."""
    from repro.fed.personalize import majority_vote
    from repro.models import heads as heads_mod
    spec = cfg.head_spec()
    base = _make_step(cfg)
    k = cfg.k

    def allk(tau, head_params, keys, data, point_mask, k_valid):
        labels, centers, cmask, weights, _ = base(tau, keys, data,
                                                  point_mask, k_valid)
        B = data.shape[0]
        cluster = majority_vote(jnp.where(point_mask, labels, -1),
                                k).astype(jnp.int32)
        qdata = jnp.broadcast_to(data[None], (k,) + data.shape)
        qmask = jnp.broadcast_to(point_mask[None],
                                 (k,) + point_mask.shape)
        yb = heads_mod.apply_heads(head_params, qdata, qmask, spec,
                                   serve_dtype=cfg.serve_dtype)
        preds = yb[cluster, jnp.arange(B)]
        kept = jnp.ones((B,), jnp.bool_)
        return labels, centers, cmask, weights, preds, cluster, kept

    return allk


def _serve_step(cfg, axes=None, axis_sizes=None):
    """THE plan's serve step, composed from the stages the plan turns
    on. With encoder and heads off it IS :func:`_make_step`'s ``step``
    (the plain program, module ``jit_step``: no wrapper, no extra
    argument). Otherwise the §17 encode stage runs in front of the
    label body and the §16 routing (:func:`_make_routed_step`, with the
    sharded plane's ``axes``/``axis_sizes``) behind it, in one
    dispatch, called as ``step((tau, encoder, heads), keys, data,
    point_mask, k_valid[, token_mask])`` with ``None`` for a stage the
    plan leaves off. Outputs: the plain five, or the routed eight."""
    encoded = getattr(cfg, "encoder", "off") != "off"
    routed = getattr(cfg, "heads", "off") != "off"
    if not (encoded or routed):
        return _make_step(cfg)
    body = (_make_routed_step(cfg, axes=axes, axis_sizes=axis_sizes)
            if routed else _make_step(cfg))
    if encoded:
        from repro.models import encoder as enc_mod
        enc_spec = cfg.encoder_spec()

    def step(params, keys, data, point_mask, k_valid, token_mask=None):
        tau, enc_params, head_params = params
        if encoded:
            # (B, n, S, d) sequences -> (B, n, d) f32 embeddings at the
            # plan's encode_dtype; the latents never round-trip to host.
            data = enc_mod.apply_encoder(enc_params, data, token_mask,
                                         enc_spec,
                                         encode_dtype=cfg.encode_dtype)
        lead = (tau, head_params) if routed else (tau,)
        return body(*lead, keys, data, point_mask, k_valid)

    return step


class ServePlane:
    """Executes the streaming hot path for an ``AttachService``.

    ``serve_axes=None`` is the single-host plane: ``step`` is exactly
    the historical jitted serve step and ``fold`` is one
    ``server.aggregate_incremental`` scatter — bitwise identical to the
    pre-plane streaming layer. With ``serve_axes`` (and a mesh), both
    are shard_mapped: the request batch axis splits over the named mesh
    axes, tau and the fold state stay replicated, and the fold runs
    through ``server.aggregate_incremental_sharded``.

    The fold contract is fixed-shape: a ``(B,)`` slot vector aligned
    with the batch, where an out-of-capacity sentinel (>= capacity)
    marks declined/padding entries — the scatter drops them
    (``mode="drop"``), so the fold never recompiles as admission
    decisions vary.
    """

    @staticmethod
    def validate_mesh_axes(mesh, axes, batch_size: int) -> int:
        """THE serve-axes validation (shared by the eager Session check
        and plane construction — one rule set, never two). Returns the
        shard count. Raises :class:`ServePlaneError` naming the field
        and the accepted values."""
        if not axes or not all(isinstance(a, str) for a in axes):
            raise ServePlaneError(
                f"serve_axes={axes!r} is invalid: must be None "
                f"(single-host serving) or a non-empty tuple of mesh "
                f"axis names, e.g. ('data',)")
        if mesh is None:
            raise ServePlaneError(
                f"serve_axes={tuple(axes)!r} needs a mesh: "
                f"Session(plan, mesh=...)")
        missing = [a for a in axes if a not in mesh.shape]
        if missing:
            raise ServePlaneError(
                f"serve_axes={tuple(axes)!r}: axes {missing} not in "
                f"the mesh (available: {list(mesh.shape)})")
        n = 1
        for a in axes:
            n *= mesh.shape[a]
        if batch_size % n:
            raise ServePlaneError(
                f"batch_size={batch_size} is invalid: must be "
                f"divisible by the serve_axes shard count {n} "
                f"(axes {tuple(axes)})")
        return n

    def __init__(self, cfg, mesh=None, serve_axes=None):
        self.cfg = cfg
        axes = tuple(serve_axes) if serve_axes else None
        n = (self.validate_mesh_axes(mesh, axes, cfg.batch_size)
             if axes else 1)
        self.mesh = mesh
        self.axes = axes
        self.n_shards = n
        # The RECOMMENDED per-shard row-chunk budget (kernels/ops.py
        # hint, surfaced in stats()): callers streaming large point
        # sets next to this plane (e.g. attach_fn-scale labeling)
        # should chunk at this, not the global threshold, so the
        # aggregate footprint across concurrent shards stays bounded.
        self.chunk_rows = ops.plan_chunk_rows(self.n_shards)
        # Per-active-shard-count compiled entries (the §12 multi-spec
        # cache): s -> (step_jit, fold_jit | None, batch sharding | None,
        # param sharding | None). The step is the plan's ONE composed
        # step (:func:`_serve_step`). Entries are built once and kept
        # forever; together with jax's shape-keyed jit cache, every
        # (shards, batch, bucket) triple compiles exactly once.
        # ``compile_count`` counts first-seen (kind, shards, shape)
        # signatures — what the autoscale tests and the benchmark
        # assert stays flat in steady state.
        self._planes = {}
        self._signatures = set()
        self.compile_count = 0
        self.last_proj_iters = None
        self._has_encoder = getattr(cfg, "encoder", "off") != "off"
        self._has_heads = getattr(cfg, "heads", "off") != "off"
        self._plane_for(n)

    # ------------------------------------------------------------------
    def _submesh(self, s: int):
        """A mesh over the first ``s`` granted devices (single serve
        axis only — a multi-axis grant has no canonical sub-grant and
        the controller never asks for one)."""
        return Mesh(self.mesh.devices.flatten()[:s], self.axes)

    def _plane_for(self, s: int):
        """The compiled (step, fold, batch sharding, param sharding)
        entry for an active shard count ``s`` — built on first use,
        cached forever."""
        entry = self._planes.get(s)
        if entry is not None:
            return entry
        if not (1 <= s <= self.n_shards):
            raise ServePlaneError(
                f"shards={s} is invalid: the plan's serve_axes grant "
                f"1..{self.n_shards} active shards")
        if s > 1 and s != self.n_shards and len(self.axes) > 1:
            raise ServePlaneError(
                f"shards={s} is invalid: multi-axis serve_axes "
                f"{self.axes!r} only switch between 1 and the full "
                f"grant ({self.n_shards})")
        if s == 1:
            entry = (jax.jit(_serve_step(self.cfg)), None, None, None)
        else:
            from jax.sharding import NamedSharding
            mesh = self.mesh if s == self.n_shards else self._submesh(s)
            axes = self.axes
            spec = P(axes)
            sizes = tuple(int(mesh.shape[a]) for a in axes)
            # Params (tau, encoder and head weights) ride replicated;
            # every batch array splits over the batch axis.
            n_batch = 5 if self._has_encoder else 4
            step_sharded = _shard_map(
                _serve_step(self.cfg, axes=axes, axis_sizes=sizes),
                mesh=mesh, in_specs=(P(),) + (spec,) * n_batch,
                out_specs=spec)

            def fold_sharded(state, slots, centers, cmask, weights,
                             epochs):
                return server.aggregate_incremental_sharded(
                    state, slots, centers, cmask, axes, weights=weights,
                    epochs=epochs)

            fold_mesh = jax.jit(_shard_map(
                fold_sharded, mesh=mesh,
                in_specs=(P(), spec, spec, spec, spec, spec),
                out_specs=P()))
            entry = (jax.jit(step_sharded), fold_mesh,
                     NamedSharding(mesh, spec),
                     NamedSharding(mesh, P()))
        self._planes[s] = entry
        return entry

    def _count(self, kind: str, s: int, shape) -> None:
        sig = (kind, s, tuple(shape))
        if sig not in self._signatures:
            self._signatures.add(sig)
            self.compile_count += 1

    def _place(self, entry, params, batch):
        """THE placement of one step's inputs. On the sharded plane the
        host batch lands directly in its batch-axis sharding — one
        host->shard copy each, not a device-0 bounce plus an all-to-all
        reshard inside the jitted step — and the params ride along
        replicated (tau is k x d — bytes), so a buffer committed
        elsewhere by a refresh can never clash with the batch's device
        set when the active shard count switches. A single-host step on
        a mesh plane pins the params to the canonical device."""
        _, _, batch_sh, param_sh = entry
        if batch_sh is not None:
            return (jax.device_put(params, param_sh),
                    jax.device_put(batch, batch_sh))
        if self.axes:
            return (jax.device_put(params, self.mesh.devices.flatten()[0]),
                    batch)
        return params, batch

    def step(self, tau, keys, data, point_mask, k_valid, shards=None, *,
             heads=None, encoder=None, token_mask=None):
        """Serve one fixed-shape batch through the plan's one step.
        ``data`` is (B, n_pad, d) points, or with the encoder on
        (B, n_pad, seq_pad, d) raw token/patch sequences with their
        (B, n_pad, seq_pad) ``token_mask`` (DESIGN.md §17). Returns
        (labels (B, n_pad), centers (B, k', d), center_mask (B, k'),
        core weights (B, k')) and, with heads on (§16), also (preds
        (B, d) f32, cluster (B,) i32, kept (B,) bool) — preds are zero
        and kept False where a request overflowed its cluster's queue.
        Sharded over the batch axis on the sharded plane, bitwise
        identical per request at ANY active shard count (``shards``,
        default: the full grant). The batch's Algorithm 1 step-1
        iteration counts, (B,) int32 on the device, are left in
        :attr:`last_proj_iters`."""
        bad = [name for name, arg, on in (
            ("encoder", encoder, self._has_encoder),
            ("token_mask", token_mask, self._has_encoder),
            ("heads", heads, self._has_heads)) if (arg is not None) != on]
        if bad:
            raise ServePlaneError(
                f"step(): {bad} disagree with the plan (encoder="
                f"{getattr(self.cfg, 'encoder', 'off')!r}, heads="
                f"{getattr(self.cfg, 'heads', 'off')!r}): pass encoder "
                f"params and token_mask exactly when the encoder is on, "
                f"head params exactly when heads are on")
        s = self.n_shards if shards is None else int(shards)
        entry = self._plane_for(s)
        self._count("step", s, data.shape)
        params = ((tau, encoder, heads)
                  if self._has_encoder or self._has_heads else tau)
        batch = ((keys, data, point_mask, k_valid)
                 + ((token_mask,) if self._has_encoder else ()))
        params, batch = self._place(entry, params, batch)
        out = entry[0](params, *batch)
        self.last_proj_iters = out[-1]
        return out[:-1]

    def localize(self, x):
        """Pull a (small) array stranded on an active sub-mesh — e.g. a
        tau re-finalized from a sharded fold state — back to one
        canonical device, so the double-buffer stack and later steps at
        OTHER shard counts never mix incompatible device sets."""
        if self.axes:
            return jax.device_put(jnp.asarray(x),
                                  self.mesh.devices.flatten()[0])
        return jnp.asarray(x)

    def fold(self, state, slots, centers, cmask, weights=None,
             shards=None, epochs=None):
        """Scatter one batch of already-admitted reports into the
        replicated fold state. ``slots``: (B,) int32, entries >= the
        state capacity are dropped (declined / padding / within-batch
        evictions). ``shards`` is the flush decision's active count;
        with the default (None), only the steady plan-shaped batch
        rides the mesh — other lengths (e.g. round seeding) take the
        single-host scatter, as before the controller existed.
        ``epochs``: optional (B,) request-id epochs stamped on the
        slots for the drift layer (default: the slot ids, matching
        ``aggregate_incremental``)."""
        if weights is None:
            # The explicit form of aggregate_incremental's default —
            # same scattered values, one jit signature for both cases.
            weights = jnp.ones(jnp.shape(cmask), jnp.float32)
        if epochs is None:
            # Likewise the explicit epochs default (the slot ids).
            epochs = jnp.asarray(slots, jnp.int32)
        else:
            epochs = jnp.asarray(epochs, jnp.int32)
        B = int(slots.shape[0])
        if shards is None:
            s = self.n_shards if B == self.cfg.batch_size else 1
        else:
            s = int(shards) if B % max(int(shards), 1) == 0 else 1
        if s > 1:
            _, fold_mesh, _, state_sh = self._plane_for(s)
            self._count("fold", s, (B,) + tuple(centers.shape[1:]))
            # A shard-count switch strands the state on the PREVIOUS
            # active sub-mesh; re-place it (replicated) on the target —
            # a no-op whenever the count is unchanged, one transfer per
            # switch otherwise.
            state = jax.device_put(state, state_sh)
            return fold_mesh(state, slots, centers, cmask, weights,
                             epochs)
        self._count("fold", 1, (B,) + tuple(centers.shape[1:]))
        if self.axes:
            # Same stranding in the other direction: a sharded-plane
            # state dropping to the single-host scatter.
            state = jax.device_put(state,
                                   self.mesh.devices.flatten()[0])
        return server.aggregate_incremental(state, slots, centers, cmask,
                                            weights=weights, epochs=epochs)

    def describe(self) -> dict:
        return {"serve_axes": list(self.axes) if self.axes else None,
                "serve_shards": self.n_shards,
                "chunk_rows": self.chunk_rows,
                "plane_compiles": self.compile_count}
