"""Jaxpr determinism auditor (DESIGN.md §15, pass 1).

The repo's replay guarantees (bitwise labels / tau versions / fold
state / drift decisions, DESIGN.md §9-§14) are runtime-tested at a few
shapes; this pass certifies them STRUCTURALLY on every CI run by
tracing the real serving artifacts — the serve step, the §16 routed
personalization step (label -> dispatch -> per-cluster head ->
combine; its routing scatters are int/bool overwrites onto unique
slots, which is exactly what this pass proves stays true), the §17
encode+serve step (the zoo encoder fused ahead of the label body), the
fold, the finalize, and the drift split/retire refresh, via the same
``ServePlane`` construction the service runs — and walking their
jaxprs with the shared :mod:`analysis.visitor` engine.

Rule catalog (ids are what ``# repro: allow(...)`` and the JSON report
use; determinism findings are suppressed by artifact CONTRACT, never
by comment — a hazard in a traced artifact has no source line):

  * ``float-scatter-add`` — an accumulating scatter (scatter-add /
    scatter-mul) on float data whose indices are not provably
    duplicate-free. XLA applies colliding scatter updates in
    implementation-defined order, so float accumulation over data-
    derived indices (labels, slots) is a replay hazard. Indices whose
    backward slice is pure iota/literal (an arange) are statically
    unique and pass; so does ``unique_indices=True`` (the caller's
    explicit promise).
  * ``implicit-rng`` — ``rng_uniform`` / ``rng_bit_generator``: XLA's
    stateful or backend-defined RNG, not reproducible across backends
    or replays. All randomness must thread explicit PRNG keys.
  * ``rng-unthreaded-key`` — a keyed RNG primitive (threefry,
    random_bits, ...) whose key derives only from baked-in constants,
    never from the artifact's inputs: every trace re-uses the same
    stream, silently correlating what should be per-request keys.
  * ``unordered-collective`` — a float cross-replica reduction (psum /
    psum_scatter): FP addition is non-associative and the replica
    reduction order is unspecified. Integer psum and idempotent
    pmax/pmin are exact; all_gather/ppermute/all_to_all move data in
    fixed order and are allowed per contract.
  * ``contract-collective`` — a collective outside the artifact's
    allowlist (the serve step is embarrassingly parallel: NONE; the
    sharded fold transports reports with all_gather ONLY).
  * ``fold-single-scatter`` — the §11 invariant, structurally: the
    fold jaxpr contains EXACTLY one overwrite scatter per
    ``ServerState`` leaf, all in drop mode (out-of-capacity slots
    ignored, never clipped onto a live slot), all indexed by the same
    slot vector, and no accumulating scatter anywhere. The sharded
    fold must satisfy the identical contract inside its shard_map
    body. A second scatter, a scatter-add, a clip-mode scatter, or a
    diverging index source each violate it.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.analysis.visitor import (Finding, backward_slice, iter_eqns,
                                    statically_unique_indices)

PASS = "determinism"

# Accumulating scatters: colliding updates combine in impl-defined
# order. scatter-min/max are idempotent+commutative — exact — and the
# overwrite "scatter" is covered by the fold contract instead.
ACCUM_SCATTERS = ("scatter-add", "scatter-mul")
OVERWRITE_SCATTER = "scatter"
IMPLICIT_RNG = ("rng_uniform", "rng_bit_generator")
KEYED_RNG = ("threefry2x32", "random_seed", "random_wrap", "random_bits",
             "random_fold_in", "random_gamma", "random_split")
UNORDERED_FLOAT_REDUCE = ("psum", "psum_scatter", "reduce_scatter")
COLLECTIVE_PRIMS = ("psum", "psum_scatter", "reduce_scatter", "pmax",
                    "pmin", "all_gather", "all_to_all", "ppermute",
                    "pbroadcast")


@dataclass(frozen=True)
class Contract:
    """Per-artifact allowances: which collectives may appear, and (for
    fold artifacts) the exact overwrite-scatter census the §11
    invariant demands (= the number of ``ServerState`` leaves)."""
    allow_collectives: frozenset = frozenset()
    fold_leaves: Optional[int] = None


def _is_float(var) -> bool:
    return jnp.issubdtype(var.aval.dtype, jnp.floating)


def audit_jaxpr(closed_jaxpr, artifact: str,
                contract: Contract = Contract()) -> List[Finding]:
    """All determinism findings of one traced artifact."""
    findings: List[Finding] = []
    sites = iter_eqns(closed_jaxpr, branches="all")

    def emit(rule, site, msg):
        findings.append(Finding(PASS, rule,
                                f"{artifact}:{site.path_str}", msg))

    scatter_sites = []
    for site in sites:
        eqn = site.eqn
        name = eqn.primitive.name
        if name in ACCUM_SCATTERS:
            scatter_sites.append(site)
            if not any(_is_float(v) for v in eqn.outvars):
                continue                       # integer accumulation: exact
            if eqn.params.get("unique_indices"):
                continue                       # caller-promised unique
            if statically_unique_indices(site.jaxpr, eqn.invars[1]):
                continue                       # iota-derived: provably unique
            emit("float-scatter-add", site,
                 f"{name} on {eqn.outvars[0].aval.dtype} with possibly-"
                 f"overlapping data-derived indices: XLA applies "
                 f"colliding updates in implementation-defined order")
        elif name == OVERWRITE_SCATTER:
            scatter_sites.append(site)
        elif name in IMPLICIT_RNG:
            emit("implicit-rng", site,
                 f"{name} uses XLA's stateful/backend-defined RNG; "
                 f"thread an explicit PRNG key instead")
        elif name in KEYED_RNG:
            reaches = any(backward_slice(site.jaxpr, v).reaches_input
                          for v in eqn.invars)
            if not reaches:
                emit("rng-unthreaded-key", site,
                     f"{name} key derives only from baked-in constants "
                     f"— every invocation replays the same stream; "
                     f"thread the key through the artifact's inputs")
        if name in COLLECTIVE_PRIMS:
            if name not in contract.allow_collectives:
                emit("contract-collective", site,
                     f"collective {name} is outside this artifact's "
                     f"allowlist {sorted(contract.allow_collectives)}")
            if name in UNORDERED_FLOAT_REDUCE and \
                    any(_is_float(v) for v in eqn.outvars):
                # An allowlisted float reduce stays VISIBLE in the
                # report but does not gate — the contract author has
                # accepted its reduction-order semantics.
                findings.append(Finding(
                    PASS, "unordered-collective",
                    f"{artifact}:{site.path_str}",
                    f"float {name}: cross-replica FP reduction order "
                    f"is unspecified (non-associative)",
                    suppressed=name in contract.allow_collectives))

    if contract.fold_leaves is not None:
        findings.extend(_check_fold_contract(artifact, contract,
                                             scatter_sites))
    return findings


def _check_fold_contract(artifact, contract, scatter_sites):
    """The ``fold-single-scatter`` structural assertion."""
    out: List[Finding] = []
    want = contract.fold_leaves

    def emit(site_or_none, msg):
        where = (f"{artifact}:{site_or_none.path_str}"
                 if site_or_none is not None else artifact)
        out.append(Finding(PASS, "fold-single-scatter", where, msg))

    overwrite = [s for s in scatter_sites
                 if s.eqn.primitive.name == OVERWRITE_SCATTER]
    accum = [s for s in scatter_sites
             if s.eqn.primitive.name in ACCUM_SCATTERS]
    for s in accum:
        emit(s, f"accumulating {s.eqn.primitive.name} on the fold path "
                f"— the fold must be pure overwrite scatters")
    if len(overwrite) != want:
        emit(None, f"fold contains {len(overwrite)} overwrite scatters, "
                   f"expected exactly {want} (one per ServerState leaf)")
        return out

    # All scatters must drop out-of-range slots (mode="drop"): a
    # clipping scatter would corrupt the last live slot instead.
    for s in overwrite:
        mode = str(s.eqn.params.get("mode"))
        if "FILL_OR_DROP" not in mode:
            emit(s, f"fold scatter mode is {mode}, expected "
                    f"FILL_OR_DROP (out-of-capacity ids must drop)")

    # ... and must all consume the SAME slot vector: one admission
    # decision drives every leaf. Diverging index provenance means two
    # leaves could disagree about which slot a report landed in.
    by_level: Dict[int, list] = {}
    for s in overwrite:
        by_level.setdefault(id(s.jaxpr), []).append(s)
    if len(by_level) != 1:
        emit(None, "fold scatters span multiple jaxpr scopes — the "
                   "fold must be one primitive at one level")
        return out
    sources = []
    for s in overwrite:
        sl = backward_slice(s.jaxpr, s.eqn.invars[1])
        sources.append(frozenset(sl.invar_positions))
    if not sources[0] or any(src != sources[0] for src in sources):
        emit(None, f"fold scatter index provenance diverges across "
                   f"leaves ({sorted(map(sorted, sources))}) — all "
                   f"leaves must scatter by the same slot vector")
    return out


# --------------------------------------------------------------------------
# The real artifacts, traced at CI smoke shapes via the same
# ServePlane/StreamConfig construction the service runs.
# --------------------------------------------------------------------------

SMOKE = dict(k=16, k_prime=4, d=32, capacity=64, batch_size=8, n=64,
             drift_half_life=8, heads="qwen1.5-0.5b", head_arch="ffn",
             encoder="qwen1.5-0.5b", encode_seq_len=16)


@dataclass
class Artifact:
    name: str
    closed_jaxpr: object
    contract: Contract


def _smoke_cfg(heads: bool = False, encoder: bool = False):
    from repro.fed.stream import StreamConfig
    kw = ({"heads": SMOKE["heads"], "head_arch": SMOKE["head_arch"]}
          if heads else {})
    if encoder:
        kw.update(encoder=SMOKE["encoder"],
                  encode_seq_len=SMOKE["encode_seq_len"])
    return StreamConfig(k=SMOKE["k"], k_prime=SMOKE["k_prime"],
                        d=SMOKE["d"], capacity=SMOKE["capacity"],
                        batch_size=SMOKE["batch_size"],
                        bucket_sizes=(SMOKE["n"],), **kw)


def _heads_struct(cfg):
    """Abstract (shape/dtype) stacked head params for tracing the
    routed step without materializing an init."""
    from repro.models import heads as heads_mod
    return jax.eval_shape(lambda: heads_mod.init_heads(
        jax.random.PRNGKey(0), cfg.k, cfg.head_spec()))


def _encoder_struct(cfg):
    """Abstract (shape/dtype) encoder params for tracing the §17
    encode step without materializing an init."""
    from repro.models import encoder as enc_mod
    return jax.eval_shape(lambda: enc_mod.init_encoder(
        jax.random.PRNGKey(0), cfg.encoder_spec()))


def _encode_args(cfg):
    """The (B, n, seq, d) raw-sequence batch + token mask the encode
    step prepends to the plain step arguments."""
    S = jax.ShapeDtypeStruct
    B, n, sq = cfg.batch_size, SMOKE["n"], cfg.encode_seq_len
    return (S((B, n, sq, cfg.d), jnp.float32),       # token sequences
            S((B, n, sq), jnp.bool_))                # token mask


def _step_args(cfg):
    S = jax.ShapeDtypeStruct
    B, n = cfg.batch_size, SMOKE["n"]
    return (S((cfg.k, cfg.d), jnp.float32),          # tau
            S((B, 2), jnp.uint32),                   # per-request keys
            S((B, n, cfg.d), jnp.float32),           # data
            S((B, n), jnp.bool_),                    # point mask
            S((B,), jnp.int32))                      # k_valid


def _state_struct(cfg):
    from repro.core import server
    S = jax.ShapeDtypeStruct
    cap, kp, d = cfg.capacity, cfg.k_prime, cfg.d
    return server.ServerState(S((cap, kp, d), jnp.float32),
                              S((cap, kp), jnp.bool_),
                              S((cap, kp), jnp.float32),
                              S((cap,), jnp.bool_),
                              S((cap,), jnp.int32))


def _fold_args(cfg):
    S = jax.ShapeDtypeStruct
    B, kp, d = cfg.batch_size, cfg.k_prime, cfg.d
    return (_state_struct(cfg),
            S((B,), jnp.int32),                      # slots
            S((B, kp, d), jnp.float32),              # centers
            S((B, kp), jnp.bool_),                   # center mask
            S((B, kp), jnp.float32),                 # weights
            S((B,), jnp.int32))                      # epochs


def n_fold_leaves() -> int:
    from repro.core import server
    return len(server.ServerState._fields)


def trace_artifacts(include_sharded: Optional[bool] = None
                    ) -> Tuple[List[Artifact], List[str]]:
    """(artifacts, skipped-names). ``include_sharded=None`` auto-detects
    from ``jax.device_count()`` — the CI static-analysis job forces 8
    host devices so the shard_mapped serve/fold contracts are audited
    structurally, not just on the mesh test legs."""
    from repro.core import server
    from repro.fed import plane as plane_mod

    cfg = _smoke_cfg()
    leaves = n_fold_leaves()
    arts: List[Artifact] = []
    skipped: List[str] = []

    step = plane_mod._serve_step(cfg)
    arts.append(Artifact(
        "serve_step", jax.make_jaxpr(step)(*_step_args(cfg)), Contract()))

    # The §16 routed personalization step: same label body + routing
    # scatters + per-cluster head forwards. Single-host: no collectives
    # allowed; the audit also proves every routing scatter is an
    # int/bool overwrite (an accumulating float scatter here would be
    # a replay hazard).
    hcfg = _smoke_cfg(heads=True)
    routed = plane_mod._serve_step(hcfg)
    tau_s, keys_s, data_s, pmask_s, kv_s = _step_args(hcfg)
    routed_args = ((tau_s, None, _heads_struct(hcfg)), keys_s, data_s,
                   pmask_s, kv_s)
    arts.append(Artifact(
        "routed_step", jax.make_jaxpr(routed)(*routed_args), Contract()))

    # The §17 encode+serve step: the zoo encoder forward fused ahead of
    # the label body. Encoding is pure matmul/softmax on its inputs —
    # no RNG, no scatters, no collectives — so the artifact's contract
    # is the plain serve step's (the solve's keyed RNG still threads
    # from the request keys).
    ecfg = _smoke_cfg(encoder=True)
    enc_step = plane_mod._serve_step(ecfg)
    tau_e, keys_e, _, pmask_e, kv_e = _step_args(ecfg)
    data_e, tmask_e = _encode_args(ecfg)
    enc_args = ((tau_e, _encoder_struct(ecfg), None), keys_e, data_e,
                pmask_e, kv_e, tmask_e)
    arts.append(Artifact(
        "encode_step", jax.make_jaxpr(enc_step)(*enc_args), Contract()))

    def fold(state, slots, centers, cmask, weights, epochs):
        return server.aggregate_incremental(state, slots, centers, cmask,
                                            weights=weights, epochs=epochs)

    arts.append(Artifact(
        "fold", jax.make_jaxpr(fold)(*_fold_args(cfg)),
        Contract(fold_leaves=leaves)))

    def finalize(state):
        return server.finalize(state, cfg.k,
                               weighted=cfg.weight_by_core_counts)

    arts.append(Artifact(
        "finalize", jax.make_jaxpr(finalize)(_state_struct(cfg)),
        Contract()))

    def refresh_split_retire(state, now_epoch):
        # The drift="split_merge" refresh, composed exactly as
        # AttachService._refinalize does at a flush boundary.
        decay = (now_epoch, SMOKE["drift_half_life"])
        agg = server.finalize(state, cfg.k, decay=decay)
        mask, w = server.decayed_evidence(state, *decay)
        mass = server.center_mass(agg, mask, w)
        flat = jnp.where(mask[..., None], state.centers,
                         jnp.zeros_like(state.centers)
                         ).reshape(-1, cfg.d).astype(jnp.float32)
        return server.split_retire(
            flat, mask.reshape(-1), agg, mass, cfg.k,
            split_factor=2.0, retire_frac=0.1, max_moves=1,
            weights=w.reshape(-1))

    arts.append(Artifact(
        "split_retire",
        jax.make_jaxpr(refresh_split_retire)(
            _state_struct(cfg), jax.ShapeDtypeStruct((), jnp.int32)),
        Contract()))

    ndev = jax.device_count()
    if include_sharded is None:
        include_sharded = ndev > 1
    if include_sharded and ndev > 1:
        from repro.utils.compat import make_mesh
        s = ndev if cfg.batch_size % ndev == 0 else 2
        mesh = make_mesh((s,), ("data",))
        plane = plane_mod.ServePlane(cfg, mesh=mesh, serve_axes=("data",))
        step_sh, fold_sh = plane._plane_for(s)[:2]
        arts.append(Artifact(
            "serve_step_sharded",
            jax.make_jaxpr(step_sh)(*_step_args(cfg)), Contract()))
        arts.append(Artifact(
            "fold_sharded",
            jax.make_jaxpr(fold_sh)(*_fold_args(cfg)),
            Contract(allow_collectives=frozenset({"all_gather"}),
                     fold_leaves=leaves)))
        plane_h = plane_mod.ServePlane(hcfg, mesh=mesh,
                                       serve_axes=("data",))
        routed_sh = plane_h._plane_for(s)[0]
        # Sharded: the global keep/overflow ranking all_gathers the
        # int32 cluster votes (deterministic shard-order tiling) —
        # exactly the fold's collective allowance, nothing else.
        arts.append(Artifact(
            "routed_step_sharded",
            jax.make_jaxpr(routed_sh)(*routed_args),
            Contract(allow_collectives=frozenset({"all_gather"}))))
        # Sharded §17 encode+serve: the batch axis stays embarrassingly
        # parallel through the encode stage (encoder params replicated
        # like tau), so no collective is allowed here either.
        plane_e = plane_mod.ServePlane(ecfg, mesh=mesh,
                                       serve_axes=("data",))
        enc_sh = plane_e._plane_for(s)[0]
        arts.append(Artifact(
            "encode_step_sharded",
            jax.make_jaxpr(enc_sh)(*enc_args), Contract()))
    else:
        skipped.extend(["serve_step_sharded", "fold_sharded",
                        "routed_step_sharded", "encode_step_sharded"])
    return arts, skipped


def audit_all(include_sharded: Optional[bool] = None
              ) -> Tuple[List[Finding], List[str], List[str]]:
    """(findings, audited artifact names, skipped artifact names)."""
    arts, skipped = trace_artifacts(include_sharded)
    findings: List[Finding] = []
    for a in arts:
        findings.extend(audit_jaxpr(a.closed_jaxpr, a.name, a.contract))
    return findings, [a.name for a in arts], skipped
