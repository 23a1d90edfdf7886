"""Pallas kernel static checker (DESIGN.md §15, pass 2).

Every kernel module in ``kernels/`` publishes a ``block_plan`` — the
static BlockSpec/grid/scratch metadata of its ``pallas_call``, computed
by the same padding arithmetic as the dispatch itself. This pass
evaluates those plans across the REGISTERED bucket ladder shapes (the
``StreamConfig`` default rungs x representative serve dims) and gates:

  * ``vmem-overflow`` — the VMEM footprint implied by the plan must fit
    the ``launch.roofline`` ``HW_PROFILES`` per-core VMEM budget.
    Streaming blocks are double-buffered by the Pallas pipeline (x2);
    grid-constant (resident) blocks and scratch are single-buffered;
    scalar-prefetch operands live in SMEM and are counted once.
  * ``lane-misaligned`` / ``sublane-misaligned`` — a dimension that the
    grid PARTITIONS (block extent < array extent) must tile cleanly:
    the minor (lane) axis in multiples of 128, the second-minor
    (sublane) axis in multiples of 8 for 4-byte / 16 for 2-byte
    elements. The TPU compiler refuses anything else, a single-row
    window included: a one-row gather travels as a (rows, 1, d) array,
    whose unit sublane axis the block spans whole. Unpartitioned dims
    only pad, never relayout.
  * ``short-1d-block`` — a 1-D block that partitions its array must
    cover whole XLA layout tiles (1024 elements): XLA lays a 1-D array
    out in T(1024) tiles and the compiler refuses a kernel whose block
    implies a different tile. Per-row vectors travel as (1, n) rows.
  * ``bf16-accum`` — sub-4-byte storage must declare f32 accumulation
    (the ``preferred_element_type`` contract of every matmul kernel
    here); bf16-accumulating reductions drift from the f32 oracles.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from repro.analysis.visitor import Finding

PASS = "kernels"

_ITEMSIZE = {"f32": 4, "i32": 4, "bf16": 2, "f16": 2, "i8": 1}
_LANE = 128
_TILE_1D = 1024   # XLA's layout tile of a 1-D array on the TPU


def _sublane(dtype: str) -> int:
    return 16 if _ITEMSIZE.get(dtype, 4) == 2 else 8


def _prod(xs) -> int:
    out = 1
    for x in xs:
        out *= int(x)
    return out


def footprint_bytes(plan: dict) -> int:
    """VMEM bytes implied by one block plan: 2x each streaming in/out
    block (pipeline double-buffering), 1x resident blocks, scratch, and
    scalar-prefetch operands."""
    total = 0
    for b in plan["blocks"]:
        nbytes = _prod(b["shape"]) * _ITEMSIZE[b["dtype"]]
        streams = (b["kind"] in ("in", "out")) and not b.get("resident")
        total += nbytes * (2 if streams else 1)
    return total


def check_plan(plan: dict, hw: dict, shape_tag: str = "") -> List[Finding]:
    """All checker findings for one kernel block plan against one
    hardware profile (needs ``hw["vmem_bytes"]``)."""
    findings: List[Finding] = []
    where = f"{plan['kernel']}{'[' + shape_tag + ']' if shape_tag else ''}"

    used = footprint_bytes(plan)
    budget = int(hw["vmem_bytes"])
    if used > budget:
        findings.append(Finding(
            PASS, "vmem-overflow", where,
            f"VMEM footprint {used / 2**20:.2f} MiB exceeds the "
            f"{budget / 2**20:.0f} MiB per-core budget (grid "
            f"{plan['grid']}): shrink the block tiles"))

    for b in plan["blocks"]:
        if b["kind"] == "scalar":
            continue
        shape, arr = b["shape"], b["array_shape"]
        if len(shape) == 1:
            if int(shape[0]) < int(arr[0]) and int(shape[0]) % _TILE_1D:
                findings.append(Finding(
                    PASS, "short-1d-block", where,
                    f"1-D block {b['name']}{tuple(shape)} partitions a "
                    f"{tuple(arr)} array in pieces that are not whole "
                    f"{_TILE_1D}-element XLA layout tiles: the TPU "
                    f"compiler refuses it; use a (1, n) row"))
            continue
        lane, sub = int(shape[-1]), int(shape[-2])
        lane_part = lane < int(arr[-1])
        sub_part = sub < int(arr[-2])
        if lane_part and lane % _LANE:
            findings.append(Finding(
                PASS, "lane-misaligned", where,
                f"block {b['name']}{shape} partitions the lane axis at "
                f"{lane}, not a multiple of {_LANE}: the TPU compiler "
                f"refuses partial lane tiles"))
        sl = _sublane(b["dtype"])
        if sub_part and sub % sl:
            findings.append(Finding(
                PASS, "sublane-misaligned", where,
                f"block {b['name']}{shape} partitions the sublane axis "
                f"at {sub}, not a multiple of {sl} for {b['dtype']}: "
                f"the TPU compiler refuses partial sublane tiles"))

    if _ITEMSIZE[plan["storage"]] < 4 and plan["accum"] != "f32":
        findings.append(Finding(
            PASS, "bf16-accum", where,
            f"{plan['storage']} storage with {plan['accum']} "
            f"accumulation: sub-4-byte matmuls must accumulate in f32 "
            f"(preferred_element_type)"))
    return findings


# --------------------------------------------------------------------------
# The registered shape ladder: StreamConfig's default bucket rungs x
# representative serve dims (the CI smoke dims and a production-ish
# wide config), both storage dtypes where the kernel supports them.
# --------------------------------------------------------------------------

# (d, k_prime, k) columns the ladder rungs are crossed with.
DIM_COLUMNS: Tuple[Tuple[int, int, int], ...] = ((64, 4, 16),
                                                 (512, 8, 128))


def ladder() -> Tuple[int, ...]:
    """The registered serve bucket rungs — read from the StreamConfig
    default, so a ladder change re-registers the checker shapes."""
    import dataclasses
    from repro.fed.stream import StreamConfig
    for f in dataclasses.fields(StreamConfig):
        if f.name == "bucket_sizes":
            return tuple(f.default)
    raise AssertionError("StreamConfig.bucket_sizes default not found")


def ladder_plans() -> List[Tuple[str, dict]]:
    """Every (shape_tag, block_plan) the gate evaluates."""
    from repro.fed.stream import StreamConfig
    import dataclasses
    from repro.kernels import (kmeans_update, moe_dispatch, pdist_argmin,
                               solve_attach)
    from repro.kernels.ref import SOLVE_ATTACH_DTYPES

    B = next(f.default for f in dataclasses.fields(StreamConfig)
             if f.name == "batch_size")
    plans: List[Tuple[str, dict]] = []
    for n in ladder():
        for d, kp, k in DIM_COLUMNS:
            for dt in SOLVE_ATTACH_DTYPES:
                plans.append((f"B{B},n{n},d{d},k'{kp},k{k},{dt}",
                              solve_attach.block_plan(B, n, d, kp, k,
                                                      dtype=dt)))
            # the chunked large-k attach path: n rows per chunk against
            # the rung-sized retained center set
            plans.append((f"n4096,d{d},k{n}",
                          pdist_argmin.block_plan(4096, d, n)))
            plans.append((f"n{n * B},d{d},k{k}",
                          kmeans_update.block_plan(n * B, d, k)))
    for d, _, _ in DIM_COLUMNS:
        plans.append((f"T1024,d{d},S2048",
                      moe_dispatch.dispatch_block_plan(1024, d, 2048)))
        plans.append((f"S2048,d{d},T1024",
                      moe_dispatch.combine_block_plan(2048, d, 1024)))
    # The §16 routed-serving dispatch/combine shapes: whole (n_pad * d)
    # requests gather into k * C queue slots (C from the default
    # head_capacity), and (S, d) pooled head outputs combine back to
    # request order with top_k=1 — per bucket rung x dim column.
    from repro.fed.plane import route_capacity
    cap = next(f.default for f in dataclasses.fields(StreamConfig)
               if f.name == "head_capacity")
    for n in ladder():
        for d, kp, k in DIM_COLUMNS:
            C = route_capacity(B, k, cap)
            S = k * C
            plans.append((f"route,B{B},n{n},d{d},k{k},C{C}",
                          moe_dispatch.dispatch_block_plan(B, n * d, S)))
            plans.append((f"route,S{S},d{d},B{B}",
                          moe_dispatch.combine_block_plan(S, d, B,
                                                          top_k=1)))
    # The §17 ingestion-encoder forward: B * n_pad flattened token
    # sequences per step at the default encode_seq_len, through the
    # reduced zoo spec re-dimensioned to each dim column — both storage
    # dtypes (the plan's encode_dtype choices).
    from repro.models import encoder as enc_mod
    sq = next(f.default for f in dataclasses.fields(StreamConfig)
              if f.name == "encode_seq_len")
    for n in (ladder()[0], ladder()[-1]):
        for d, _, _ in DIM_COLUMNS:
            spec = enc_mod.resolve_encoder_spec("qwen1.5-0.5b", d)
            for dt in ("f32", "bf16"):
                plans.append(
                    (f"encode,T{B * n},S{sq},d{d},ff{spec.d_ff},{dt}",
                     enc_mod.block_plan(B * n, sq, d, spec.d_ff,
                                        spec.n_heads, dtype=dt)))
    return plans


def audit_all(hw: Optional[Dict] = None
              ) -> Tuple[List[Finding], int]:
    """(findings, number of plans checked) across the whole ladder."""
    if hw is None or isinstance(hw, str):
        from repro.launch.roofline import hw_profile
        hw = hw_profile(hw)
    findings: List[Finding] = []
    plans = ladder_plans()
    for tag, plan in plans:
        findings.extend(check_plan(plan, hw, tag))
    return findings, len(plans)
