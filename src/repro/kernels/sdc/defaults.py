"""Single source of truth for SDC kernel launch-shape defaults.

Every un-tuned path reads its launch shapes from here, and the
block-plan autotuner (``launch/autotune.py``) uses this table as its
fallback plan — a kernel signature that has never been tuned runs with
exactly these shapes.

The fused scan's query tile is derived, not fixed: ``scan_block_q``
sizes it from the request's row count (a static shape at trace time) as
the smallest multiple of 8 sublanes that holds the request, capped at
``BLOCK_Q`` rows. An 8-query request scans with an 8-row tile, and a
128-query request with one 128-row tile, so the corpus streams through
VMEM ``ceil(Q / BLOCK_Q)`` times a request. ``FlatSDC`` and the
distributed engine's leaves both use the rule; an explicit ``block_q``
or a tuned scan plan still wins.

``BlockPlan`` lives here (not in ``launch/``) so the kernel layer can
accept plans without importing the launch layer. A plan is a plain
NamedTuple of scalars; ``kind`` selects which knobs apply:

  * ``scan``   — ``block_q``/``block_n`` are the tile shapes of the
    fused scan+top-k kernel (``ops.sdc_search``).
  * ``gather`` — the gather-then-scan kernel's geometry is fixed by the
    index layout (one probed list per grid step against a
    ``GATHER_BLOCK_Q``-row query tile); a plan records provenance but
    pins the defaults.
  * ``rerank`` — the fine rerank gathers each query's survivors into
    one candidate list (``rerank._gathered_lists``) and scores it on the
    gather kernel, so like ``gather`` its geometry is fixed and a plan
    records provenance only.

Roofline constants for the hillclimb cost model (``launch/hillclimb.py``)
live here too, so the tt_retrieval variants and the autotuner price
kernels off one table.
"""

from __future__ import annotations

from typing import NamedTuple


class BlockPlan(NamedTuple):
    """Launch shapes for one kernel kind, plus where they came from.

    ``source`` is provenance only (never part of equality-for-execution):
    "default" (this table), "tuned" (fresh sweep), "cache" (reloaded from
    the tune cache), "inert-backend" (xla — blocks don't reach the
    kernel), "fixed-geometry" (gather, rerank — nothing to sweep).
    """

    kind: str
    block_q: int
    block_n: int
    source: str = "default"

    def blocks(self) -> tuple[int, int]:
        return (self.block_q, self.block_n)


# Canonical scan tiles: MXU-aligned (multiples of (8, 128) f32 / int8
# lanes); TQ=128, TN=512 keeps a (TN, D<=2048) int8 tile under 1 MiB of
# VMEM. The fused top-k kernel historically defaulted to TN=1024 — that
# divergence is gone; anything wanting 1024 now asks the autotuner.
# BLOCK_Q is also the cap of the derived query tile (``scan_block_q``).
BLOCK_Q = 128
BLOCK_N = 512


def scan_block_q(q_rows: int) -> int:
    """The fused scan's query tile for a request of ``q_rows`` queries.

    The smallest multiple of 8 (one f32 sublane group) that holds the
    request, capped at ``BLOCK_Q``: the corpus streams through VMEM once
    per query tile, so fewer, taller tiles save whole corpus passes,
    while a tile taller than the request only pads it.
    """
    return min(-(-max(q_rows, 1) // 8) * 8, BLOCK_Q)


# Gather kernel query tile: one f32 sublane group. Each (query, probe)
# grid step scores its list against the whole tile (the MXU pass costs
# the same for 1 row or 8) and folds only its own row.
GATHER_BLOCK_Q = 8

# Gather kernel list chunk: a probed list is scored GATHER_CHUNK slots at
# a time, bounding VMEM temporaries for long lists (gather.gather_list_len
# pads lists longer than this to whole 128-lane tiles).
GATHER_CHUNK = 1024

DEFAULT_PLANS = {
    "scan": BlockPlan("scan", BLOCK_Q, BLOCK_N, "default"),
    "gather": BlockPlan("gather", 1, 0, "default"),
    "rerank": BlockPlan("rerank", 1, 0, "default"),
}

KERNEL_KINDS = tuple(DEFAULT_PLANS)


def default_plan(kind: str) -> BlockPlan:
    """The fallback plan for a kernel kind (KeyError on unknown kinds)."""
    if kind not in DEFAULT_PLANS:
        raise KeyError(f"unknown kernel kind {kind!r}; want one of {KERNEL_KINDS}")
    return DEFAULT_PLANS[kind]


def plan_for(block_plan, kind: str) -> BlockPlan | None:
    """Select the plan for one kernel kind from a caller-supplied plan.

    The ``*_search_from_snapshot`` entry points accept either a single
    ``BlockPlan`` (applied only where its ``kind`` matches) or a
    ``{kind: BlockPlan}`` mapping (one tuned plan per kernel kind, the
    shape ``launch/autotune`` produces for a whole serving tier).
    Returns None when no plan targets ``kind`` — the defaults then
    apply.
    """
    if block_plan is None:
        return None
    if isinstance(block_plan, BlockPlan):
        return block_plan if block_plan.kind == kind else None
    plan = block_plan.get(kind)
    if plan is not None and plan.kind != kind:
        raise ValueError(f"plan under key {kind!r} has kind {plan.kind!r}")
    return plan


# Roofline constants of one TPU v5e chip (JAX device_kind "TPU v5 lite"),
# from Google Cloud's "TPU v5e" documentation: 393 TOP/s int8 and
# 197 TFLOP/s bf16 on the MXU, 819 GB/s of HBM. The SDC scan is an int8
# matmul, so the hillclimb cost model prices it off the int8 peak.
PEAK_INT8_OPS = 393e12  # ops/s
HBM_BW = 819e9  # bytes/s
LINK_BW = 50e9  # bytes/s per ICI link
N_LINKS = 4
