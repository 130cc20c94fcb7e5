"""Explicit VMEM budget model for the fused EGNN edge kernels.

The fused forward/backward kernels (``kernel.py``) are H-blocked: a
``block_h`` grid dimension tiles the φ_e *inner* hidden axis (fc0's output
columns == fc1's contraction rows), so every (H, H) weight tile, the f32
weight-grad scratches, and the per-step dense intermediates are bounded by
``block_h · H`` bytes instead of ``H²``. What still scales with full H is
only the *node-sided* state (``A·H`` features/accumulators and ``block_e·H``
edge rows) — small for this workload's padded-structure batches.

This module is the single source of truth for what fits: an itemized,
unit-tested byte model of the resident set (``fwd_vmem_items`` /
``bwd_vmem_items``), a planner (``plan_blocks``) that NEVER emits an
over-budget ``(block_e, block_h)``, and a validator (``check_blocks``) that
raises ``VmemBudgetError`` on over-budget explicit overrides instead of
letting them silently compile and OOM on device.

Accounting rules (deliberately conservative):

  * every ``pallas_call`` input/output block counts TWICE — the Mosaic
    pipeline double-buffers block DMA;
  * scratch (``pltpu.VMEM``) counts once;
  * the large *live* jnp intermediates of one kernel step (gathered edge
    rows, the masked cotangent gather, the per-block dense products) are
    itemized too — Mosaic keeps them in VMEM between ops;
  * f32 unless the buffer holds compute-dtype values (``itemsize``).

The default budget is 16 MiB/core of physical VMEM minus 4 MiB headroom
for Mosaic spills, semaphores, and accounting slack (``VMEM_BUDGET``).
``tests/test_egnn_budget.py`` pins the model: planned configs are within
budget at paper widths (H ∈ {256, 512, 866}, A ∈ {64, 128}) and
over-budget overrides raise.
"""
from __future__ import annotations

from repro.kernels.segment_sum.kernel import lane_tiles

VMEM_BYTES = 16 << 20          # physical VMEM per TPU core
VMEM_HEADROOM = 4 << 20        # Mosaic spills / semaphores / model slack
VMEM_BUDGET = VMEM_BYTES - VMEM_HEADROOM


class VmemBudgetError(ValueError):
    """An explicit (block_e, block_h) override exceeds the VMEM budget."""


def _clamp(block, dim):
    return max(1, min(block, dim))


def fwd_vmem_items(A: int, block_e: int, block_h: int, H: int, *,
                   itemsize: int = 4) -> dict:
    """Itemized resident bytes of one forward kernel step (grid (B, ne, nh)).

    ``itemsize`` is the compute dtype's width (4 = f32, 2 = bf16); masks,
    indices, positions, and every accumulator stay f32/int32."""
    be, bh = _clamp(block_e, 10 ** 9), _clamp(block_h, H)
    return {
        # --- double-buffered input blocks (×2) -----------------------------
        "in.src_dst": 2 * 2 * be * 4,
        "in.h": 2 * A * H * itemsize,
        "in.pos": 2 * A * 3 * 4,
        "in.w0_blocks": 2 * 2 * H * bh * itemsize,       # w0i + w0j (H, bh)
        "in.w0d_b0": 2 * 2 * bh * itemsize,              # (1, bh) rows
        "in.w1_block": 2 * bh * H * itemsize,            # (bh, H)
        "in.b1": 2 * H * itemsize,
        # --- double-buffered output block (×2) -----------------------------
        "out.o": 2 * A * H * itemsize,
        # --- scratch (×1) --------------------------------------------------
        "scratch.m_acc": be * H * 4,                     # f32 message row acc
        "scratch.node_acc": A * H * 4,                   # f32 (A, H)
        # --- live step intermediates --------------------------------------
        "live.hi_hj": 2 * be * H * itemsize,             # gathered endpoints
        "live.xi_xj_diff": 3 * be * 3 * 4,
        "live.z_silu": 2 * be * bh * itemsize,           # z_j + silu(z_j)
        "live.partial_m": be * H * 4,                    # (silu @ w1_blk) f32
    }


def bwd_vmem_items(A: int, block_e: int, block_h: int, H: int, *,
                   itemsize: int = 4) -> dict:
    """Itemized resident bytes of one backward kernel step (grid
    (B, nh, ne)). The weight-grad accumulators are PER-BLOCK (H·bh f32),
    flushed at the end of each (b, h-block) edge sweep — the old whole-H
    (H, H) scratches are exactly what this model exists to forbid."""
    be, bh = _clamp(block_e, 10 ** 9), _clamp(block_h, H)
    return {
        # --- double-buffered input blocks (×2) -----------------------------
        "in.src_dst": 2 * 2 * be * 4,
        "in.h": 2 * A * H * itemsize,
        "in.g": 2 * A * H * 4,                           # upstream cotangent
        "in.pos": 2 * A * 3 * 4,
        "in.w0_blocks": 2 * 2 * H * bh * itemsize,
        "in.w0d_b0": 2 * 2 * bh * itemsize,
        "in.w1_block": 2 * bh * H * itemsize,
        # --- double-buffered output blocks (×2) ----------------------------
        "out.dh": 2 * A * H * itemsize,
        "out.dpos": 2 * A * 3 * 4,
        "out.dw0_blocks": 2 * 2 * H * bh * 4,            # per-(b, j) partials
        "out.dw1_block": 2 * bh * H * 4,
        "out.rows": 2 * (2 * bh + H) * 4,                # dw0d, db0, db1
        # --- scratch (×1) --------------------------------------------------
        "scratch.node_acc": A * (H + 3) * 4,             # acc_dh + acc_dpos
        "scratch.w0_grad": 2 * H * bh * 4,               # acc_w0i + acc_w0j
        "scratch.w1_grad": bh * H * 4,
        "scratch.rows": (2 * bh + H) * 4,
        # --- live step intermediates --------------------------------------
        "live.hi_hj": 2 * be * H * itemsize,
        "live.xi_xj_diff": 3 * be * 3 * 4,
        "live.dm": be * H * 4,                           # masked g gather
        "live.dhi_dhj": 2 * be * H * 4,                  # dz_j @ w0ᵀ rows
        "live.z_chain": 4 * be * bh * 4,                 # z/s/ds/dz f32
    }


def vmem_bytes(A: int, block_e: int, block_h: int, H: int, *,
               itemsize: int = 4) -> int:
    """Worst-direction resident bytes — the custom_vjp pins ONE
    (block_e, block_h) into both directions, so the plan must satisfy the
    larger (backward) set."""
    kw = dict(itemsize=itemsize)
    return max(sum(fwd_vmem_items(A, block_e, block_h, H, **kw).values()),
               sum(bwd_vmem_items(A, block_e, block_h, H, **kw).values()))


def check_blocks(A: int, E: int, H: int, block_e: int, block_h: int, *,
                 itemsize: int = 4, vmem_limit: int = VMEM_BUDGET) -> None:
    """Raise ``VmemBudgetError`` if an explicit (block_e, block_h) override
    exceeds the budget — never let an over-budget config silently compile."""
    be, bh = _clamp(block_e, E), _clamp(block_h, H)
    need = vmem_bytes(A, be, bh, H, itemsize=itemsize)
    if need > vmem_limit:
        raise VmemBudgetError(
            f"egnn_edge block override (block_e={block_e}, block_h={block_h}) "
            f"needs ≈{need / 2 ** 20:.1f} MiB of VMEM at (A={A}, E={E}, "
            f"H={H}, itemsize={itemsize}) — over the {vmem_limit / 2 ** 20:.1f}"
            f" MiB budget. Shrink the blocks (plan_blocks(A, E, H) suggests "
            f"{plan_blocks(A, E, H, itemsize=itemsize, vmem_limit=vmem_limit)}"
            f") or raise vmem_limit if the target core really has more VMEM.")


def plan_blocks(A: int, E: int, H: int, *, itemsize: int = 4,
                vmem_limit: int = VMEM_BUDGET) -> tuple[int, int]:
    """Plan ``(block_e, block_h)`` for the fused kernels: start from the
    MXU-native 256-wide tiles (clamped to the problem) and shrink —
    ``block_h`` first, since the ``block_h·H`` weight tiles dominate at
    paper widths — through the lane-aligned sizes the TPU compiler accepts
    (``lane_tiles``: the whole axis, or a multiple of 128) until the
    modeled resident set fits. Never returns an over-budget config; raises
    ``VmemBudgetError`` if even the smallest aligned pair does not fit
    (then the problem needs an A/H split this kernel doesn't have)."""
    es, hs = lane_tiles(E), lane_tiles(H)
    ie = ih = 0
    while vmem_bytes(A, es[ie], hs[ih], H, itemsize=itemsize) > vmem_limit:
        if ih + 1 < len(hs) and (hs[ih] >= es[ie] or ie + 1 == len(es)):
            ih += 1
        elif ie + 1 < len(es):
            ie += 1
        else:
            raise VmemBudgetError(
                f"no (block_e, block_h) fits (A={A}, E={E}, H={H}, "
                f"itemsize={itemsize}) in {vmem_limit / 2 ** 20:.1f} MiB — "
                f"the A·H node state alone exceeds the budget; this shape "
                f"needs a node-dimension split.")
    return es[ie], hs[ih]
