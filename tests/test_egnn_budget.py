"""Unit tests for the fused-kernel VMEM budget model
(``repro.kernels.egnn_edge.budget``).

The model is the single source of truth for what the H-blocked kernels may
hold resident: the planner must never emit an over-budget
``(block_e, block_h)`` — at the paper widths H ∈ {256, 512, 866} and
A ∈ {64, 128} in particular — and over-budget explicit overrides must
raise instead of silently compiling.
"""
import pytest

from repro.kernels.egnn_edge import budget, ops as edge_ops
from repro.kernels.egnn_edge.budget import (VMEM_BUDGET, VmemBudgetError,
                                            bwd_vmem_items, check_blocks,
                                            fwd_vmem_items, plan_blocks,
                                            vmem_bytes)

PAPER_E = 768


@pytest.mark.parametrize("H", [256, 512, 866])
@pytest.mark.parametrize("A", [64, 128])
def test_planned_blocks_always_within_budget(H, A):
    """The acceptance grid: every planned config fits the documented
    budget, blocks are positive and problem-clamped."""
    be, bh = plan_blocks(A, PAPER_E, H)
    assert 8 <= be <= PAPER_E and 8 <= bh <= H
    assert vmem_bytes(A, be, bh, H) <= VMEM_BUDGET


def test_paper_width_requires_h_split():
    """At the paper width the whole-H config is over budget (the ROADMAP
    gap this PR closes) and the planner responds by splitting H."""
    A, H = 128, 866
    assert vmem_bytes(A, 256, H, H) > VMEM_BUDGET    # whole-H does NOT fit
    be, bh = plan_blocks(A, PAPER_E, H)
    assert bh < H
    assert vmem_bytes(A, be, bh, H) <= VMEM_BUDGET


def test_model_is_monotone_in_blocks_and_h():
    """Sanity on the byte model itself: more block, more bytes."""
    base = vmem_bytes(128, 128, 128, 512)
    assert vmem_bytes(128, 256, 128, 512) > base
    assert vmem_bytes(128, 128, 256, 512) > base
    assert vmem_bytes(128, 128, 128, 866) > base
    # bf16 compute shrinks the compute-dtype tiles
    assert vmem_bytes(128, 128, 128, 512, itemsize=2) < base


def test_itemized_model_covers_both_directions():
    """The backward resident set dominates (it is what vmem_bytes budgets),
    and every item is a positive byte count."""
    fwd = fwd_vmem_items(128, 128, 128, 866)
    bwd = bwd_vmem_items(128, 128, 128, 866)
    assert all(v > 0 for v in fwd.values())
    assert all(v > 0 for v in bwd.values())
    assert sum(bwd.values()) > sum(fwd.values())
    assert vmem_bytes(128, 128, 128, 866) == sum(bwd.values())


def test_over_budget_override_raises_with_guidance():
    """Explicit whole-H blocks at the paper width must raise a clear
    error naming the shape, the overage, and a fitting plan — not compile."""
    with pytest.raises(VmemBudgetError, match="block_e=256, block_h=866"):
        check_blocks(128, PAPER_E, 866, 256, 866)
    with pytest.raises(VmemBudgetError, match="plan_blocks"):
        check_blocks(128, PAPER_E, 866, 256, 866)
    # within budget: no raise
    check_blocks(128, PAPER_E, 866, *plan_blocks(128, PAPER_E, 866))


def test_over_budget_override_raises_through_public_entry():
    """The validation is wired into egnn_edge_agg itself — an over-budget
    cfg override fails fast at call time, before any pallas_call."""
    import jax, jax.numpy as jnp
    from repro.models.mlp import mlp_init
    B, E, A, H = 1, 16, 8, 866
    h = jnp.zeros((B, A, H))
    pos = jnp.zeros((B, A, 3))
    src = dst = jnp.zeros((B, E), jnp.int32)
    em = jnp.ones((B, E), bool)
    phi_e = mlp_init(jax.random.PRNGKey(0), 2 * H + 1, H, H, 1, jnp.float32)
    with pytest.raises(VmemBudgetError):
        edge_ops.egnn_edge_agg(h, pos, src, dst, em, phi_e,
                               block_e=16, block_h=866)


def test_partial_override_is_validated_as_a_mix():
    """Overriding only one knob re-validates the (override, planned) pair."""
    be, bh = edge_ops._resolve_blocks(None, 64, 128, PAPER_E, 866)
    assert bh == 64 and vmem_bytes(128, be, bh, 866) <= VMEM_BUDGET


def test_planner_raises_when_nothing_fits():
    """A node state too large for any (block_e, block_h) raises instead of
    looping or emitting a bogus config."""
    with pytest.raises(VmemBudgetError, match="node-dimension"):
        plan_blocks(4096, PAPER_E, 8192, vmem_limit=1 << 20)


def test_segment_sum_autotune_never_over_budget():
    """The shared segment-sum heuristic also respects its budget at wide F
    (it used to stall at block_e=8 and sail past): the emitted config's
    resident set fits the limit it was given, and its tiles are ones the
    TPU compiler accepts (block_e a multiple of 128 or all of E, block_n a
    multiple of 8). Where no such tile fits it raises instead."""
    from repro.kernels.segment_sum.kernel import autotune_blocks
    for F in (256, 512, 866, 4096):
        for A in (64, 128, 1024):
            limit = 2 << 20
            try:
                bn, be = autotune_blocks(A, PAPER_E, F, vmem_limit=limit)
            except ValueError:
                # only the widest F leaves no aligned tile inside 2 MiB
                assert F == 4096, (A, F)
                continue
            assert bn % 8 == 0 and (be % 128 == 0 or be == PAPER_E)
            assert 4 * (bn * F + be * F + be * bn) <= limit, (A, F, bn, be)
