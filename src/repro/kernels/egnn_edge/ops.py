"""Public entry for the fused EGNN edge kernel, forward and backward.

``egnn_edge_agg`` runs the fused Pallas forward (one kernel for gather ->
d² -> φ_e -> masked segment-sum) and carries a ``jax.custom_vjp`` whose
backward is the fused Pallas backward kernel (``kernel.egnn_edge_fused_bwd``):
it recomputes the edge-major residuals tile-by-tile from the saved INPUTS
(h, pos, src, dst, edge_mask) and emits d_h / d_x / φ_e weight cotangents
without materializing the (B, E, 2H+1) concat or the (B, E, H) message
tensor in HBM — so ``impl="fused"`` trains with the same memory profile it
infers with. The pure-jnp reference (``ref.py``) remains the parity oracle
for both directions (tests/test_hotpath.py, tests/test_egnn_paper_shape.py).

Block planning: every call resolves ``(block_e, block_h)`` against the
itemized VMEM budget model in ``budget.py`` — ``None`` means "plan it"
(``plan_blocks`` never emits an over-budget config, which is what lets the
fused path run at the paper width H=866), and explicit overrides are
validated (``VmemBudgetError`` instead of silently compiling a config that
cannot fit a TPU core's VMEM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .budget import check_blocks, plan_blocks
from .kernel import egnn_edge_fused, egnn_edge_fused_bwd


def _split_phi_e(phi_e, H, cd):
    """fc0 weight (2H+1, H) -> its h_i / h_j / d² row blocks (biases to
    (1, H) rows for lane-aligned VMEM tiles)."""
    w0 = phi_e["fc0"]["w"].astype(cd)
    assert w0.shape[0] == 2 * H + 1, \
        f"phi_e fc0 expects (2H+1, H)={2 * H + 1}, got {w0.shape}"
    return (w0[:H], w0[H:2 * H], w0[2 * H:],
            phi_e["fc0"]["b"].astype(cd)[None, :],
            phi_e["fc1"]["w"].astype(cd),
            phi_e["fc1"]["b"].astype(cd)[None, :])


def _resolve_blocks(block_e, block_h, A, E, H):
    """Plan-or-validate ``(block_e, block_h)`` against the VMEM budget
    model. The resolved pair is pinned into the custom_vjp static for BOTH
    directions, so the model's worst-direction (backward) resident set is
    what gets budgeted (``budget.vmem_bytes``). Explicit overrides that
    exceed the budget raise ``VmemBudgetError`` — never silently compile."""
    if block_e and block_h:
        check_blocks(A, E, H, block_e, block_h)
        return block_e, block_h
    pe, ph = plan_blocks(A, E, H)
    be, bh = block_e or pe, block_h or ph
    if block_e or block_h:          # one side overridden: re-validate the mix
        check_blocks(A, E, H, be, bh)
    return be, bh


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _edge_agg(static, h, pos, src, dst, edge_mask, phi_e):
    compute_dtype, block_e, block_h, interpret = static
    cd = compute_dtype or h.dtype
    H = h.shape[-1]
    A = h.shape[1]
    w0i, w0j, w0d, b0, w1, b1 = _split_phi_e(phi_e, H, cd)
    # masked edges -> sentinel A (excluded from the membership tile)
    sr = jnp.where(edge_mask, src, A)
    dr = jnp.where(edge_mask, dst, A)
    return egnn_edge_fused(h.astype(cd), pos, sr, dr,
                           w0i, w0j, w0d, b0, w1, b1,
                           block_e=block_e, block_h=block_h,
                           interpret=interpret)


def _edge_agg_fwd(static, h, pos, src, dst, edge_mask, phi_e):
    out = _edge_agg(static, h, pos, src, dst, edge_mask, phi_e)
    # residuals are the primal INPUTS only — every edge-major intermediate
    # is recomputed inside the backward kernel (see module docstring)
    return out, (h, pos, src, dst, edge_mask, phi_e)


def _edge_agg_bwd(static, res, g):
    compute_dtype, block_e, block_h, interpret = static
    h, pos, src, dst, edge_mask, phi_e = res
    cd = compute_dtype or h.dtype
    H = h.shape[-1]
    A = h.shape[1]
    w0i, w0j, w0d, b0, w1, _ = _split_phi_e(phi_e, H, cd)
    sr = jnp.where(edge_mask, src, A)
    dr = jnp.where(edge_mask, dst, A)
    dh, dpos, dw0i, dw0j, dw0d, db0, dw1, db1 = egnn_edge_fused_bwd(
        g, h.astype(cd), pos, sr, dr, w0i, w0j, w0d, b0, w1,
        block_e=block_e, block_h=block_h, interpret=interpret)
    f0, f1 = phi_e["fc0"], phi_e["fc1"]
    dphi = {
        "fc0": {"w": jnp.concatenate([dw0i, dw0j, dw0d],
                                     axis=0).astype(f0["w"].dtype),
                "b": db0[0].astype(f0["b"].dtype)},
        "fc1": {"w": dw1.astype(f1["w"].dtype),
                "b": db1[0].astype(f1["b"].dtype)},
    }
    return dh.astype(h.dtype), dpos.astype(pos.dtype), None, None, None, dphi


_edge_agg.defvjp(_edge_agg_fwd, _edge_agg_bwd)


def egnn_edge_agg(h, pos, src, dst, edge_mask, phi_e, *, compute_dtype=None,
                  block_e=None, block_h=None, interpret=None):
    """Fused EGNN message + aggregation: (B, A, H) node features in,
    (B, A, H) aggregated messages out. Drop-in for the unfused
    ``repro.models.gnn.message_agg`` (numerics: ``ref.py``),
    differentiable end-to-end via the fused backward kernel.
    ``block_e``/``block_h``: None plans against the VMEM budget model
    (``cfg.kernel_block_e`` / ``cfg.kernel_block_h`` override via
    ``egnn_apply``; over-budget overrides raise ``budget.VmemBudgetError``);
    ``interpret=None`` auto-detects the backend."""
    block_e, block_h = _resolve_blocks(block_e, block_h, h.shape[1],
                                       src.shape[1], h.shape[-1])
    static = (compute_dtype, block_e, block_h, interpret)
    return _edge_agg(static, h, pos, src, dst, edge_mask, phi_e)
