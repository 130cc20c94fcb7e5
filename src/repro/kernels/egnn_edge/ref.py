"""Pure-jnp oracle for the fused EGNN edge kernel.

The message path in its concat form: gather -> d² -> φ_e via ``mlp_apply``
on the materialized (B, E, 2H+1) concat -> scatter segment-sum. The model's
non-fused path (``repro.models.gnn.message_agg``) computes the same sum with
fc0 projected onto the atoms before the gather, and is held to this oracle
as the kernel is (tests/test_hotpath.py). ``jax.grad`` through this
function is likewise the oracle for the fused BACKWARD kernel
(``kernel.egnn_edge_fused_bwd``): the custom_vjp in ``ops.py`` must match
it within tolerance in every cotangent (tests/test_hotpath.py paper-shape
parity suite)."""
from __future__ import annotations

import jax.numpy as jnp

from repro.models.mlp import mlp_apply


def egnn_edge_agg_ref(h, pos, src, dst, edge_mask, phi_e, *,
                      compute_dtype=None):
    """h: (B, A, H); pos: (B, A, 3); src/dst: (B, E); edge_mask: (B, E);
    phi_e: 2-layer MLP params ({"fc0": {w,b}, "fc1": {w,b}}).
    Returns (B, A, H) aggregated messages."""
    cd = compute_dtype or h.dtype
    B, A, H = h.shape

    def gather(x, idx):
        return jnp.take_along_axis(x, idx[..., None], axis=1)

    sc = jnp.minimum(src, A - 1)
    dc = jnp.minimum(dst, A - 1)
    hi = gather(h, sc)
    hj = gather(h, dc)
    xi = gather(pos.astype(jnp.float32), sc)
    xj = gather(pos.astype(jnp.float32), dc)
    d2 = jnp.sum((xi - xj) ** 2, -1, keepdims=True).astype(cd)
    m = mlp_apply(phi_e, jnp.concatenate([hi, hj, d2], -1), "silu", cd)
    m = jnp.where(edge_mask[..., None], m, 0.0)
    d = jnp.where(edge_mask, dst, A)
    out = jnp.zeros((B, A, H), m.dtype)
    return out.at[jnp.arange(B)[:, None], d].add(m, mode="drop")
