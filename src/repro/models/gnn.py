"""EGNN encoder (the paper's HydraGNN backbone: 4-layer EGNN, 866 hidden).

Operates on padded graph batches (atomistic structures are small graphs —
hundreds of nodes — so we batch many padded graphs, per the paper's workload
shape, rather than partitioning one monolithic graph):

  species:    (B, A)    int32   atomic numbers (0 = pad)
  pos:        (B, A, 3) float   coordinates
  edge_src:   (B, E)    int32   source node index (A = pad sentinel)
  edge_dst:   (B, E)    int32   destination node index
  node_mask:  (B, A)    bool
  edge_mask:  (B, E)    bool

Each layer's message is φ_e(h_i, h_j, d²_ij) on every edge slot, summed into
its destination atom. On the non-fused paths φ_e's first dense is projected
onto the atoms before the gather: fc0 is linear, so its (2H+1, H) weight
splits into the h_i, h_j and d² row blocks, the two H-row blocks multiply the
(B, A, H) node features once per atom, and the edge slots gather the
projections (``message_agg``). fc0 then costs A rows per graph instead of
E, and the (B, E, 2H+1) concat never exists. SiLU and the later denses run
per edge slot as before.

Message aggregation is a segment-sum — the MPNN hot spot. Implementations
(selected per call or via ``cfg.segment_sum_impl``; the first three share
the node-side fc0 above):

  * ``"scatter"`` (default) — ``zeros.at[b, dst].add(msg)``: one XLA
    scatter-add, O(E·F) work. Fastest lowering on CPU/GPU and what XLA:TPU
    rewrites into its own sorted-segment ops.
  * ``"jnp"``     — one-hot einsum per graph, O(E·A·F) work. The original
    reference formulation; kept as the parity oracle.
  * ``"pallas"``  — blocked mask-matmul MXU kernel
    (``repro.kernels.segment_sum``), batched grid over B.
  * ``"fused"``   — the full message hot path (gather -> d² -> φ_e MLP ->
    masked segment-sum) in one Pallas kernel (``repro.kernels.egnn_edge``);
    it keeps φ_e's fc0 per edge, split by rows inside the kernel.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import (ACT, KeyGen, Params, cast, dense, embedding_init,
                     embed)
from .mlp import mlp_init, mlp_apply

SEGMENT_SUM_IMPLS = ("scatter", "jnp", "pallas", "fused")


def segment_sum_nodes(messages, dst, n_nodes, *, edge_mask, impl="scatter",
                      block_n=None, block_e=None):
    """messages: (B,E,F), dst: (B,E) -> (B,A,F) summing messages into nodes.

    ``impl``: "scatter" | "jnp" | "pallas" (see module docstring; "fused" is
    a whole-layer path and is dispatched in ``egnn_apply``, not here).
    ``block_n``/``block_e`` tile the Pallas kernel (None = autotune; only
    the "pallas" impl consumes them)."""
    if impl == "pallas":
        from repro.kernels.segment_sum import ops as ss_ops
        return ss_ops.segment_sum(messages, dst, n_nodes, edge_mask=edge_mask,
                                  block_n=block_n, block_e=block_e)
    if impl == "scatter":
        B = messages.shape[0]
        m = jnp.where(edge_mask[..., None], messages, 0.0)
        # masked / pad edges -> index n_nodes, out of range: dropped by the
        # scatter (mode="drop"), mirroring the Pallas sentinel contract
        d = jnp.where(edge_mask, dst, n_nodes)
        out = jnp.zeros((B, n_nodes) + messages.shape[2:], messages.dtype)
        return out.at[jnp.arange(B)[:, None], d].add(m, mode="drop")
    if impl != "jnp":
        raise ValueError(
            f"segment_sum impl '{impl}'; this op takes 'scatter' | 'jnp' | "
            "'pallas' ('fused' is a whole-layer path — select it via "
            "egnn_apply / cfg.segment_sum_impl)")
    m = jnp.where(edge_mask[..., None], messages, 0.0)
    oh = jax.nn.one_hot(dst, n_nodes, dtype=messages.dtype)       # (B,E,A)
    return jnp.einsum("bea,bef->baf", oh, m)


def egnn_init(key, cfg) -> Params:
    kg = KeyGen(key)
    hid = cfg.gnn_hidden
    dt = cfg.param_dtype
    p: Params = {"embed": embedding_init(kg(), cfg.n_species, hid, dt)}
    for i in range(cfg.gnn_layers):
        p[f"layer{i}"] = {
            "phi_e": mlp_init(kg(), 2 * hid + 1, hid, hid, 1, dt),
            "phi_h": mlp_init(kg(), 2 * hid, hid, hid, 1, dt),
        }
    return p


def _gather(x, idx):
    """x: (B, A, F), idx: (B, E) in [0, A) -> (B, E, F)."""
    return jnp.take_along_axis(x, idx[..., None], axis=1)


def message_agg(h, pos, src, dst, edge_mask, phi_e: Params, *,
                compute_dtype, impl="scatter", block_n=None, block_e=None):
    """One layer's non-fused message path -> (B, A, H) aggregated messages.

    Same arguments and result as ``egnn_edge_agg`` (``repro.kernels.
    egnn_edge``); ``impl`` picks the segment-sum ("scatter" | "jnp" |
    "pallas"). φ_e's fc0 is linear, so W·[h_i; h_j; d²] + b = (h W_a)[i]
    + (h W_b)[j] + d²·w_d + b, with W_a, W_b, w_d the row blocks of
    ``fc0.w`` ((2H+1, H)): the two projections run on the (B, A, H) node
    rows and the edge slots gather them."""
    cd = compute_dtype
    A, H = h.shape[1], h.shape[2]
    sc = jnp.minimum(src, A - 1)
    dc = jnp.minimum(dst, A - 1)
    pos = pos.astype(jnp.float32)
    d2 = jnp.sum((_gather(pos, sc) - _gather(pos, dc)) ** 2, -1,
                 keepdims=True).astype(cd)
    fc0 = phi_e["fc0"]
    w = cast(fc0["w"], cd)
    x = cast(h, cd)
    m = (_gather(x @ w[:H], sc) + _gather(x @ w[H:2 * H], dc)
         + d2 * w[2 * H] + cast(fc0["b"], cd))
    for i in range(1, len(phi_e)):
        m = dense(phi_e[f"fc{i}"], ACT["silu"](m), cd)
    return segment_sum_nodes(m, dst, A, edge_mask=edge_mask, impl=impl,
                             block_n=block_n, block_e=block_e)


def egnn_apply(params: Params, batch: dict, *, cfg, impl=None) -> jnp.ndarray:
    """-> node features (B, A, hidden). Invariant (distance-based) features.
    impl selects the message-aggregation path ("scatter" | "jnp" | "pallas" |
    "fused"); None defers to ``cfg.segment_sum_impl`` (config-driven kernel
    selection)."""
    if impl is None:
        impl = getattr(cfg, "segment_sum_impl", "scatter") or "scatter"
    if impl not in SEGMENT_SUM_IMPLS:
        raise ValueError(f"segment_sum impl '{impl}'; "
                         f"known: {SEGMENT_SUM_IMPLS}")
    cd = cfg.compute_dtype
    # kernel tile override shared by the pallas + fused paths (0/absent =
    # autotune inside the kernel wrappers); block_h additionally tiles the
    # fused kernel's φ_e hidden axis (the H=866 VMEM enabler)
    bn = getattr(cfg, "kernel_block_n", 0) or None
    be = getattr(cfg, "kernel_block_e", 0) or None
    bh = getattr(cfg, "kernel_block_h", 0) or None
    species = batch["species"]
    pos = batch["pos"].astype(jnp.float32)
    src, dst = batch["edge_src"], batch["edge_dst"]
    nm, em = batch["node_mask"], batch["edge_mask"]

    # named scopes: the device trace and the optimized HLO's op_name read
    # egnn/embed, egnn/layer{i}/message, egnn/layer{i}/node_update, forward
    # and backward alike; they change only metadata
    with jax.named_scope("egnn"):
        with jax.named_scope("embed"):
            h = embed(params["embed"], species, cd) * nm[..., None].astype(cd)
        for i in range(cfg.gnn_layers):
            lp = params[f"layer{i}"]
            with jax.named_scope(f"layer{i}/message"):
                if impl == "fused":
                    from repro.kernels.egnn_edge import ops as edge_ops
                    agg = edge_ops.egnn_edge_agg(
                        h, pos, src, dst, em, lp["phi_e"], compute_dtype=cd,
                        block_e=be, block_h=bh)
                else:
                    agg = message_agg(h, pos, src, dst, em, lp["phi_e"],
                                      compute_dtype=cd, impl=impl,
                                      block_n=bn, block_e=be)
            with jax.named_scope(f"layer{i}/node_update"):
                upd = mlp_apply(lp["phi_h"], jnp.concatenate([h, agg], -1),
                                "silu", cd)
                h = (h + upd) * nm[..., None].astype(cd)
    return h
