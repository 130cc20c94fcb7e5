"""Decoding heads — including the paper's two-level hierarchical MTL heads.

Level 1: one branch per data source (task). Level 2: each branch owns an
energy head (graph-level scalar via masked mean-pool + MLP) and a force head
(node-level 3-vector via MLP). Heads are *stacked* along a leading task dim
so the multi-task-parallelism core can shard that dim over the mesh's task
axis (paper: each process owns one branch).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import KeyGen, Params
from .mlp import mlp_apply, mlp_init


def branch_init(key, cfg) -> Params:
    """One per-source branch: {energy, force} MLPs (paper: 3 FC x 889)."""
    kg = KeyGen(key)
    hid = cfg.gnn_hidden
    hh, hl = cfg.head_hidden, cfg.head_layers
    dt = cfg.param_dtype
    return {
        "energy": mlp_init(kg(), hid, hh, 1, hl, dt),
        "force": mlp_init(kg(), hid, hh, 3, hl, dt),
    }


def stacked_branches_init(key, cfg, n_tasks: int) -> Params:
    keys = jax.random.split(key, n_tasks)
    return jax.vmap(lambda k: branch_init(k, cfg))(keys)


def branch_apply(bp: Params, node_feats, node_mask, *, cfg):
    """node_feats: (B,A,hid) -> (energy_per_atom: (B,), forces: (B,A,3)).
    Its device ops carry the named scope ``heads``."""
    cd = cfg.compute_dtype
    with jax.named_scope("heads"):
        nm = node_mask[..., None].astype(cd)
        n = jnp.maximum(node_mask.sum(-1, keepdims=True).astype(jnp.float32),
                        1.0)
        pooled = (node_feats * nm).sum(1) / n.astype(cd)   # masked mean-pool
        e = mlp_apply(bp["energy"], pooled, "silu", cd)[..., 0]   # (B,)
        f = mlp_apply(bp["force"], node_feats, "silu", cd) * nm   # (B,A,3)
        return e.astype(jnp.float32), f.astype(jnp.float32)


def stacked_branches_apply(bp: Params, node_feats, node_mask, *, cfg):
    """Task-major inputs: node_feats (T,B,A,hid), node_mask (T,B,A).
    bp leaves have leading task dim (shardable over the task mesh axis)."""
    return jax.vmap(lambda p, h, m: branch_apply(p, h, m, cfg=cfg))(
        bp, node_feats, node_mask)
