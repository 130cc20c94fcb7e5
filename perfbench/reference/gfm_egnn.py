"""Plain float32 reference of the GFM's EGNN trunk and its branches, in
straightforward ``jax.numpy``; the loss, AdamW and the readings are shared
by every architecture (``common.py``).

It follows the model as the paper describes it (arXiv 2506.21788 §5, the
HydraGNN EGNN) in the invariant form this repository trains: per layer an
edge MLP phi_e over [h_src, h_dst, |x_src - x_dst|^2] with SiLU between its
two dense layers, a masked sum of the messages into each edge's destination
atom, a node MLP phi_h over [h, aggregate], and a residual update masked to
real atoms. Each branch holds an energy MLP on the masked mean of the atom
features and a force MLP on each atom (``head_layers`` hidden layers of
``head_hidden``, SiLU). Sizes: ``gnn_hidden`` H, ``gnn_layers``,
``head_hidden``, ``head_layers``, ``n_species``, ``n_tasks``.

Nothing here imports the program. Every matrix product runs at
``Precision.HIGHEST``; ``cd`` lowers the dtype of the activations and
weights for a control run.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from perfbench.reference.common import mlp, mlp_init, mlp_macs, mlp_shapes


def init(cfg: dict, key):
    """Parameters in the program's layout: ``{"shared": {"embed",
    "layer{i}": {"phi_e", "phi_h"}}, "heads": {"energy", "force"}}``, the
    branches stacked on a leading head axis."""
    hidden, layers = cfg["gnn_hidden"], cfg["gnn_layers"]
    head_hidden, head_layers = cfg["head_hidden"], cfg["head_layers"]
    dtype = jnp.dtype(cfg["param_dtype"])
    ks = jax.random.split(key, layers + 3)
    shared = {"embed": {"table": (0.02 * jax.random.normal(
        ks[0], (cfg["n_species"], hidden), jnp.float32)).astype(dtype)}}
    for i in range(layers):
        ke, kh = jax.random.split(ks[1 + i])
        shared[f"layer{i}"] = {
            "phi_e": mlp_init(ke, mlp_shapes(2 * hidden + 1, hidden, hidden,
                                             1), (), dtype),
            "phi_h": mlp_init(kh, mlp_shapes(2 * hidden, hidden, hidden, 1),
                              (), dtype)}
    lead = (cfg["n_tasks"],)
    heads = {
        "energy": mlp_init(ks[-2], mlp_shapes(hidden, head_hidden, 1,
                                              head_layers), lead, dtype),
        "force": mlp_init(ks[-1], mlp_shapes(hidden, head_hidden, 3,
                                             head_layers), lead, dtype)}
    return {"shared": shared, "heads": heads}


def tiny(cfg: dict) -> dict:
    """``cfg`` at the sizes of the CPU tests."""
    return dict(cfg, gnn_hidden=16, gnn_layers=2, head_hidden=8,
                head_layers=1)


def forward_flops(cfg: dict, *, structures: int, atoms: int,
                  edges: int) -> int:
    """Forward operations for ``structures`` real structures holding
    ``atoms`` real atoms and ``edges`` real (directed) edges in all.

    Matrix multiplications at 2 operations per multiply-add; biases,
    activations, gathers, the distance feature and the segment sum are left
    out (each is below 1% of a layer's matmuls at these widths). Per layer
    (hidden H): phi_e maps [h_i, h_j, d2] (2H+1) to H and then H to H, once
    per edge; phi_h maps [h, agg] (2H) to H and H to H, once per atom. Per
    branch (width W, ``head_layers`` hidden layers): the energy MLP runs
    once per structure on the pooled features (H -> W -> ... -> W -> 1),
    the force MLP once per atom (... -> 3)."""
    H, L = cfg["gnn_hidden"], cfg["gnn_layers"]
    W, n = cfg["head_hidden"], cfg["head_layers"]
    edge = mlp_macs(2 * H + 1, H, H, 1)
    node = mlp_macs(2 * H, H, H, 1)
    energy = mlp_macs(H, W, 1, n)
    force = mlp_macs(H, W, 3, n)
    macs = L * (edges * edge + atoms * node) + structures * energy \
        + atoms * force
    return 2 * macs


def trunk(shared, batch, cfg: dict, cd):
    """Atom features (B, A, H) of a padded batch."""
    species, nm = batch["species"], batch["node_mask"]
    src, dst, em = batch["edge_src"], batch["edge_dst"], batch["edge_mask"]
    pos = batch["pos"].astype(jnp.float32)
    B, A = species.shape
    keep = nm[..., None].astype(cd)
    h = shared["embed"]["table"].astype(cd)[species] * keep
    s = jnp.minimum(src, A - 1)
    d = jnp.minimum(dst, A - 1)
    rows = jnp.arange(B)[:, None]
    d2 = jnp.sum((pos[rows, s] - pos[rows, d]) ** 2, -1, keepdims=True)
    # messages of pad edges go to one spare segment that is dropped
    seg = jnp.where(em, rows * A + d, B * A).reshape(-1)
    for i in range(cfg["gnn_layers"]):
        lp = shared[f"layer{i}"]
        msg = mlp(lp["phi_e"],
                  jnp.concatenate([h[rows, s], h[rows, d], d2.astype(cd)],
                                  -1), cd)
        msg = jnp.where(em[..., None], msg, 0)
        agg = jax.ops.segment_sum(msg.reshape(B * msg.shape[1], -1), seg,
                                  num_segments=B * A + 1)[:-1]
        agg = agg.reshape(B, A, -1).astype(cd)
        h = (h + mlp(lp["phi_h"], jnp.concatenate([h, agg], -1), cd)) * keep
    return h


def branch(bp, h, batch, cd):
    """-> energy (B,) from the mean of the atom features and forces
    (B, A, 3) per atom, float32."""
    nm = batch["node_mask"]
    keep = nm[..., None].astype(cd)
    n = jnp.maximum(nm.sum(-1, keepdims=True), 1).astype(cd)
    pooled = (h * keep).sum(1) / n
    e = mlp(bp["energy"], pooled, cd)[..., 0]
    f = mlp(bp["force"], h, cd) * keep
    return e.astype(jnp.float32), f.astype(jnp.float32)

