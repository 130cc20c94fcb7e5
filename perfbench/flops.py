"""Operations a GFM training step requires, from its shapes.

Counted for the real atoms and edges only (pad excluded), as matrix
multiplications at 2 operations per multiply-add; biases, activations,
gathers, the distance feature and the segment sum are left out (each is
below 1% of a layer's matmuls at these widths). The backward pass is
counted as twice the forward, so a training step is 3x the forward.

Per EGNN layer (hidden H): the edge MLP phi_e maps [h_i, h_j, d2] (2H+1)
to H and then H to H, once per edge; the node MLP phi_h maps [h, agg] (2H)
to H and H to H, once per atom. Per branch (width W, ``head_layers`` hidden
layers): the energy MLP runs once per structure on the pooled features
(H -> W -> ... -> W -> 1), the force MLP once per atom (... -> 3).
"""
from __future__ import annotations


def mlp_macs(d_in: int, hidden: int, d_out: int, n_hidden: int) -> int:
    dims = [d_in] + [hidden] * n_hidden + [d_out]
    return sum(a * b for a, b in zip(dims[:-1], dims[1:]))


def forward_flops(cfg: dict, *, structures: int, atoms: int,
                  edges: int) -> int:
    """Forward operations for ``structures`` real structures holding
    ``atoms`` real atoms and ``edges`` real (directed) edges in all."""
    H, L = cfg["gnn_hidden"], cfg["gnn_layers"]
    W, n = cfg["head_hidden"], cfg["head_layers"]
    edge = mlp_macs(2 * H + 1, H, H, 1)
    node = mlp_macs(2 * H, H, H, 1)
    energy = mlp_macs(H, W, 1, n)
    force = mlp_macs(H, W, 3, n)
    macs = L * (edges * edge + atoms * node) + structures * energy \
        + atoms * force
    return 2 * macs


def train_step_flops(cfg: dict, **counts) -> int:
    """Forward + backward operations of one training step."""
    return 3 * forward_flops(cfg, **counts)
