"""jit'd wrapper: (B,1,H,D) query + (B,S,K,D) cache -> (B,1,H,D)."""
from __future__ import annotations

import jax.numpy as jnp

from .kernel import combine_partials, flash_decode_partials


def flash_decode(q, k, v, *, q_pos, k_pos, window=0, scale=None,
                 n_splits=8, block_k=512, interpret=None):
    """q: (B,1,H,D); k,v: (B,S,K,D); q_pos: (B,) or scalar; k_pos: (B,S) or
    (S,). Returns (B,1,H,D)."""
    B, _, H, D = q.shape
    S, K = k.shape[1], k.shape[2]
    if k_pos.ndim == 1:
        k_pos = jnp.broadcast_to(k_pos[None], (B, S))
    q_pos = jnp.broadcast_to(jnp.asarray(q_pos).reshape(-1), (B,))
    m, l, acc = flash_decode_partials(
        q[:, 0].transpose(0, 1, 2), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), q_pos, k_pos, window=window, scale=scale,
        n_splits=n_splits, block_k=block_k, interpret=interpret)
    o = combine_partials(m, l, acc)                 # (B,K,G,D)
    return o.reshape(B, 1, H, D).astype(q.dtype)
