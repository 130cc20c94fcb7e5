"""jit'd public wrapper: (B,S,H,D) layout + GQA, dispatching to the kernel."""
from __future__ import annotations

import jax.numpy as jnp

from .kernel import flash_attention_bhsd


def flash_attention(q, k, v, *, q_pos, k_pos, causal=True, window=0,
                    scale=None, block_q=128, block_k=128, interpret=None):
    """q: (B,Sq,H,D); k,v: (B,Sk,K,D) -> (B,Sq,H,D)."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    o = flash_attention_bhsd(qt, kt, vt, q_pos, k_pos, causal=causal,
                             window=window, scale=scale, block_q=block_q,
                             block_k=block_k, interpret=interpret)
    return o.transpose(0, 2, 1, 3)
