"""jit'd public wrapper for graph segment-sum (batched or single-graph).

Batched ``(B, E, F)`` input goes through ``segment_sum_batched`` (B as a
leading grid dimension); unbatched ``(E, F)`` input through
``segment_sum_2d``. Masked edges are routed to an out-of-range destination
sentinel so they contribute nothing (the kernel's pad-sentinel contract —
see ``kernel.py``). ``interpret=None`` auto-detects the backend: compiled on
TPU, interpreter mode elsewhere. A ``pallas_call`` has no transpose rule,
so ``jax.grad`` goes through a ``custom_vjp`` whose backward is the
segment sum's transpose, a gather of the output cotangent at each edge's
destination.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import autotune_blocks, segment_sum_2d, segment_sum_batched


def segment_sum(messages, dst, n_nodes: int, *, edge_mask=None,
                block_n=None, block_e=None, interpret=None):
    """messages: (B,E,F) or (E,F); dst: (B,E) or (E,) -> (B,n_nodes,F) or
    (n_nodes,F). ``block_n``/``block_e`` default to the ``autotune_blocks``
    heuristic; pass explicit values (e.g. the ``kernel_block_*`` config
    knobs) to override. Differentiable in ``messages``."""
    if messages.ndim not in (2, 3):
        raise ValueError(f"messages must be (E,F) or (B,E,F), got "
                         f"ndim={messages.ndim}")
    E, F = messages.shape[-2], messages.shape[-1]
    auto_n, auto_e = autotune_blocks(n_nodes, E, F)
    block_n = block_n or auto_n
    block_e = block_e or auto_e
    if edge_mask is not None:
        # n_nodes is >= every valid id and lands on a discarded padded row
        # (or matches nothing) inside the kernel — see sentinel contract
        dst = jnp.where(edge_mask, dst, n_nodes)
    return _segment_sum((n_nodes, block_n, block_e, interpret), messages,
                        dst)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _segment_sum(static, messages, dst):
    n_nodes, block_n, block_e, interpret = static
    kernel = segment_sum_batched if messages.ndim == 3 else segment_sum_2d
    return kernel(messages, dst, n_nodes, block_n=block_n, block_e=block_e,
                  interpret=interpret)


def _segment_sum_fwd(static, messages, dst):
    return _segment_sum(static, messages, dst), dst


def _segment_sum_bwd(static, dst, g):
    """The transpose of a segment sum is a gather: each edge takes the
    output cotangent of its destination row; routed edges (dst >= n_nodes)
    take zero."""
    valid = dst < static[0]
    rows = jnp.take_along_axis(g, jnp.where(valid, dst, 0)[..., None],
                               axis=-2)
    return jnp.where(valid[..., None], rows, 0).astype(g.dtype), None


_segment_sum.defvjp(_segment_sum_fwd, _segment_sum_bwd)
