"""Flash-decode Pallas TPU kernel: one query token vs a long KV cache.

Decode attention is bandwidth-bound on cache reads and, unlike prefill,
offers no query-block parallelism. The standard adaptation (flash-decoding)
splits the KV length across the grid so every split streams its cache slice
at full HBM bandwidth, emitting PARTIAL online-softmax states (m, l, acc);
a cheap second phase combines the partials exactly.

Grid: (batch, kv_heads, n_splits). Each program handles all G = H/K query
heads of its kv head (GQA without repeat), reading a (BK, D) cache tile per
inner step via ``pl.when``-guarded accumulation over its split's blocks.

Outputs (partials, combined on the host side of the op in ops.py):
  m_part:   (B, K, G, n_splits)
  l_part:   (B, K, G, n_splits)
  acc_part: (B, K, G, n_splits, D)

VMEM per program at BK=512, D=256, G=8: k/v tiles 2x512x256x4 = 1 MiB,
q (8,256) + acc (8,256) negligible.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.segment_sum.kernel import resolve_interpret

NEG_INF = -1e30

VMEM_BUDGET = (16 << 20) - (4 << 20)   # physical VMEM minus Mosaic headroom


def vmem_bytes(D: int, G: int, per_split: int, block_k: int, *,
               itemsize: int = 4) -> int:
    """Modeled resident VMEM of one (batch, kv_head, split) program. The
    dominant term is the SPLIT slice, not ``block_k``: the k/v BlockSpecs
    carve ``(1, 1, per_split, D)``, so the whole slice is DMA'd (double-
    buffered) and the fori_loop sub-tiles it in-VMEM with ``pl.dslice``."""
    return (2 * 2 * per_split * D * itemsize   # k + v split slices (×2 DMA)
            + 2 * per_split * 4                # k_pos int32 stream
            + 2 * G * D * itemsize             # q block
            + 2 * (2 * G + G * D) * 4          # m/l/acc partial outputs
            + 2 * block_k * D * 4              # live f32 casts of k, v tiles
            + 2 * G * D * 4                    # live f32 q cast + acc carry
            + 2 * G * block_k * 4)             # live s and p score tiles


def check_blocks(S: int, D: int, G: int, n_splits: int, block_k: int, *,
                 itemsize: int = 4, vmem_limit: int = VMEM_BUDGET) -> None:
    """Raise if an (n_splits, block_k) config exceeds the VMEM budget for a
    cache of length S — fail at trace time instead of OOMing on core. Longer
    caches need MORE splits (per_split shrinks), not bigger blocks."""
    bk = min(block_k, S)
    per_split = -(-S // (n_splits * bk)) * bk
    need = vmem_bytes(D, G, per_split, bk, itemsize=itemsize)
    if need > vmem_limit:
        raise ValueError(
            f"flash_decode config (n_splits={n_splits}, block_k={block_k}) "
            f"puts a per-split slice of {per_split} kv rows ≈"
            f"{need / 2 ** 20:.1f} MiB in VMEM at (S={S}, D={D}, G={G}) — "
            f"over the {vmem_limit / 2 ** 20:.1f} MiB budget; raise n_splits "
            f"or shrink block_k.")


def _fd_kernel(qpos_ref, kp_ref, q_ref, k_ref, v_ref,
               m_out, l_out, acc_out, *, scale, window, blocks_per_split, bk):
    """One (batch, kv_head, split). Inner loop over this split's kv blocks."""
    q = q_ref[0, 0].astype(jnp.float32)            # (G, D)
    qpos = qpos_ref[0]                              # scalar int32

    def body(i, carry):
        m, l, acc = carry
        rows = pl.ds(i * bk, bk)
        k = k_ref[0, 0, rows, :].astype(jnp.float32)    # (BK, D)
        v = v_ref[0, 0, rows, :].astype(jnp.float32)
        kp = kp_ref[0, rows]                            # (BK,)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # (G,BK)
        dpos = qpos - kp
        mask = (kp > -(10 ** 8)) & (dpos >= 0)
        if window > 0:
            mask &= dpos < window
        s = jnp.where(mask[None, :], s, NEG_INF)
        m_cur = jnp.maximum(m, jnp.max(s, axis=1))
        corr = jnp.exp(m - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        l_new = l * corr + jnp.sum(p, axis=1)
        acc_new = acc * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        return m_cur, l_new, acc_new

    G, D = q.shape
    m0 = jnp.full((G,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((G,), jnp.float32)
    a0 = jnp.zeros((G, D), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, blocks_per_split, body, (m0, l0, a0))
    m_out[0, 0, :, 0] = m
    l_out[0, 0, :, 0] = l
    acc_out[0, 0, :, 0] = acc


@functools.partial(jax.jit, static_argnames=("window", "scale", "n_splits",
                                             "block_k", "interpret"))
def flash_decode_partials(q, k, v, q_pos, k_pos, *, window=0, scale=None,
                          n_splits=8, block_k=512, interpret=None):
    """q: (B,H,D) one token per sequence; k,v: (B,K,S,D); k_pos: (B,S).
    Returns partials (m, l, acc) with a trailing split dim.
    ``interpret=None`` runs compiled on TPU, interpreted elsewhere."""
    B, H, D = q.shape
    K, S = k.shape[1], k.shape[2]
    G = H // K
    scale = scale if scale is not None else D ** -0.5
    bk = min(block_k, S)
    check_blocks(S, D, G, n_splits, block_k, itemsize=q.dtype.itemsize)
    # pad S to n_splits * blocks_per_split * bk
    per_split = -(-S // (n_splits * bk)) * bk
    S_pad = per_split * n_splits
    if S_pad != S:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, S_pad - S), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, S_pad - S), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, S_pad - S)),
                        constant_values=-(10 ** 9))
    blocks_per_split = per_split // bk
    qg = q.reshape(B, K, G, D)

    kern = functools.partial(_fd_kernel, scale=scale, window=window,
                             blocks_per_split=blocks_per_split, bk=bk)
    out_shape = [
        jax.ShapeDtypeStruct((B, K, G, n_splits), jnp.float32),
        jax.ShapeDtypeStruct((B, K, G, n_splits), jnp.float32),
        jax.ShapeDtypeStruct((B, K, G, n_splits, D), jnp.float32),
    ]
    m, l, acc = pl.pallas_call(
        kern,
        grid=(B, K, n_splits),
        in_specs=[
            pl.BlockSpec((1, 1), lambda b, h, s: (b, 0)),      # q_pos (B,1)
            pl.BlockSpec((1, per_split), lambda b, h, s: (b, s)),
            pl.BlockSpec((1, 1, G, D), lambda b, h, s: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, per_split, D), lambda b, h, s: (b, h, s, 0)),
            pl.BlockSpec((1, 1, per_split, D), lambda b, h, s: (b, h, s, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, G, 1), lambda b, h, s: (b, h, 0, s)),
            pl.BlockSpec((1, 1, G, 1), lambda b, h, s: (b, h, 0, s)),
            pl.BlockSpec((1, 1, G, 1, D), lambda b, h, s: (b, h, 0, s, 0)),
        ],
        out_shape=out_shape,
        interpret=resolve_interpret(interpret),
    )(q_pos.reshape(B, 1).astype(jnp.int32), k_pos.astype(jnp.int32),
      qg, k, v)
    return m, l, acc


def combine_partials(m, l, acc):
    """Exact combine of per-split online-softmax partials -> (B,K,G,D)."""
    m_max = jnp.max(m, axis=-1, keepdims=True)              # (B,K,G,1)
    w = jnp.exp(m - m_max)                                  # (B,K,G,S)
    l_tot = jnp.sum(l * w, axis=-1)                         # (B,K,G)
    acc_tot = jnp.sum(acc * w[..., None], axis=-2)          # (B,K,G,D)
    return acc_tot / jnp.maximum(l_tot, 1e-30)[..., None]
