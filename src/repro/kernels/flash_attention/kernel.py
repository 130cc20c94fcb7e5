"""Flash-attention Pallas TPU kernel (causal + sliding-window, GQA-aware).

Grid: (batch, q_heads, num_q_blocks, num_kv_blocks) — the last grid dim is
sequential on TPU, so online-softmax state (m, l, acc) lives in VMEM scratch
carried across kv blocks; the output tile is written on the final kv block.

BlockSpec tiling (VMEM working set, MXU-aligned):
  q:   (1, 1, BQ, D)  indexed (b, h, iq, ·)
  k/v: (1, 1, BK, D)  indexed (b, h // G, ·, ik)  — GQA without kv repeat
  pos: (BQ,) / (BK,)  int32 streams, so padded / rolling-window caches mask
       correctly (pad sentinel = -1e9).

Defaults BQ=BK=128: for D=256 the resident set (q,k,v tiles + f32 score tile
+ f32 accumulator) is ~0.7 MiB — far under the ~16 MiB VMEM budget, leaving
room for double-buffered pipelining.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.segment_sum.kernel import resolve_interpret

NEG_INF = -1e30

VMEM_BUDGET = (16 << 20) - (4 << 20)   # physical VMEM minus Mosaic headroom


def vmem_bytes(D: int, block_q: int, block_k: int, *,
               itemsize: int = 4) -> int:
    """Modeled resident VMEM of one kernel step: double-buffered block DMA
    (×2) for q/k/v/out tiles and the pos streams, f32 online-softmax scratch
    (m, l, acc), plus the live f32 casts and the (BQ, BK) score/prob tiles.
    ``itemsize`` is the in/out dtype width; all kernel math is f32."""
    bq, bk = block_q, block_k
    return (2 * (bq + bk) * 4                  # q_pos / k_pos int32 streams
            + 2 * bq * D * itemsize            # q tile (double-buffered)
            + 2 * 2 * bk * D * itemsize        # k + v tiles
            + 2 * bq * D * itemsize            # out tile
            + (2 * bq + bq * D) * 4            # m, l, acc scratch
            + (bq + 2 * bk) * D * 4            # live f32 casts of q, k, v
            + 2 * bq * bk * 4)                 # live s and p score tiles


def check_blocks(D: int, block_q: int, block_k: int, *, itemsize: int = 4,
                 vmem_limit: int = VMEM_BUDGET) -> None:
    """Raise if an explicit (block_q, block_k) override exceeds the VMEM
    budget — over-budget configs must fail at trace time, not OOM on core."""
    need = vmem_bytes(D, block_q, block_k, itemsize=itemsize)
    if need > vmem_limit:
        raise ValueError(
            f"flash_attention blocks (block_q={block_q}, block_k={block_k}) "
            f"need ≈{need / 2 ** 20:.1f} MiB of VMEM at D={D} — over the "
            f"{vmem_limit / 2 ** 20:.1f} MiB budget; halve the blocks "
            f"(the 128/128 defaults fit every supported head dim).")


def _fa_kernel(qp_ref, kp_ref, q_ref, k_ref, v_ref, o_ref,
               m_ref, l_ref, acc_ref, *, scale, causal, window, nk):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)          # (BQ, D)
    k = k_ref[0, 0].astype(jnp.float32)          # (BK, D)
    v = v_ref[0, 0].astype(jnp.float32)
    qp = qp_ref[...]                             # (BQ,) int32
    kp = kp_ref[...]                             # (BK,)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # (BQ,BK)
    dpos = qp[:, None] - kp[None, :]
    mask = kp[None, :] > -(10 ** 8)              # padded keys out
    if causal:
        mask &= dpos >= 0
    if window > 0:
        mask &= dpos < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
    corr = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, None])
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))
    m_ref[...] = m_cur

    @pl.when(ik == nk - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "scale",
                                             "block_q", "block_k", "interpret"))
def flash_attention_bhsd(q, k, v, q_pos, k_pos, *, causal=True, window=0,
                         scale=None, block_q=128, block_k=128, interpret=None):
    """q: (B,H,Sq,D); k,v: (B,K,Sk,D); H % K == 0. Returns (B,H,Sq,D).
    ``interpret=None`` runs compiled on TPU, interpreted elsewhere."""
    B, H, Sq, D = q.shape
    K, Sk = k.shape[1], k.shape[2]
    G = H // K
    scale = scale if scale is not None else D ** -0.5
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    nq, nk = -(-Sq // bq), -(-Sk // bk)
    check_blocks(D, bq, bk, itemsize=q.dtype.itemsize)
    q_pos = q_pos.astype(jnp.int32)
    k_pos = k_pos.astype(jnp.int32)
    if nq * bq != Sq:
        pq = nq * bq - Sq
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
        q_pos = jnp.pad(q_pos, (0, pq), constant_values=-(10 ** 9))
    if nk * bk != Sk:
        pk = nk * bk - Sk
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
        k_pos = jnp.pad(k_pos, (0, pk), constant_values=-(10 ** 9))

    kern = functools.partial(_fa_kernel, scale=scale, causal=causal,
                             window=window, nk=nk)
    out = pl.pallas_call(
        kern,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((bq,), lambda b, h, iq, ik: (iq,)),
            pl.BlockSpec((bk,), lambda b, h, iq, ik: (ik,)),
            pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, iq, ik: (b, h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, nq * bq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(q_pos, k_pos, q, k, v)
    return out[:, :, :Sq]
