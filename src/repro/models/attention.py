"""Attention: GQA/MHA (+QKV bias), sliding-window, and DeepSeek-V2 MLA.

Two compute paths:
  * ``impl="chunked"`` — pure-JAX blocked online-softmax (flash-style) used
    for dry-run lowering and CPU tests. Memory is O(q_chunk * k_chunk), never
    O(S^2), so 32k prefill lowers with a sane working set.
  * ``impl="pallas"`` — the Pallas TPU kernel in ``repro.kernels``
    (validated in interpret mode; TPU-only at runtime).
  * ``impl="naive"`` — full score matrix; oracle for tests.

Cache layout (GQA):  {"k","v": (B, C, K, hd), "pos": ()} where C is either
full seq_len or the rolling window size. Keys are stored *post-RoPE* at their
absolute positions so a rolling cache stays valid.
Cache layout (MLA):  {"ckv": (B, C, r), "krope": (B, C, dr), "pos": ()}.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from .common import KeyGen, Params, apply_rope, dense, dense_init

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Mask helper
# ---------------------------------------------------------------------------

def _mask(q_pos, k_pos, causal: bool, window: int):
    """q_pos: (..., Sq), k_pos: (..., Sk) -> bool (..., Sq, Sk); True=keep.
    Padded/invalid positions use large-negative sentinels; guard them
    explicitly (a -1e9 k_pos would otherwise pass the causal test)."""
    d = q_pos[..., :, None] - k_pos[..., None, :]
    m = (k_pos > -(10 ** 8))[..., None, :] & (q_pos > -(10 ** 8))[..., :, None]
    if causal:
        m &= d >= 0
    if window > 0:
        m &= d < window
    return m


# ---------------------------------------------------------------------------
# Scaled dot-product attention (grouped-query, no kv repeat)
# ---------------------------------------------------------------------------

def sdpa_naive(q, k, v, *, q_pos, k_pos, causal=True, window=0, scale=None):
    """q: (B,Sq,H,hd) k,v: (B,Sk,K,hd). Oracle path."""
    B, Sq, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    qg = q.reshape(B, Sq, K, G, hd)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    m = _mask(q_pos, k_pos, causal, window)  # (Sq,Sk) or (B,Sq,Sk)
    while m.ndim < s.ndim:
        m = m[..., None, :, :] if m.ndim >= 2 else m
    s = jnp.where(m, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskh->bqkgh", p, v.astype(jnp.float32))
    return o.reshape(B, Sq, H, hd).astype(q.dtype)


def sdpa_chunked(q, k, v, *, q_pos, k_pos, causal=True, window=0, scale=None,
                 q_chunk=512, k_chunk=1024):
    """Blocked online-softmax attention in pure JAX (lowering-friendly)."""
    B, Sq, H, hd = q.shape
    Sk, K = k.shape[1], k.shape[2]
    G = H // K
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    qc = min(q_chunk, Sq)
    kc = min(k_chunk, Sk)
    nq, nk = -(-Sq // qc), -(-Sk // kc)
    # pad to multiples
    if nq * qc != Sq:
        pad = nq * qc - Sq
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, pad),), constant_values=-10 ** 9)
    if nk * kc != Sk:
        pad = nk * kc - Sk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, pad),), constant_values=-10 ** 9)

    qb = q.reshape(B, nq, qc, K, G, hd).astype(jnp.float32)
    kb = k.reshape(B, nk, kc, K, hd).astype(jnp.float32)
    vb = v.reshape(B, nk, kc, K, hd).astype(jnp.float32)
    qpb = q_pos.reshape(nq, qc)
    kpb = k_pos.reshape(nk, kc)

    def q_block(args):
        qi, qp = args  # (B,qc,K,G,hd), (qc,)

        def kv_step(carry, kv):
            m_prev, l_prev, acc = carry
            ki, vi, kp = kv
            s = jnp.einsum("bqkgh,bskh->bkgqs", qi, ki) * scale
            msk = _mask(qp, kp, causal, window)
            s = jnp.where(msk[None, None, None], s, NEG_INF)
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_cur[..., None])
            corr = jnp.exp(m_prev - m_cur)
            l_new = l_prev * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum("bkgqs,bskh->bkgqh", p, vi)
            return (m_cur, l_new, acc), None

        m0 = jnp.full((B, K, G, qc), NEG_INF)
        l0 = jnp.zeros((B, K, G, qc))
        a0 = jnp.zeros((B, K, G, qc, hd))
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), (kb.swapaxes(0, 1), vb.swapaxes(0, 1), kpb))
        o = acc / jnp.maximum(l[..., None], 1e-30)
        return o.transpose(0, 3, 1, 2, 4)  # (B,qc,K,G,hd)

    out = jax.lax.map(q_block, (qb.swapaxes(0, 1), qpb))  # (nq,B,qc,K,G,hd)
    out = out.swapaxes(0, 1).reshape(B, nq * qc, H, hd)
    return out[:, :Sq].astype(q.dtype)


def sdpa(q, k, v, *, q_pos, k_pos, causal=True, window=0, scale=None,
         impl="chunked", **kw):
    if impl == "naive":
        return sdpa_naive(q, k, v, q_pos=q_pos, k_pos=k_pos, causal=causal,
                          window=window, scale=scale)
    if impl == "pallas":
        from repro.kernels.flash_attention import ops as fa_ops
        return fa_ops.flash_attention(q, k, v, q_pos=q_pos, k_pos=k_pos,
                                      causal=causal, window=window, scale=scale,
                                      interpret=kw.get("interpret"))
    return sdpa_chunked(q, k, v, q_pos=q_pos, k_pos=k_pos, causal=causal,
                        window=window, scale=scale,
                        q_chunk=kw.get("q_chunk", 512), k_chunk=kw.get("k_chunk", 1024))


# ---------------------------------------------------------------------------
# GQA attention block
# ---------------------------------------------------------------------------

def gqa_init(key, cfg) -> Params:
    kg = KeyGen(key)
    d, H, K, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    dt = cfg.param_dtype
    return {
        "wq": dense_init(kg(), d, H * hd, dt, bias=cfg.qkv_bias),
        "wk": dense_init(kg(), d, K * hd, dt, bias=cfg.qkv_bias),
        "wv": dense_init(kg(), d, K * hd, dt, bias=cfg.qkv_bias),
        "wo": dense_init(kg(), H * hd, d, dt, stddev=0.02 / math.sqrt(2 * cfg.n_layers or 2)),
    }


def gqa_apply(params: Params, x, *, cfg, positions, window=0, cache=None,
              impl="chunked", cache_window=0):
    """x: (B,S,d). cache None => train/prefill (returns new cache if requested
    via cache == "init"); else decode step (S==1), returns (out, new_cache)."""
    B, S, d = x.shape
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    cd = cfg.compute_dtype
    q = dense(params["wq"], x, cd).reshape(B, S, H, hd)
    k = dense(params["wk"], x, cd).reshape(B, S, K, hd)
    v = dense(params["wv"], x, cd).reshape(B, S, K, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)

    if cache is None or cache == "init":
        o = sdpa(q, k, v, q_pos=positions, k_pos=positions, causal=True,
                 window=window, impl=impl)
        out = dense(params["wo"], o.reshape(B, S, H * hd), cd)
        if cache == "init":
            return out, {"k": k, "v": v, "pos": jnp.array(S, jnp.int32)}
        return out

    # ---- decode: S == 1, rolling or full cache --------------------------
    C = cache["k"].shape[1]
    pos = cache["pos"]  # absolute position of the new token
    slot = jnp.mod(pos, C)
    ck = jax.lax.dynamic_update_slice(cache["k"], k, (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache["v"], v, (0, slot, 0, 0))
    # absolute position held by each slot j after the write:
    j = jnp.arange(C)
    slot_pos = pos - jnp.mod(pos - j, C)  # <= pos, same residue as j
    valid = slot_pos >= 0
    if window > 0:
        valid &= slot_pos > pos - window
    k_pos = jnp.where(valid, slot_pos, -10 ** 9)
    if impl == "pallas":
        # window already folded into k_pos validity
        from repro.kernels.flash_decode import ops as fd_ops
        o = fd_ops.flash_decode(q, ck, cv, q_pos=pos,
                                k_pos=jnp.broadcast_to(k_pos[None], (B, C)))
    else:
        o = sdpa_naive(q, ck, cv, q_pos=positions, k_pos=k_pos, causal=True,
                       window=0)
    out = dense(params["wo"], o.reshape(B, 1, H * hd), cd)
    return out, {"k": ck, "v": cv, "pos": pos + 1}


def gqa_cache_init(cfg, batch: int, cache_len: int, dtype=None) -> Params:
    dt = dtype or cfg.compute_dtype
    K, hd = cfg.n_kv_heads, cfg.hd
    return {"k": jnp.zeros((batch, cache_len, K, hd), dt),
            "v": jnp.zeros((batch, cache_len, K, hd), dt),
            "pos": jnp.array(0, jnp.int32)}


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2): low-rank compressed KV, absorbed decode
# ---------------------------------------------------------------------------

def mla_init(key, cfg) -> Params:
    kg = KeyGen(key)
    d, H = cfg.d_model, cfg.n_heads
    r, rq = cfg.kv_lora, cfg.q_lora
    dn = cfg.hd                 # nope sub-dim per head
    dr = cfg.rope_dims
    dv = cfg.v_head_dim
    dt = cfg.param_dtype
    return {
        "wq_a": dense_init(kg(), d, rq, dt),
        "q_norm": {"scale": jnp.ones((rq,), dt)},
        "wq_b": dense_init(kg(), rq, H * (dn + dr), dt),
        "wkv_a": dense_init(kg(), d, r + dr, dt),
        "kv_norm": {"scale": jnp.ones((r,), dt)},
        "wk_b": dense_init(kg(), r, H * dn, dt),
        "wv_b": dense_init(kg(), r, H * dv, dt),
        "wo": dense_init(kg(), H * dv, d, dt, stddev=0.02 / math.sqrt(2 * cfg.n_layers)),
    }


def _mla_project_q(params, x, cfg, positions):
    from .common import rmsnorm
    B, S, _ = x.shape
    H, dn, dr = cfg.n_heads, cfg.hd, cfg.rope_dims
    cd = cfg.compute_dtype
    qa = rmsnorm(params["q_norm"], dense(params["wq_a"], x, cd))
    qb = dense(params["wq_b"], qa, cd).reshape(B, S, H, dn + dr)
    q_nope, q_rope = qb[..., :dn], qb[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def mla_apply(params: Params, x, *, cfg, positions, cache=None, impl="chunked"):
    from .common import rmsnorm
    B, S, d = x.shape
    H, r, dn, dr, dv = cfg.n_heads, cfg.kv_lora, cfg.hd, cfg.rope_dims, cfg.v_head_dim
    cd = cfg.compute_dtype
    q_nope, q_rope = _mla_project_q(params, x, cfg, positions)

    kv = dense(params["wkv_a"], x, cd)
    ckv, k_rope = kv[..., :r], kv[..., r:]
    ckv = rmsnorm(params["kv_norm"], ckv)
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0, :]

    if cache is None or cache == "init":
        # prefill/train: up-project and run standard MHA with split rope dims
        k_nope = dense(params["wk_b"], ckv, cd).reshape(B, S, H, dn)
        vv = dense(params["wv_b"], ckv, cd).reshape(B, S, H, dv)
        q = jnp.concatenate([q_nope, q_rope], -1)
        k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (B, S, H, dr))], -1)
        scale = 1.0 / math.sqrt(dn + dr)
        # pad v to q head_dim for the shared sdpa, then slice back
        o = sdpa(q, k, jnp.pad(vv, ((0, 0), (0, 0), (0, 0), (0, dn + dr - dv))),
                 q_pos=positions, k_pos=positions, causal=True, impl=impl, scale=scale)
        o = o[..., :dv]
        out = dense(params["wo"], o.reshape(B, S, H * dv), cd)
        if cache == "init":
            return out, {"ckv": ckv, "krope": k_rope, "pos": jnp.array(S, jnp.int32)}
        return out

    # ---- absorbed decode (S == 1): score/value in latent space ----------
    C = cache["ckv"].shape[1]
    pos = cache["pos"]
    cc = jax.lax.dynamic_update_slice(cache["ckv"], ckv, (0, pos, 0))
    cr = jax.lax.dynamic_update_slice(cache["krope"], k_rope, (0, pos, 0))
    # absorb W_uk into q:  q_lat[b,h,r'] = sum_dn q_nope[b,h,dn] * Wk_b[r',h,dn]
    wkb = params["wk_b"]["w"].reshape(r, H, dn).astype(cd)
    q_lat = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], wkb)
    scale = 1.0 / math.sqrt(dn + dr)
    s = (jnp.einsum("bhr,bsr->bhs", q_lat.astype(jnp.float32), cc.astype(jnp.float32))
         + jnp.einsum("bhd,bsd->bhs", q_rope[:, 0].astype(jnp.float32), cr.astype(jnp.float32))) * scale
    k_pos = jnp.arange(C)
    s = jnp.where((k_pos <= pos)[None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhs,bsr->bhr", p, cc.astype(jnp.float32))  # (B,H,r)
    wvb = params["wv_b"]["w"].reshape(r, H, dv).astype(cd)
    o = jnp.einsum("bhr,rhd->bhd", o_lat.astype(cd), wvb)
    out = dense(params["wo"], o.reshape(B, 1, H * dv), cd)
    return out, {"ckv": cc, "krope": cr, "pos": pos + 1}


def mla_cache_init(cfg, batch: int, cache_len: int, dtype=None) -> Params:
    dt = dtype or cfg.compute_dtype
    return {"ckv": jnp.zeros((batch, cache_len, cfg.kv_lora), dt),
            "krope": jnp.zeros((batch, cache_len, cfg.rope_dims), dt),
            "pos": jnp.array(0, jnp.int32)}
