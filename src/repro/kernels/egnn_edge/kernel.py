"""Fused EGNN edge-message Pallas kernel, H-blocked for paper widths.

One ``pallas_call`` computes, per edge block, the whole EGNN message hot
path that ``egnn_apply`` otherwise lowers as five separate HBM-bound ops:

    gather(h_i, h_j, x_i, x_j) -> d² -> φ_e MLP (2 dense + SiLU)
        -> masked segment-sum into node rows

Nothing edge-major ever round-trips to HBM: the ``(BE, 2H+1)`` concat input
of φ_e is never materialized (the first dense layer's weight is split into
its ``h_i`` / ``h_j`` / ``d²`` row blocks, so the concat-matmul becomes a sum
of three small matmuls), and the aggregation happens tile-by-tile in VMEM
via the membership-matmul trick of ``repro.kernels.segment_sum`` — no
``(B, E, A)`` one-hot tensor at the XLA level.

H-blocking (the paper-width enabler, H=866). A ``block_h`` grid dimension
tiles the φ_e *inner* hidden axis — fc0's output columns, which are also
fc1's contraction (K) rows. Per H-block ``j`` the kernel computes the full
slice ``z_j = h_i @ w0i[:, j] + h_j @ w0j[:, j] + d²·w0d[:, j] + b0[:, j]``
(the contraction over the input-H runs whole inside one matmul, so no z
accumulator is needed and the backward stays single-pass) and folds it
straight into fc1's K-split: ``m += silu(z_j) @ w1[j, :]``. VMEM residency
is therefore bounded by ``block_h·H`` weight tiles plus ``A·H``/``block_e·H``
node-sided rows — never by an ``(H, H)`` matrix. Tiling fc0's *input*-K
instead would bound the same bytes but make the backward two-phase (the
SiLU chain rule needs a complete z before any cotangent flows), which is
why the inner axis is the one that gets the grid dimension.

Forward grid: (B, num_edge_blocks, num_h_blocks) — h-blocks innermost so
the (block_e, H) f32 message row finishes before its single membership
matmul; edge blocks sequential above it accumulate the (A, H) node scratch,
flushed on the batch's last step.

Masked/pad edges arrive with ``src = dst = A`` (routed by
``ops.egnn_edge_agg``). Endpoint rows are gathered by membership matmul
(``segment_sum.gather_rows``: Mosaic cannot lower a row gather), so a pad
edge gathers zero rows, and the same sentinel keeps it out of every
scatter. Edge indices enter as (B, 1, E): an edge block is a (1, block_e)
lane row, the layout the TPU compiler tiles. Ragged ``E % block_e`` is
padded with the sentinel; ragged ``H % block_h`` is padded with ZERO weight
columns/rows — ``silu(0) @ 0-rows`` contributes exactly nothing, and the
pad columns of the weight-grad outputs are sliced away by the wrapper.

Backward (``egnn_edge_fused_bwd``) — residual-recompute contract: the
``custom_vjp`` saves ONLY the primal inputs (h, pos, src, dst, edge_mask,
φ_e); no edge-major intermediate survives the forward. Grid
(B, num_h_blocks, num_edge_blocks): per (graph, h-block), the edge sweep
re-gathers h_i/h_j/x_i/x_j, re-derives d², recomputes the φ_e fc0 slice
``z_j`` + SiLU in the compute dtype (bit-identical rounding to the forward
— same dot shape, same inputs), then runs the chain rule in f32 and emits:

  * ``d_h`` / ``d_x`` — masked scatter-transposes of the per-block
    cotangents back to BOTH endpoint rows (membership matmuls shared with
    ``repro.kernels.segment_sum.accumulate_tile``), accumulated in (A, H) /
    (A, 3) f32 scratch across the whole (h-block × edge-block) sweep and
    flushed once per graph;
  * φ_e grads — PER-H-BLOCK f32 reductions: the ``(H, block_h)`` /
    ``(block_h, H)`` accumulators flush at the end of each (graph, h-block)
    edge sweep into per-graph partial outputs (summed over B by the
    wrapper — B-partials, not (H, H) scratch, is what keeps the grad path
    inside the ``block_h`` budget).

Masked/pad edges produce exact zeros in every cotangent because ``dm`` (the
gather of the upstream cotangent at ``dst``) is a zero row for them, and
everything below multiplies it.

The one-hot gathers and scatters of f32 values run at
``Precision.HIGHEST`` so they stay exact (``exact_precision``); every other
in-kernel dot (φ_e fc0/fc1 and the chain rule) follows the ambient
``jax_default_matmul_precision``, as the XLA aggregation paths do.

VMEM budgets are not estimated here — ``budget.py`` is the itemized,
unit-tested model (``tests/test_egnn_budget.py``), and ``ops.py`` plans or
validates every (block_e, block_h) against it before calling these.

``interpret=None`` auto-detects the backend (compiled on TPU, interpreter
mode elsewhere — CPU CI validates numerics, not timing).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.egnn_edge.budget import check_blocks
from repro.kernels.segment_sum.kernel import (accumulate_tile, gather_rows,
                                             resolve_interpret)


def _pad_h_blocks(nh, bh, H, w0i, w0j, w0d, b0, w1):
    """Zero-pad the h-block-tiled weight axes (fc0 output columns, fc1 rows)
    up to ``nh*bh``. Zero pad columns give z_pad = 0, silu(0) = 0, and the
    matching w1 pad rows are zero too — pad blocks contribute exactly
    nothing in either direction."""
    ph = nh * bh - H
    if ph == 0:
        return w0i, w0j, w0d, b0, w1
    col = ((0, 0), (0, ph))
    return (jnp.pad(w0i, col), jnp.pad(w0j, col), jnp.pad(w0d, col),
            jnp.pad(b0, col), jnp.pad(w1, ((0, ph), (0, 0))))


def _gather_edge_tile(src, dst, h, pos):
    """Endpoint rows of one edge tile by membership matmul; src/dst are
    (1, BE) rows. Pad edges (index >= A) gather zero rows and are kept out
    of every sum by the same sentinel downstream."""
    hi = gather_rows(src, h)                                  # (BE, H)
    hj = gather_rows(dst, h)
    diff = gather_rows(src, pos) - gather_rows(dst, pos)      # (BE, 3) f32
    return hi, hj, diff


def _fc0(hi, hj, d2, w0i, w0j, w0d, b0):
    """φ_e fc0, H-block slice j of the *virtual* concat [hi | hj | d2]: the
    weight arrives pre-split into its three row blocks (no (BE, 2H+1)
    tensor) and pre-tiled into its output columns (no (H, H) tile). The
    input-H contraction runs whole inside one f32-accumulated matmul pair
    (Mosaic needs a 32-bit accumulator). Forward and backward both call
    this, so the recomputed z rounds exactly as the forward's did."""
    cd = hi.dtype
    z = (jnp.dot(hi, w0i, preferred_element_type=jnp.float32)
         + jnp.dot(hj, w0j, preferred_element_type=jnp.float32))
    return z.astype(cd) + d2.astype(cd) * w0d + b0           # (BE, bh) cd


def _edge_kernel(src_ref, dst_ref, h_ref, pos_ref, w0i_ref, w0j_ref, w0d_ref,
                 b0_ref, w1_ref, b1_ref, o_ref, m_acc, acc_ref, *, ne, nh):
    je = pl.program_id(1)   # edge block (sequential)
    jh = pl.program_id(2)   # h-block (sequential inner)

    @pl.when((je == 0) & (jh == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    src = src_ref[0]                      # (1, BE) int32, >= A marks pad
    dst = dst_ref[0]
    h = h_ref[0]                          # (A, H) compute dtype
    pos = pos_ref[0].astype(jnp.float32)  # (A, 3)
    A = h.shape[0]
    cd = h.dtype

    hi, hj, diff = _gather_edge_tile(src, dst, h, pos)
    d2 = jnp.sum(diff ** 2, axis=-1, keepdims=True)           # (BE, 1) f32

    @pl.when(jh == 0)
    def _init_row():
        m_acc[...] = jnp.broadcast_to(
            b1_ref[...].astype(jnp.float32), m_acc.shape)

    z = _fc0(hi, hj, d2, w0i_ref[...], w0j_ref[...], w0d_ref[...],
             b0_ref[...])                                     # (BE, bh) cd
    # fc1 K-split: fold this h-block straight into the f32 message row
    # (SiLU in f32: Mosaic cannot lower a bf16 logistic)
    s = jax.nn.silu(z.astype(jnp.float32)).astype(cd)
    m_acc[...] += jnp.dot(s, w1_ref[...], preferred_element_type=jnp.float32)

    # membership matmul (MXU): pad edges carry dst >= A, which matches no
    # node-id row (shared scatter tile with repro.kernels.segment_sum)
    @pl.when(jh == nh - 1)
    def _aggregate():
        accumulate_tile(dst, m_acc[...], acc_ref, ib=0, bn=A)

    @pl.when((je == ne - 1) & (jh == nh - 1))
    def _flush():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_e", "block_h",
                                             "interpret"))
def egnn_edge_fused(h, pos, src, dst, w0i, w0j, w0d, b0, w1, b1, *,
                    block_e=256, block_h=256, interpret=None):
    """Fused forward. h: (B, A, H) compute-dtype node features; pos:
    (B, A, 3); src/dst: (B, E) int32 with A marking masked/pad edges
    (route them before calling — see ``ops.egnn_edge_agg``); φ_e fc0 weight
    pre-split into w0i (H,H), w0j (H,H), w0d (1,H), plus b0 (1,H), fc1
    w1 (H,H), b1 (1,H). ``block_h`` tiles the φ_e inner hidden axis (see
    module docstring) — ``ops.py`` plans it from the VMEM budget model.
    Returns (B, A, H) aggregated messages."""
    B, A, H = h.shape
    E = src.shape[1]
    be = min(block_e, E)
    ne = -(-E // be)
    bh = min(block_h, H)
    nh = -(-H // bh)
    # defense in depth: ops plans blocks, but a direct caller's override
    # must never compile over-budget (trace-time raise, shapes are static)
    check_blocks(A, E, H, be, bh, itemsize=h.dtype.itemsize)
    if ne * be != E:
        pe = ne * be - E
        # pad sentinel A: matches no node id, contributes nothing
        src = jnp.pad(src, ((0, 0), (0, pe)), constant_values=A)
        dst = jnp.pad(dst, ((0, 0), (0, pe)), constant_values=A)
    src = src.astype(jnp.int32)[:, None, :]
    dst = dst.astype(jnp.int32)[:, None, :]
    w0i, w0j, w0d, b0, w1 = _pad_h_blocks(nh, bh, H, w0i, w0j, w0d, b0, w1)

    kern = functools.partial(_edge_kernel, ne=ne, nh=nh)
    return pl.pallas_call(
        kern,
        grid=(B, ne, nh),
        in_specs=[
            pl.BlockSpec((1, 1, be), lambda b, je, jh: (b, 0, je)),  # src
            pl.BlockSpec((1, 1, be), lambda b, je, jh: (b, 0, je)),  # dst
            pl.BlockSpec((1, A, H), lambda b, je, jh: (b, 0, 0)),  # h
            pl.BlockSpec((1, A, 3), lambda b, je, jh: (b, 0, 0)),  # pos
            pl.BlockSpec((H, bh), lambda b, je, jh: (0, jh)),      # w0i
            pl.BlockSpec((H, bh), lambda b, je, jh: (0, jh)),      # w0j
            pl.BlockSpec((1, bh), lambda b, je, jh: (0, jh)),      # w0d
            pl.BlockSpec((1, bh), lambda b, je, jh: (0, jh)),      # b0
            pl.BlockSpec((bh, H), lambda b, je, jh: (jh, 0)),      # w1
            pl.BlockSpec((1, H), lambda b, je, jh: (0, 0)),        # b1
        ],
        out_specs=pl.BlockSpec((1, A, H), lambda b, je, jh: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, A, H), h.dtype),
        scratch_shapes=[pltpu.VMEM((be, H), jnp.float32),   # m_acc
                        pltpu.VMEM((A, H), jnp.float32)],   # node acc
        interpret=resolve_interpret(interpret),
    )(src, dst, h, pos, w0i, w0j, w0d, b0, w1, b1)


def _edge_bwd_kernel(src_ref, dst_ref, h_ref, pos_ref, g_ref,
                     w0i_ref, w0j_ref, w0d_ref, b0_ref, w1_ref,
                     dh_ref, dpos_ref, dw0i_ref, dw0j_ref, dw0d_ref,
                     db0_ref, dw1_ref, db1_ref,
                     acc_dh, acc_dpos, acc_w0i, acc_w0j, acc_w0d,
                     acc_b0, acc_w1, acc_b1, *, nb, ne, nh):
    b = pl.program_id(0)    # graph (outer)
    jh = pl.program_id(1)   # h-block (sequential middle)
    je = pl.program_id(2)   # edge block (sequential inner)

    @pl.when((jh == 0) & (je == 0))
    def _init_batch():
        acc_dh[...] = jnp.zeros_like(acc_dh)
        acc_dpos[...] = jnp.zeros_like(acc_dpos)

    @pl.when(je == 0)
    def _init_block_grads():
        # per-(graph, h-block) weight-grad accumulators: (H, bh)/(bh, H),
        # flushed into per-graph partials after this edge sweep — the
        # whole-H (H, H) scratch of the un-blocked kernel is gone
        acc_w0i[...] = jnp.zeros_like(acc_w0i)
        acc_w0j[...] = jnp.zeros_like(acc_w0j)
        acc_w0d[...] = jnp.zeros_like(acc_w0d)
        acc_b0[...] = jnp.zeros_like(acc_b0)
        acc_w1[...] = jnp.zeros_like(acc_w1)

    @pl.when((b == 0) & (jh == 0) & (je == 0))
    def _init_b1():
        acc_b1[...] = jnp.zeros_like(acc_b1)

    src = src_ref[0]                      # (1, BE) int32, >= A marks pad
    dst = dst_ref[0]
    h = h_ref[0]                          # (A, H) compute dtype
    pos = pos_ref[0].astype(jnp.float32)  # (A, 3)
    g = g_ref[0]                          # (A, H) upstream cotangent
    A = h.shape[0]
    cd = h.dtype

    # --- recompute this h-block's forward residuals for this edge tile
    # (nothing was saved edge-major in HBM; see the residual-recompute
    # contract in the module docstring). z_j is recomputed in the compute
    # dtype — identical dot shape and rounding to the forward kernel —
    # then the chain rule runs in f32.
    hi, hj, diff = _gather_edge_tile(src, dst, h, pos)
    d2f = jnp.sum(diff ** 2, axis=-1, keepdims=True)          # (BE, 1) f32
    z = _fc0(hi, hj, d2f, w0i_ref[...], w0j_ref[...], w0d_ref[...],
             b0_ref[...])                                     # (BE, bh) cd
    zf = z.astype(jnp.float32)
    sig = jax.nn.sigmoid(zf)
    s = zf * sig                                              # silu(z), f32

    # --- dm: gather of g at the destination, a zero row on masked/pad
    # edges (dst >= A). Every downstream cotangent is a product with dm (or
    # dz), so masked edges contribute exact zeros everywhere below.
    dm = gather_rows(dst, g.astype(jnp.float32))              # (BE, H)

    w1f = w1_ref[...].astype(jnp.float32)                     # (bh, H)
    ds = jax.lax.dot_general(dm, w1f, (((1,), (1,)), ((), ())))  # (BE, bh)
    dz = ds * (sig * (1.0 + zf * (1.0 - sig)))                # silu'(z)

    # --- node cotangents: this h-block's slice of the chain, scattered via
    # the shared membership-matmul tile (pad indices hit no row; their
    # cotangents are exact zeros anyway) and accumulated across ALL h-blocks
    # in the per-graph (A, H)/(A, 3) scratch
    w0if = w0i_ref[...].astype(jnp.float32)                   # (H, bh)
    w0jf = w0j_ref[...].astype(jnp.float32)
    w0df = w0d_ref[...].astype(jnp.float32)                   # (1, bh)
    dhi = jax.lax.dot_general(dz, w0if, (((1,), (1,)), ((), ())))  # (BE, H)
    dhj = jax.lax.dot_general(dz, w0jf, (((1,), (1,)), ((), ())))
    dd2 = jnp.sum(dz * w0df, axis=-1, keepdims=True)          # (BE, 1)
    ddiff = 2.0 * diff * dd2                                  # (BE, 3) = d xi
    accumulate_tile(src, dhi, acc_dh, ib=0, bn=A)
    accumulate_tile(dst, dhj, acc_dh, ib=0, bn=A)
    accumulate_tile(src, ddiff, acc_dpos, ib=0, bn=A)
    accumulate_tile(dst, -ddiff, acc_dpos, ib=0, bn=A)

    # --- φ_e weight cotangents, H-block slice: reduce over this edge tile
    hif = hi.astype(jnp.float32)
    hjf = hj.astype(jnp.float32)
    acc_w0i[...] += jax.lax.dot_general(hif, dz, (((0,), (0,)), ((), ())))
    acc_w0j[...] += jax.lax.dot_general(hjf, dz, (((0,), (0,)), ((), ())))
    acc_w0d[...] += jnp.sum(dz * d2f, axis=0, keepdims=True)
    acc_b0[...] += jnp.sum(dz, axis=0, keepdims=True)
    acc_w1[...] += jax.lax.dot_general(s, dm, (((0,), (0,)), ((), ())))

    @pl.when(jh == 0)
    def _acc_b1():
        # db1 = Σ dm is h-block-independent: reduce it exactly once
        acc_b1[...] += jnp.sum(dm, axis=0, keepdims=True)

    @pl.when(je == ne - 1)
    def _flush_block_grads():
        dw0i_ref[0] = acc_w0i[...]
        dw0j_ref[0] = acc_w0j[...]
        dw0d_ref[0] = acc_w0d[...]
        db0_ref[0] = acc_b0[...]
        dw1_ref[0] = acc_w1[...]

    @pl.when((jh == nh - 1) & (je == ne - 1))
    def _flush_batch():
        dh_ref[0] = acc_dh[...].astype(dh_ref.dtype)
        dpos_ref[0] = acc_dpos[...].astype(dpos_ref.dtype)

    @pl.when((b == nb - 1) & (jh == nh - 1) & (je == ne - 1))
    def _flush_b1():
        db1_ref[...] = acc_b1[...]


@functools.partial(jax.jit, static_argnames=("block_e", "block_h",
                                             "interpret"))
def egnn_edge_fused_bwd(g, h, pos, src, dst, w0i, w0j, w0d, b0, w1, *,
                        block_e=256, block_h=256, interpret=None):
    """Fused backward. Inputs mirror ``egnn_edge_fused`` (same routed
    src/dst with the >= A pad sentinel) plus ``g``, the (B, A, H) cotangent
    of the aggregated output. The forward's edge-major intermediates are
    recomputed H-block-by-H-block in VMEM — no (B, E, 2H+1) concat, no
    (B, E, H) message tensor, and no (H, H) weight-grad scratch.

    Returns ``(dh, dpos, dw0i, dw0j, dw0d, db0, dw1, db1)``:
    dh (B, A, H) in h.dtype; dpos (B, A, 3) f32; the φ_e cotangents in f32
    (split row blocks, biases as (1, H) rows — ``ops._edge_agg_bwd``
    reassembles the param dict and casts to the param dtypes). The kernel
    emits the weight grads as per-graph H-block partials; the trailing
    ``sum(axis=0)`` over B here is the only out-of-kernel reduction."""
    B, A, H = h.shape
    E = src.shape[1]
    be = min(block_e, E)
    ne = -(-E // be)
    bh = min(block_h, H)
    nh = -(-H // bh)
    check_blocks(A, E, H, be, bh, itemsize=h.dtype.itemsize)
    Hp = nh * bh
    if ne * be != E:
        pe = ne * be - E
        src = jnp.pad(src, ((0, 0), (0, pe)), constant_values=A)
        dst = jnp.pad(dst, ((0, 0), (0, pe)), constant_values=A)
    src = src.astype(jnp.int32)[:, None, :]
    dst = dst.astype(jnp.int32)[:, None, :]
    w0i, w0j, w0d, b0, w1 = _pad_h_blocks(nh, bh, H, w0i, w0j, w0d, b0, w1)

    kern = functools.partial(_edge_bwd_kernel, nb=B, ne=ne, nh=nh)
    out_shape = [
        jax.ShapeDtypeStruct((B, A, H), h.dtype),          # dh
        jax.ShapeDtypeStruct((B, A, 3), jnp.float32),      # dpos
        jax.ShapeDtypeStruct((B, H, Hp), jnp.float32),     # dw0i partials
        jax.ShapeDtypeStruct((B, H, Hp), jnp.float32),     # dw0j partials
        jax.ShapeDtypeStruct((B, 1, Hp), jnp.float32),     # dw0d partials
        jax.ShapeDtypeStruct((B, 1, Hp), jnp.float32),     # db0 partials
        jax.ShapeDtypeStruct((B, Hp, H), jnp.float32),     # dw1 partials
        jax.ShapeDtypeStruct((1, H), jnp.float32),         # db1
    ]
    dh, dpos, dw0i_p, dw0j_p, dw0d_p, db0_p, dw1_p, db1 = pl.pallas_call(
        kern,
        grid=(B, nh, ne),
        in_specs=[
            pl.BlockSpec((1, 1, be), lambda b, jh, je: (b, 0, je)),  # src
            pl.BlockSpec((1, 1, be), lambda b, jh, je: (b, 0, je)),  # dst
            pl.BlockSpec((1, A, H), lambda b, jh, je: (b, 0, 0)),  # h
            pl.BlockSpec((1, A, 3), lambda b, jh, je: (b, 0, 0)),  # pos
            pl.BlockSpec((1, A, H), lambda b, jh, je: (b, 0, 0)),  # g
            pl.BlockSpec((H, bh), lambda b, jh, je: (0, jh)),      # w0i
            pl.BlockSpec((H, bh), lambda b, jh, je: (0, jh)),      # w0j
            pl.BlockSpec((1, bh), lambda b, jh, je: (0, jh)),      # w0d
            pl.BlockSpec((1, bh), lambda b, jh, je: (0, jh)),      # b0
            pl.BlockSpec((bh, H), lambda b, jh, je: (jh, 0)),      # w1
        ],
        out_specs=[
            pl.BlockSpec((1, A, H), lambda b, jh, je: (b, 0, 0)),
            pl.BlockSpec((1, A, 3), lambda b, jh, je: (b, 0, 0)),
            pl.BlockSpec((1, H, bh), lambda b, jh, je: (b, 0, jh)),
            pl.BlockSpec((1, H, bh), lambda b, jh, je: (b, 0, jh)),
            pl.BlockSpec((1, 1, bh), lambda b, jh, je: (b, 0, jh)),
            pl.BlockSpec((1, 1, bh), lambda b, jh, je: (b, 0, jh)),
            pl.BlockSpec((1, bh, H), lambda b, jh, je: (b, jh, 0)),
            pl.BlockSpec((1, H), lambda b, jh, je: (0, 0)),
        ],
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((A, H), jnp.float32),    # acc_dh
            pltpu.VMEM((A, 3), jnp.float32),    # acc_dpos
            pltpu.VMEM((H, bh), jnp.float32),   # acc_w0i (per h-block)
            pltpu.VMEM((H, bh), jnp.float32),   # acc_w0j (per h-block)
            pltpu.VMEM((1, bh), jnp.float32),   # acc_w0d (per h-block)
            pltpu.VMEM((1, bh), jnp.float32),   # acc_b0  (per h-block)
            pltpu.VMEM((bh, H), jnp.float32),   # acc_w1  (per h-block)
            pltpu.VMEM((1, H), jnp.float32),    # acc_b1
        ],
        interpret=resolve_interpret(interpret),
    )(src, dst, h, pos, g, w0i, w0j, w0d, b0, w1)
    # sum the per-graph partials and drop the zero-padded h-block columns —
    # the only reduction that happens outside the kernel
    return (dh, dpos,
            dw0i_p.sum(axis=0)[:, :H], dw0j_p.sum(axis=0)[:, :H],
            dw0d_p.sum(axis=0)[:, :H], db0_p.sum(axis=0)[:, :H],
            dw1_p.sum(axis=0)[:H], db1)
