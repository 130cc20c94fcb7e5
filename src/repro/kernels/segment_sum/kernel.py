"""Segment-sum Pallas TPU kernel — the MPNN aggregation hot spot.

GPU frameworks implement scatter-add with atomics; TPU has none, so the
operation is re-thought for the MXU (the DESIGN.md "adapt, don't port" item):
tile (edges x nodes), build the one-hot membership tile in VMEM from the
destination-index block, and accumulate ``one_hot @ messages`` as a matmul.

Two entry points:

  * ``segment_sum_2d``      — one graph: (E, F) messages -> (n_nodes, F);
  * ``segment_sum_batched`` — padded graph batches: (B, E, F) -> (B, A, F)
    with the batch as the leading (parallel) grid dimension. This is what
    ``repro.models.gnn.segment_sum_nodes`` feeds; it replaces the old
    ``vmap(segment_sum_2d)`` lowering, which re-traced the kernel under the
    batching rule instead of expressing B as a grid axis.

Grid: (num_node_blocks, num_edge_blocks) — edge blocks are the sequential
inner dim; a VMEM f32 scratch accumulates the (BN, F) node tile and is
flushed on the last edge block. The batched kernel prepends B to the grid.

Pad-edge sentinel contract: edges whose destination must not contribute
(ragged-E padding added here, or masked edges routed by ``ops.segment_sum``)
carry a ``dst`` value ``>= n_nodes``. The kernel compares ``dst`` against
node ids ``0 .. num_node_blocks*BN - 1``; because the output is padded up to
``num_node_blocks*BN >= n_nodes`` rows and then sliced back to ``n_nodes``,
any ``dst`` in ``[n_nodes, num_node_blocks*BN)`` lands on a padded row that
is discarded, and any ``dst >= num_node_blocks*BN`` matches no row at all.
The internal ragged-E pad sentinel is ``num_node_blocks*BN + 1`` — strictly
above every node id a tile can generate (asserted below, not assumed).

VMEM budget at BN=128, BE=256, F=896: membership tile (128x256 f32) 128 KiB,
message tile (256x896 f32) 896 KiB, accumulator (128x896 f32) 448 KiB —
≈1.5 MiB resident.

``interpret=None`` (the default) auto-detects: the kernel runs compiled on
TPU backends and falls back to interpreter mode everywhere else (CPU CI).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def resolve_interpret(interpret) -> bool:
    """None -> interpret only off-TPU (compiled Mosaic path on TPU)."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def _block_geometry(n_nodes: int, E: int, block_n: int, block_e: int):
    """Clamp block sizes to the problem (explicitly — a ``block_n`` larger
    than ``n_nodes`` would otherwise pad every node tile with dead rows, and
    a ``block_e`` larger than ``E`` would pad every edge tile) and derive
    block counts + the ragged-E pad sentinel."""
    if block_n < 1 or block_e < 1:
        raise ValueError(f"block sizes must be >= 1, got block_n={block_n}, "
                         f"block_e={block_e}")
    bn = min(block_n, n_nodes)
    be = min(block_e, E)
    nb, ne = -(-n_nodes // bn), -(-E // be)
    sentinel = nb * bn + 1
    # the one-hot tile compares dst against node ids 0 .. nb*bn - 1; the
    # sentinel must exceed ALL of them or a pad edge would alias a real node
    assert sentinel > nb * bn - 1 and nb * bn >= n_nodes, \
        (sentinel, nb, bn, n_nodes)
    return bn, be, nb, ne, sentinel


def membership(idx, n, *, base=0):
    """(n, BE) f32 one-hot of a (1, BE) int32 index row: entry (r, e) is 1
    where ``idx[e] == base + r``. Indices outside ``base .. base+n-1`` (the
    pad sentinels) give an all-zero column. The row layout keeps the edge
    axis on lanes, so the tile is a sublane iota against a broadcast row."""
    rows = base + jax.lax.broadcasted_iota(jnp.int32, (n, idx.shape[-1]), 0)
    return (rows == idx).astype(jnp.float32)


def exact_precision(dtype):
    """Dot precision for the membership matmuls: a one-hot gather/scatter of
    f32 values must not round them through one bf16 MXU pass."""
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


def accumulate_tile(dst, msg, acc_ref, *, ib, bn):
    """One (edge-block x node-block) scatter tile: ``acc += onehot @ msg``
    on the MXU, with ``onehot = membership(dst, bn, base=ib*bn)``. This is
    the shared TPU replacement for scatter-add — used by both segment-sum
    entry points here and by the fused EGNN edge kernel's forward
    aggregation and backward ``d_h``/``d_x`` scatters
    (``repro.kernels.egnn_edge``). ``dst`` is a (1, BE) row; masking is by
    index, per the sentinel contract: any ``dst`` outside this tile's
    ``ib*bn .. ib*bn+bn-1`` id range contributes nothing."""
    onehot = membership(dst, bn, base=ib * bn)                # (BN, BE)
    acc_ref[...] += jnp.dot(onehot, msg,
                            precision=exact_precision(msg.dtype),
                            preferred_element_type=jnp.float32)


def gather_rows(idx, x):
    """Rows ``x[idx[e]]`` for a (1, BE) index row -> (BE, F): the transpose
    of ``accumulate_tile`` (``onehotᵀ @ x``). Mosaic cannot lower a
    row gather inside a kernel, the MXU can do it as a matmul. Sentinel
    indices ``>= x.shape[0]`` gather an all-zero row."""
    onehot = membership(idx, x.shape[0]).astype(x.dtype)     # (N, BE)
    rows = jax.lax.dot_general(onehot, x, (((0,), (0,)), ((), ())),
                               precision=exact_precision(x.dtype),
                               preferred_element_type=jnp.float32)
    return rows.astype(x.dtype)       # exact: one nonzero term per row


LANE = 128      # TPU lane width: an index row block is a multiple of it or
SUBLANE = 8     # the whole row; row blocks of a tile are multiples of 8
MAX_LANE_TILE = 256     # the MXU-native tile width the planners start from


def lane_tiles(dim: int) -> list[int]:
    """Block sizes the TPU compiler accepts on a lane (last) axis of length
    ``dim``, largest first, none above ``MAX_LANE_TILE``: the whole axis when
    it is no longer than that, then multiples of ``LANE``."""
    first = min(dim, MAX_LANE_TILE)
    return [first] + [t for t in range(MAX_LANE_TILE - LANE, 0, -LANE)
                      if t < first]


def autotune_blocks(n_nodes: int, E: int, F: int, *, extra_bytes: int = 0,
                    vmem_limit: int = 8 << 20) -> tuple[int, int]:
    """Heuristic (block_n, block_e) for the membership-matmul kernels: start
    from the MXU-native 128x256 tile and shrink ``block_e`` through the
    lane-aligned sizes (``lane_tiles``), then ``block_n`` by sublane
    multiples, until the resident f32 working set (node accumulator +
    message tile + membership tile, plus ``extra_bytes`` for caller-resident
    buffers) fits the VMEM budget. Raises ``ValueError`` when no aligned
    tile fits (wide F needs an F split this kernel does not have). Callers
    override via the ``kernel_block_n`` / ``kernel_block_e`` config knobs
    (``repro.configs.base.ArchConfig``)."""
    bn = max(SUBLANE, min(128, n_nodes))

    def resident(bn, be):
        return extra_bytes + 4 * (bn * F + be * F + be * bn)

    for be in lane_tiles(E):
        if resident(bn, be) <= vmem_limit:
            return bn, be
    be = lane_tiles(E)[-1]
    while bn > SUBLANE and resident(bn, be) > vmem_limit:
        bn = max(SUBLANE, bn // 2 // SUBLANE * SUBLANE)
    if resident(bn, be) > vmem_limit:
        raise ValueError(
            f"no lane-aligned segment-sum tile fits (n_nodes={n_nodes}, "
            f"E={E}, F={F}) in {vmem_limit / 2 ** 20:.1f} MiB of VMEM")
    return bn, be


def _ss_kernel(dst_ref, msg_ref, o_ref, acc_ref, *, bn, ne):
    ib = pl.program_id(0)   # node block
    je = pl.program_id(1)   # edge block (sequential)

    @pl.when(je == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    accumulate_tile(dst_ref[...], msg_ref[...].astype(jnp.float32),
                    acc_ref, ib=ib, bn=bn)       # dst block (1, BE)

    @pl.when(je == ne - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n_nodes", "block_n", "block_e",
                                             "interpret"))
def segment_sum_2d(messages, dst, n_nodes: int, *, block_n=128, block_e=256,
                   interpret=None):
    """messages: (E, F); dst: (E,) int32 in [0, n_nodes) or >= n_nodes for
    masked/pad edges (see the sentinel contract in the module docstring).
    Returns (n_nodes, F). The indices enter as a (1, E) row: the TPU
    compiler refuses a 1-D block that is not a multiple of 1024."""
    E, F = messages.shape
    bn, be, nb, ne, sentinel = _block_geometry(n_nodes, E, block_n, block_e)
    if ne * be != E:
        pe = ne * be - E
        messages = jnp.pad(messages, ((0, pe), (0, 0)))
        dst = jnp.pad(dst, (0, pe), constant_values=sentinel)
    dst = dst.astype(jnp.int32)[None, :]

    kern = functools.partial(_ss_kernel, bn=bn, ne=ne)
    out = pl.pallas_call(
        kern,
        grid=(nb, ne),
        in_specs=[
            pl.BlockSpec((1, be), lambda ib, je: (0, je)),
            pl.BlockSpec((be, F), lambda ib, je: (je, 0)),
        ],
        out_specs=pl.BlockSpec((bn, F), lambda ib, je: (ib, 0)),
        out_shape=jax.ShapeDtypeStruct((nb * bn, F), messages.dtype),
        scratch_shapes=[pltpu.VMEM((bn, F), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(dst, messages)
    return out[:n_nodes]


def _ss_batched_kernel(dst_ref, msg_ref, o_ref, acc_ref, *, bn, ne):
    ib = pl.program_id(1)   # node block
    je = pl.program_id(2)   # edge block (sequential inner dim)

    @pl.when(je == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    accumulate_tile(dst_ref[0], msg_ref[0].astype(jnp.float32),
                    acc_ref, ib=ib, bn=bn)       # dst block (1, 1, BE)

    @pl.when(je == ne - 1)
    def _flush():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n_nodes", "block_n", "block_e",
                                             "interpret"))
def segment_sum_batched(messages, dst, n_nodes: int, *, block_n=128,
                        block_e=256, interpret=None):
    """messages: (B, E, F); dst: (B, E) int32 in [0, n_nodes) or >= n_nodes
    for masked/pad edges. Returns (B, n_nodes, F). B is the leading
    (parallel) grid dimension — each graph reuses the same node/edge tiling
    as ``segment_sum_2d``. The indices enter as (B, 1, E) so an edge block
    is a lane-axis row the TPU compiler can tile."""
    B, E, F = messages.shape
    bn, be, nb, ne, sentinel = _block_geometry(n_nodes, E, block_n, block_e)
    if ne * be != E:
        pe = ne * be - E
        messages = jnp.pad(messages, ((0, 0), (0, pe), (0, 0)))
        dst = jnp.pad(dst, ((0, 0), (0, pe)), constant_values=sentinel)
    dst = dst.astype(jnp.int32)[:, None, :]

    kern = functools.partial(_ss_batched_kernel, bn=bn, ne=ne)
    out = pl.pallas_call(
        kern,
        grid=(B, nb, ne),
        in_specs=[
            pl.BlockSpec((1, 1, be), lambda b, ib, je: (b, 0, je)),
            pl.BlockSpec((1, be, F), lambda b, ib, je: (b, je, 0)),
        ],
        out_specs=pl.BlockSpec((1, bn, F), lambda b, ib, je: (b, ib, 0)),
        out_shape=jax.ShapeDtypeStruct((B, nb * bn, F), messages.dtype),
        scratch_shapes=[pltpu.VMEM((bn, F), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(dst, messages)
    return out[:, :n_nodes]
