"""The GFM Pallas kernels compile for a TPU v5e at paper width.

Interpret mode accepts tiles and ops the chip's compiler refuses (unaligned
index blocks, row gathers inside a kernel, bf16 accumulators), so these
tests compile each kernel for a described v5e chip with the TPU compiler
that ships in libtpu: no chip is attached and nothing runs. Each compiled
program must contain the kernel (``tpu_custom_call``).

The topology is described inside a fixture, never at import, so only the
worker that runs this file loads libtpu. JAX's persistent compilation cache
is off around these compiles: an entry written for a described chip cannot
be read back without one.
"""
import importlib.util

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.egnn_edge import ops as edge_ops
from repro.kernels.egnn_edge.budget import plan_blocks
from repro.kernels.segment_sum import ops as ss_ops
from repro.kernels.segment_sum.kernel import autotune_blocks
from repro.models.mlp import mlp_init

PAPER = dict(B=4, E=2048, A=64, H=866)     # hydragnn-gfm at its batch pad


@pytest.fixture(scope="module")
def topo():
    # skip only where the TPU compiler is not installed; any other failure
    # to describe the chip (a held libtpu lock, an API change) fails
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("libtpu is not installed: no TPU compiler to compile for")
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _shape(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("grad", [False, True], ids=["forward", "grad"])
def test_segment_sum_batched_compiles(one_chip, grad):
    B, E, A, H = PAPER.values()
    msg = _shape(one_chip, (B, E, H), jnp.float32)
    dst = _shape(one_chip, (B, E), jnp.int32)
    mask = _shape(one_chip, (B, E), jnp.bool_)

    def fwd(m, d, em):
        return ss_ops.segment_sum(m, d, A, edge_mask=em, interpret=False)

    # a loss that reads the output, so the grad program keeps the kernel
    fn = jax.grad(lambda m, d, em: jnp.sum(fwd(m, d, em) ** 2)) if grad \
        else fwd
    assert "tpu_custom_call" in _compiled_text(fn, msg, dst, mask)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("grad", [False, True], ids=["forward", "grad"])
def test_egnn_edge_agg_compiles(one_chip, grad, dtype):
    B, E, A, H = PAPER.values()
    phi = jax.eval_shape(
        lambda: mlp_init(jax.random.PRNGKey(0), 2 * H + 1, H, H, 1,
                         jnp.float32))
    phi = jax.tree_util.tree_map(
        lambda l: _shape(one_chip, l.shape, l.dtype), phi)
    args = (_shape(one_chip, (B, A, H), dtype),
            _shape(one_chip, (B, A, 3), jnp.float32),
            _shape(one_chip, (B, E), jnp.int32),
            _shape(one_chip, (B, E), jnp.int32),
            _shape(one_chip, (B, E), jnp.bool_), phi)

    def fwd(h, pos, src, dst, em, p):
        return edge_ops.egnn_edge_agg(h, pos, src, dst, em, p,
                                      compute_dtype=dtype, interpret=False)

    def grad_fn(h, pos, src, dst, em, p):
        return jax.grad(lambda hh, pp, ww: jnp.sum(
            fwd(hh, pp, src, dst, em, ww).astype(jnp.float32)),
            argnums=(0, 1, 2))(h, pos, p)

    assert "tpu_custom_call" in _compiled_text(grad_fn if grad else fwd,
                                               *args)


@pytest.mark.parametrize("A,E,H", [(64, 2048, 866), (128, 768, 866),
                                   (64, 200, 300)])
def test_planned_tiles_are_lane_aligned(A, E, H):
    """The planners only emit tiles the compiler accepts on a lane axis:
    a multiple of 128, or the whole axis."""
    be, bh = plan_blocks(A, E, H)
    bn, be_ss = autotune_blocks(A, E, H)
    for tile, dim in ((be, E), (bh, H), (be_ss, E)):
        assert tile % 128 == 0 or tile == dim, (tile, dim)
    assert bn % 8 == 0 or bn == A
