"""Hot-path parity suite (deterministic, fixed seeds: the always-on
coverage for the aggregation kernels, beside the hypothesis properties in
tests/test_kernels_segment.py):

  * one-hot ("jnp") vs scatter-add vs batched Pallas segment-sum agree to
    fp32 tolerance on batched shapes with pad edges AND pad nodes;
  * the batched Pallas entry point matches per-graph ``segment_sum_2d``;
  * the fused EGNN edge kernel matches its pure-jnp ``ref.py`` and, through
    ``egnn_apply``, the unfused model path — forward and gradients.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import Literal

from repro.configs.base import ArchConfig
from repro.data.synthetic_atoms import generate_all, to_batch_dict
from repro.kernels.egnn_edge import ops as edge_ops
from repro.kernels.egnn_edge.ref import egnn_edge_agg_ref
from repro.kernels.segment_sum import ops as ss_ops
from repro.kernels.segment_sum.kernel import segment_sum_2d, segment_sum_batched
from repro.models import gnn


def _case(B, E, A, F, seed=0, mask_p=0.7):
    """Random batched segment-sum inputs with pad edges (dst == A sentinel)
    and masked edges."""
    k0, k1, k2 = jax.random.split(jax.random.PRNGKey(seed), 3)
    msg = jax.random.normal(k0, (B, E, F), jnp.float32)
    dst = jax.random.randint(k1, (B, E), 0, A + 1)     # A = pad sentinel
    em = jax.random.bernoulli(k2, mask_p, (B, E)) & (dst < A)
    return msg, dst, em


@pytest.mark.parametrize("B,E,A,F,bn,be", [
    (2, 64, 16, 8, 8, 16),
    (3, 300, 33, 48, 16, 64),     # ragged E and A vs blocks
    (1, 128, 128, 128, 128, 128),
    (2, 7, 3, 5, 8, 8),           # blocks larger than the problem
])
def test_segment_sum_impl_parity(B, E, A, F, bn, be):
    msg, dst, em = _case(B, E, A, F)
    ref = gnn.segment_sum_nodes(msg, dst, A, edge_mask=em, impl="jnp")
    sc = gnn.segment_sum_nodes(msg, dst, A, edge_mask=em, impl="scatter")
    pl = ss_ops.segment_sum(msg, dst, A, edge_mask=em, block_n=bn, block_e=be)
    np.testing.assert_allclose(np.asarray(sc), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(pl), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_segment_sum_batched_matches_2d():
    msg, dst, em = _case(3, 100, 17, 12, seed=1)
    d = jnp.where(em, dst, 17)
    got = segment_sum_batched(msg, d, 17, block_n=8, block_e=32)
    per_graph = jnp.stack([
        segment_sum_2d(msg[i], d[i], 17, block_n=8, block_e=32)
        for i in range(3)])
    np.testing.assert_allclose(np.asarray(got), np.asarray(per_graph),
                               atol=1e-6, rtol=1e-6)


def test_segment_sum_rejects_bad_rank_and_blocks():
    msg, dst, em = _case(2, 16, 4, 4)
    with pytest.raises(ValueError, match="ndim"):
        ss_ops.segment_sum(msg[:, :, :, None], dst, 4, edge_mask=em)
    with pytest.raises(ValueError, match="block"):
        segment_sum_batched(msg, dst, 4, block_n=0)
    with pytest.raises(ValueError, match="impl"):
        gnn.segment_sum_nodes(msg, dst, 4, edge_mask=em, impl="nope")


def test_scatter_drops_all_pad_contributions():
    """Every masked/pad edge contributes exactly nothing (mass check)."""
    msg, dst, em = _case(2, 50, 9, 6, seed=2, mask_p=0.5)
    out = gnn.segment_sum_nodes(msg, dst, 9, edge_mask=em, impl="scatter")
    expect = jnp.where(em[..., None], msg, 0.0).sum(1)
    np.testing.assert_allclose(np.asarray(out.sum(1)), np.asarray(expect),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# fused edge kernel
# ---------------------------------------------------------------------------

def _gfm_cfg(**kw):
    base = dict(name="g", family="gnn", gnn_hidden=24, gnn_layers=2,
                n_species=64, head_hidden=12, head_layers=2, max_atoms=10,
                max_edges=40, remat=False, compute_dtype=jnp.float32)
    base.update(kw)
    return ArchConfig(**base)


def _gfm_batch(cfg, n=4, seed=0):
    data = generate_all(n, max_atoms=cfg.max_atoms, max_edges=cfg.max_edges,
                        seed=seed, sources=["ani1x"])
    return to_batch_dict(data["ani1x"], np.arange(n))


@pytest.mark.parametrize("block_e", [16, 40, 64])   # ragged/oversized blocks
def test_fused_edge_kernel_matches_ref(block_e):
    cfg = _gfm_cfg()
    batch = _gfm_batch(cfg)
    params = gnn.egnn_init(jax.random.PRNGKey(0), cfg)
    phi_e = params["layer0"]["phi_e"]
    h = gnn.embed(params["embed"], batch["species"], jnp.float32) \
        * batch["node_mask"][..., None]
    pos = batch["pos"]
    ref = egnn_edge_agg_ref(h, pos, batch["edge_src"], batch["edge_dst"],
                            batch["edge_mask"], phi_e)
    got = edge_ops.egnn_edge_agg(h, pos, batch["edge_src"],
                                 batch["edge_dst"], batch["edge_mask"],
                                 phi_e, block_e=block_e)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def _pad_batch(cfg, B=3, A=7, E=24, seed=0):
    """Hand-made batch with pad atoms, pad edges at the sentinel A, and a
    last graph with atoms but no real edge."""
    rng = np.random.default_rng(seed)
    n_atoms, n_edges = [5, 3, 2], [14, 4, 0]
    species = np.zeros((B, A), np.int32)
    pos = np.zeros((B, A, 3), np.float32)
    src = np.full((B, E), A, np.int32)
    dst = np.full((B, E), A, np.int32)
    for b, (na, ne) in enumerate(zip(n_atoms, n_edges)):
        species[b, :na] = rng.integers(1, cfg.n_species, na)
        pos[b, :na] = rng.normal(0.0, 1.5, (na, 3))
        src[b, :ne] = rng.integers(0, na, ne)
        dst[b, :ne] = rng.integers(0, na, ne)
    return {"species": jnp.asarray(species), "pos": jnp.asarray(pos),
            "edge_src": jnp.asarray(src), "edge_dst": jnp.asarray(dst),
            "node_mask": jnp.asarray(species > 0),
            "edge_mask": jnp.asarray(src < A)}


def _egnn_concat_oracle(params, batch, cfg):
    """egnn_apply with φ_e on the materialized (B, E, 2H+1) concat: each
    layer's message through ``egnn_edge_agg_ref``."""
    from repro.models.mlp import mlp_apply
    cd = cfg.compute_dtype
    nm = batch["node_mask"][..., None].astype(cd)
    h = gnn.embed(params["embed"], batch["species"], cd) * nm
    for i in range(cfg.gnn_layers):
        lp = params[f"layer{i}"]
        agg = egnn_edge_agg_ref(h, batch["pos"], batch["edge_src"],
                                batch["edge_dst"], batch["edge_mask"],
                                lp["phi_e"], compute_dtype=cd)
        upd = mlp_apply(lp["phi_h"], jnp.concatenate([h, agg], -1), "silu",
                        cd)
        h = (h + upd) * nm
    return h


@pytest.mark.parametrize("case", ["synthetic", "pads"])
@pytest.mark.parametrize("impl", ["scatter", "jnp", "pallas", "fused"])
def test_egnn_apply_all_impls_agree(impl, case):
    """Every impl matches the concat-form oracle in float32, forward and in
    the gradients with respect to the parameters and the positions."""
    cfg = _gfm_cfg()
    batch = _gfm_batch(cfg) if case == "synthetic" else _pad_batch(cfg)
    params = gnn.egnn_init(jax.random.PRNGKey(1), cfg)
    ref = _egnn_concat_oracle(params, batch, cfg)
    got = gnn.egnn_apply(params, batch, cfg=cfg, impl=impl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5, err_msg=impl)
    probe = jax.random.normal(jax.random.PRNGKey(5), ref.shape, jnp.float32)

    def loss(fn, p, pos):
        return jnp.sum(fn(p, {**batch, "pos": pos}) * probe)

    g_ref = jax.grad(lambda p, x: loss(
        lambda pp, bb: _egnn_concat_oracle(pp, bb, cfg), p, x),
        argnums=(0, 1))(params, batch["pos"])
    g_got = jax.grad(lambda p, x: loss(
        lambda pp, bb: gnn.egnn_apply(pp, bb, cfg=cfg, impl=impl), p, x),
        argnums=(0, 1))(params, batch["pos"])
    jax.tree_util.tree_map(
        lambda a, b: _assert_close_scaled(a, b, 1e-5, impl), g_got, g_ref)


@pytest.mark.parametrize("impl", ["scatter", "jnp", "pallas"])
def test_message_agg_matches_concat_ref(impl):
    """The node-side fc0 path of one layer against the concat-form oracle,
    forward and gradients with respect to h, pos and φ_e, with masked edges,
    sentinel edges and a graph with no real edge."""
    h, pos, src, dst, em, phi_e, gw = _paper_case(B=3, E=96, A=16, H=32)
    em = em.at[2].set(False)

    def loss(fn, hh, pp, ww):
        out = fn(hh, pp, src, dst, em, ww, compute_dtype=jnp.float32)
        return jnp.sum(out * gw), out

    def ours(*a, **kw):
        return gnn.message_agg(*a, impl=impl, **kw)

    (_, got), g_got = jax.value_and_grad(
        lambda *a: loss(ours, *a), argnums=(0, 1, 2), has_aux=True)(
            h, pos, phi_e)
    (_, ref), g_ref = jax.value_and_grad(
        lambda *a: loss(egnn_edge_agg_ref, *a), argnums=(0, 1, 2),
        has_aux=True)(h, pos, phi_e)
    _assert_close_scaled(got, ref, 1e-5, "forward")
    np.testing.assert_array_equal(np.asarray(got[2]), 0.0)
    for n, a, b in zip(("d_h", "d_pos", "d_phi_e"), g_got, g_ref):
        jax.tree_util.tree_map(
            lambda x, y, n=n: _assert_close_scaled(x, y, 1e-5, n), a, b)


def _jaxpr_eqns(jaxpr):
    """Every equation of a jaxpr, those of its nested jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _jaxpr_eqns(inner)


def test_phi_e_fc0_runs_on_the_atoms():
    """The scatter path never forms a (.., 2H+1) value, forward or backward,
    and φ_e's fc0 row blocks multiply (B, A, H) node rows, not edge rows."""
    H, L = 8, 2
    cfg = _gfm_cfg(gnn_hidden=H, gnn_layers=L)
    batch = _pad_batch(cfg)
    B, A = batch["species"].shape
    params = gnn.egnn_init(jax.random.PRNGKey(0), cfg)

    def fwd(p):
        return gnn.egnn_apply(p, batch, cfg=cfg, impl="scatter")

    for fn in (fwd, jax.grad(lambda p: jnp.sum(fwd(p) ** 2))):
        closed = jax.make_jaxpr(fn)(params)
        shapes = [getattr(v.aval, "shape", ()) for e in
                  _jaxpr_eqns(closed.jaxpr) for v in e.outvars]
        assert not [s for s in shapes if s and s[-1] == 2 * H + 1]

    closed = jax.make_jaxpr(fwd)(params)
    leaves, _ = jax.tree_util.tree_flatten_with_path(params)
    fc0 = {v for (path, _), v in zip(leaves, closed.jaxpr.invars)
           if "phi_e" in jax.tree_util.keystr(path)
           and "fc0" in jax.tree_util.keystr(path)
           and "'w'" in jax.tree_util.keystr(path)}
    assert len(fc0) == L
    lhs_shapes = []
    for eqn in _jaxpr_eqns(closed.jaxpr):
        if any(v in fc0 for v in eqn.invars if not isinstance(v, Literal)):
            if eqn.primitive.name == "dot_general":
                lhs_shapes.append(eqn.invars[0].aval.shape)
            elif eqn.primitive.name in ("slice", "convert_element_type"):
                fc0.update(eqn.outvars)     # a row block of fc0.w
    assert lhs_shapes == [(B, A, H)] * (2 * L)


def _paper_case(B=4, E=768, A=128, H=256, dtype=jnp.float32, seed=0):
    """Paper-shaped kernel inputs (ISSUE-3 acceptance: B=4, E=768, A=128,
    F=256) with masked AND sentinel-padded (dst == A) edges."""
    from repro.models.mlp import mlp_init
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    h = jax.random.normal(ks[0], (B, A, H), dtype)
    pos = jax.random.normal(ks[1], (B, A, 3), jnp.float32) * 2.0
    src = jax.random.randint(ks[2], (B, E), 0, A)
    dst = jax.random.randint(ks[3], (B, E), 0, A + 1)      # A = pad sentinel
    em = jax.random.bernoulli(ks[4], 0.85, (B, E)) & (dst < A)
    phi_e = mlp_init(ks[5], 2 * H + 1, H, H, 1, jnp.float32)
    gw = jax.random.normal(ks[6], (B, A, H), jnp.float32)  # cotangent probe
    return h, pos, src, dst, em, phi_e, gw


def _assert_close_scaled(got, ref, tol, name=""):
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    scale = max(1.0, float(np.abs(ref).max()))
    np.testing.assert_allclose(got, ref, atol=tol * scale, rtol=tol,
                               err_msg=name)


@pytest.mark.parametrize("dtype,tol", [
    (jnp.float32, 1e-5),       # ISSUE-3 acceptance: fp32 atol ≲ 1e-5
    (jnp.bfloat16, 4e-2),      # relaxed: bf16 forward-recompute rounding
])
def test_fused_bwd_matches_ref_at_paper_shapes(dtype, tol):
    """The fused backward kernel (d_h, d_x, φ_e weight grads) agrees with
    jax.grad through the pure-jnp reference at paper shapes, including
    masked and sentinel-padded edges."""
    h, pos, src, dst, em, phi_e, gw = _paper_case(dtype=dtype)

    def loss(fn, hh, pp, ww):
        out = fn(hh, pp, src, dst, em, ww, compute_dtype=dtype)
        return jnp.sum(out.astype(jnp.float32) * gw)

    g_fused = jax.grad(lambda *a: loss(edge_ops.egnn_edge_agg, *a),
                       argnums=(0, 1, 2))(h, pos, phi_e)
    g_ref = jax.grad(lambda *a: loss(egnn_edge_agg_ref, *a),
                     argnums=(0, 1, 2))(h, pos, phi_e)
    names = ("d_h", "d_pos", "d_phi_e")
    for n, a, b in zip(names, g_fused, g_ref):
        jax.tree_util.tree_map(
            lambda x, y, n=n: _assert_close_scaled(x, y, tol, n), a, b)
        # dtypes of the cotangents must match the primals exactly
        jax.tree_util.tree_map(
            lambda x, y: (x.dtype == y.dtype) or pytest.fail(
                f"cotangent dtype {x.dtype} != primal-grad {y.dtype}"), a, b)


def test_fused_bwd_ragged_edge_block():
    """block_e that does not divide E: the wrapper's sentinel padding must
    contribute exactly nothing to any cotangent."""
    h, pos, src, dst, em, phi_e, gw = _paper_case(B=2, E=100, A=16, H=32)

    def loss(block_e):
        def f(hh):
            out = edge_ops.egnn_edge_agg(hh, pos, src, dst, em, phi_e,
                                         block_e=block_e)
            return jnp.sum(out * gw)
        return jax.grad(f)(h)

    np.testing.assert_allclose(np.asarray(loss(64)), np.asarray(loss(128)),
                               atol=1e-6, rtol=1e-6)


def test_kernel_block_config_knob_threads_through():
    """cfg.kernel_block_e / kernel_block_n override the autotune heuristic
    for both the pallas segment-sum and the fused edge path without
    changing numerics."""
    cfg = _gfm_cfg()
    batch = _gfm_batch(cfg)
    params = gnn.egnn_init(jax.random.PRNGKey(4), cfg)
    ref = gnn.egnn_apply(params, batch, cfg=cfg, impl="jnp")
    tuned = cfg.replace(kernel_block_e=16, kernel_block_n=8)
    for impl in ("pallas", "fused"):
        got = gnn.egnn_apply(params, batch, cfg=tuned, impl=impl)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5, err_msg=impl)
    # and gradients still flow through the fused override path
    def loss(p, c):
        return jnp.mean(gnn.egnn_apply(p, batch, cfg=c, impl="fused") ** 2)
    g_t = jax.grad(lambda p: loss(p, tuned))(params)
    g_d = jax.grad(lambda p: loss(p, cfg))(params)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-4), g_t, g_d)


@pytest.mark.parametrize("impl", ["scatter", "pallas", "fused"])
def test_egnn_apply_grads_match_reference(impl):
    """The default, the Pallas segment-sum custom_vjp and the fused
    custom_vjp all differentiate like the one-hot reference — the train
    step is safe on every impl."""
    cfg = _gfm_cfg(gnn_layers=1)
    batch = _gfm_batch(cfg, seed=3)
    params = gnn.egnn_init(jax.random.PRNGKey(2), cfg)

    def loss(p, which):
        return jnp.mean(gnn.egnn_apply(p, batch, cfg=cfg, impl=which) ** 2)

    g_ref = jax.grad(lambda p: loss(p, "jnp"))(params)
    g_new = jax.grad(lambda p: loss(p, impl))(params)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-4), g_new, g_ref)
