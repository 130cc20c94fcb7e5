"""The program's own instrumentation, on the CPU: named scopes in the
compiled training step's metadata, the serve worker's spans in a
``jax.profiler`` trace, and the serving compile counter."""
import glob
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ArchConfig
from repro.core import MTPConfig, make_gfm_mtl
from repro.data.bucketing import BucketSpec
from repro.data.loader import GroupBatcher
from repro.data.synthetic_atoms import generate_all, generate_mixture, \
    source_dicts
from repro.engine import ShardingPlan, TrainState, make_step
from repro.optim import adamw
from repro.serve import ServeSession

CFG = ArchConfig(name="scopes", family="gnn", gnn_hidden=16, gnn_layers=2,
                 n_species=64, head_hidden=8, head_layers=2, remat=False,
                 compute_dtype=jnp.float32)
SERVE_STAGES = ("serve.poll", "serve.file", "serve.assemble",
                "serve.compute", "serve.dispatch", "serve.readback",
                "serve.scatter")


def _innermost_scopes(path):
    """Each component of an op_name path, stripped of transform wrappers
    such as ``transpose(jvp(...))``."""
    return [re.sub(r"^(?:\w+\()+", "", p).rstrip(")") for p in path.split("/")]


@pytest.fixture(scope="module")
def step_op_names():
    names = ["ani1x", "qm7x"]
    data = generate_all(8, max_atoms=10, max_edges=40, sources=names)
    sources = [dict(species=s.species, pos=s.pos, edge_src=s.edge_src,
                    edge_dst=s.edge_dst, node_mask=s.node_mask,
                    edge_mask=s.edge_mask, energy=s.energy, forces=s.forces)
               for s in data.values()]
    model = make_gfm_mtl(CFG, 2)
    opt = adamw(1e-3)
    plan = ShardingPlan(mtp=MTPConfig(n_tasks=2), donate=False)
    state = TrainState.create(model.init(jax.random.PRNGKey(0)), opt)
    batch = GroupBatcher(sources, 4).next_batch()
    step = plan.compile(make_step(model, opt, plan))
    text = step.lower(state, batch).compile().as_text()
    return re.findall(r'op_name="([^"]+)"', text)


@pytest.mark.parametrize("scope", ["embed", "message", "node_update",
                                   "heads", "loss"])
def test_compiled_step_carries_scope_forward_and_backward(step_op_names,
                                                          scope):
    """The optimized HLO's op_name metadata holds each model scope on
    forward ops and on backward ops (inside ``transpose(...)``)."""
    mine = [p for p in step_op_names if scope in _innermost_scopes(p)]
    assert any("transpose(" not in p for p in mine), (scope, mine[:5])
    assert any("transpose(" in p for p in mine), (scope, mine[:5])


def test_compiled_step_carries_optimizer_scope(step_op_names):
    assert any("optimizer" in _innermost_scopes(p) for p in step_op_names)


def test_message_scope_is_per_layer(step_op_names):
    layers = {p.split("/message")[0].rsplit("/", 1)[-1]
              for p in step_op_names if "/message" in p}
    assert {"layer0", "layer1"} <= layers, layers


@pytest.fixture(scope="module")
def served():
    sources = source_dicts(generate_mixture(40, max_atoms=16, max_edges=64))
    model = make_gfm_mtl(CFG, len(sources))
    return model.init(jax.random.PRNGKey(0)), sources


def _sample(sources, t, i):
    s = sources[t]
    i = i % s["species"].shape[0]
    return {k: s[k][i] for k in ("species", "pos", "edge_src", "edge_dst",
                                 "node_mask", "edge_mask")}


def test_serve_worker_spans_in_a_profiler_trace(served, tmp_path):
    """Every serve.* stage shows on one host line (the worker's), with one
    serve.dispatch per batch the engine ran."""
    from jax.profiler import ProfileData
    params, sources = served
    spec = BucketSpec((8, 16), (32, 64))
    with ServeSession(params, CFG, spec=spec, max_batch=2,
                      max_wait_ms=1.0) as srv:
        srv.warmup()
        jax.profiler.start_trace(str(tmp_path))
        try:
            futs = [srv.submit(_sample(sources, t, i), head=t)
                    for t in range(len(sources)) for i in range(3)]
            for f in futs:
                f.result(timeout=60)
            # the idle worker polls with a timeout of at most 50 ms: let a
            # few polls end inside the trace
            time.sleep(0.2)
        finally:
            jax.profiler.stop_trace()
        batches = srv.stats()["counters"]["batches"]
    [path] = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                       recursive=True)
    space = ProfileData.from_file(path)
    lines = [[ev.name for ev in line.events]
             for plane in space.planes if plane.name == "/host:CPU"
             for line in plane.lines]
    holding = [names for names in lines
               if any(n.startswith("serve.") for n in names)]
    assert len(holding) == 1, [sorted(set(n))[:8] for n in holding]
    [worker] = holding
    assert set(SERVE_STAGES) <= set(worker), sorted(set(worker))
    assert worker.count("serve.dispatch") == batches > 0


def test_recompile_of_a_warmed_shape_is_counted(served):
    """The compilations counter follows the jitted forward's own cache:
    another dtype on a warmed bucket shape compiles again, and counts."""
    params, sources = served
    spec = BucketSpec((16,), (64,))
    with ServeSession(params, CFG, spec=spec, max_batch=2) as srv:
        srv.warmup()
        warmed = srv.stats()["counters"]["compilations"]
        assert warmed == 1
        batch = {"species": np.zeros((2, 16), np.int32),
                 "pos": np.zeros((2, 16, 3), np.float16),
                 "edge_src": np.full((2, 64), 16, np.int32),
                 "edge_dst": np.full((2, 64), 16, np.int32),
                 "node_mask": np.zeros((2, 16), bool),
                 "edge_mask": np.zeros((2, 64), bool)}
        jax.block_until_ready(srv._executable((16, 64), 0)(batch))
        assert srv.stats()["counters"]["compilations"] == warmed + 1
        # the same call again hits the cache
        jax.block_until_ready(srv._executable((16, 64), 1)(batch))
        assert srv.stats()["counters"]["compilations"] == warmed + 1
