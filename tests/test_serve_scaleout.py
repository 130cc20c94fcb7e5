"""Multi-device serving scale-out (ISSUE 10).

Two layers:

  * in-process (1 device): ReplicaScheduler routing/failover invariants,
    AdaptivePolicy knee movement, serve_batch_spec, and the full
    ReplicaServeSession lifecycle (parity, failover, shed, close) with
    mesh-less replicas sharing the host device;
  * subprocess (8 forced host devices, the test_parallel_parity pattern):
    per-replica BITWISE row parity vs the plain single-device
    ``predict_one``, sharded-forward parity, compile-budget assertions
    (``shapes x plans``), per-replica param placement, and close/drain
    semantics under the replica workers.
"""
import json
import os
import subprocess
import sys
import textwrap
import time
from concurrent.futures import Future

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.mtl import make_gfm_mtl
from repro.data.bucketing import BucketSpec
from repro.data.synthetic_atoms import generate_mixture, source_dicts
from repro.serve import (AdaptivePolicy, ReplicaScheduler,
                         ReplicaServeSession, ServeClosedError,
                         SizeBinnedBatcher)
from repro.serve.queue import DeadlineExceededError, Request, _as_sample

CFG = ArchConfig(name="scaleout-test", family="gnn", gnn_hidden=16,
                 gnn_layers=2, n_species=64, head_hidden=8, head_layers=2,
                 remat=False, compute_dtype=jnp.float32)
SPEC = BucketSpec((8, 16), (32, 64))


class FakeClock:
    """Deterministic injectable clock (same base for every component)."""

    def __init__(self, t0: float = 1e6):
        self.t = float(t0)

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float):
        self.t += dt


@pytest.fixture(scope="module")
def served():
    sources = source_dicts(generate_mixture(40, max_atoms=16, max_edges=64))
    model = make_gfm_mtl(CFG, len(sources))
    params = model.init(jax.random.PRNGKey(0))
    return params, sources


def _sample(sources, t, i=0):
    s = sources[t]
    i = i % s["species"].shape[0]
    return {k: s[k][i] for k in ("species", "pos", "edge_src", "edge_dst",
                                 "node_mask", "edge_mask")}


def _request(sources, t=0, i=0, t_submit=0.0, head=0):
    canon, n_atoms, n_edges = _as_sample(_sample(sources, t, i))
    return Request(sample=canon, head=head,
                   bucket=SPEC.bucket_for(n_atoms, n_edges),
                   n_atoms=n_atoms, n_edges=n_edges, future=Future(),
                   t_submit=t_submit)


# ---------------------------------------------------------------------------
# ReplicaScheduler: sticky least-loaded routing
# ---------------------------------------------------------------------------

def test_scheduler_sticks_to_one_replica_while_a_bin_fills():
    s = ReplicaScheduler(4, max_batch=3)
    key = ((8, 32), 0)
    first = [s.route(key) for _ in range(3)]
    assert len(set(first)) == 1            # one bin, one replica
    # bin full: the 4th route re-picks least-loaded — a DIFFERENT replica,
    # since the first still holds 3 outstanding
    assert s.route(key) != first[0]


def test_scheduler_routes_to_least_loaded():
    s = ReplicaScheduler(3, max_batch=8)
    r0 = s.route(((8, 32), 0))
    r1 = s.route(((8, 32), 1))             # fresh key: avoids loaded r0
    assert r1 != r0
    s.complete(r0)                         # r0's request resolved
    assert s.outstanding[r0] == 0
    r2 = s.route(((16, 64), 2))
    assert r2 == r0                        # back to the now-idle replica


def test_scheduler_failover_and_all_dead():
    s = ReplicaScheduler(2, max_batch=4)
    key = ((8, 32), 0)
    r = s.route(key)
    s.fail(r)                              # put() failed: dead + released
    assert s.outstanding[r] == 0 and r in s.dead
    r2 = s.route(key)                      # sticky entry dropped, re-routed
    assert r2 != r
    s.fail(r2)
    with pytest.raises(ServeClosedError, match="dead"):
        s.route(key)
    s.revive(r)
    assert s.route(key) == r


# ---------------------------------------------------------------------------
# AdaptivePolicy: the knee moves with the measured rate
# ---------------------------------------------------------------------------

def test_adaptive_policy_moves_the_knee():
    p = AdaptivePolicy(max_batch=8, max_wait=0.005, min_wait=2e-4)
    key = ((8, 32), 0)
    # no estimate yet: fixed knobs
    assert p.target_rows(key) == 8 and p.wait(key) == 0.005
    # saturating arrivals (0.5 ms apart): wait for a fillable bin
    for k in range(20):
        p.observe_arrival(key, t=k * 5e-4)
    assert p.target_rows(key) == 8
    assert 0 < p.wait(key) <= 0.005
    # starved arrivals (50 ms apart): nothing else is coming — release fast
    slow = ((16, 64), 1)
    for k in range(20):
        p.observe_arrival(slow, t=k * 0.05)
    assert p.target_rows(slow) == 1
    assert p.wait(slow) == 2e-4
    snap = p.snapshot()
    assert snap[repr(slow)]["target_rows"] == 1


def test_adaptive_batcher_releases_lone_requests_early(served):
    """Once the policy has measured a starved key, a lone request releases
    on add() (target 1) instead of burning the full max_wait."""
    _, sources = served
    fc = FakeClock()
    pol = AdaptivePolicy(max_batch=8, max_wait=0.005)
    b = SizeBinnedBatcher(max_batch=8, max_wait=0.005, clock=fc, policy=pol)
    # prime the rate estimate: two arrivals 50 ms apart fill + release
    for k in range(2):
        ab = b.add(_request(sources, t_submit=fc()))
        if ab is None:
            fc.advance(1.0)
            released = b.expired()
            assert len(released) == 1
        fc.advance(0.05)
    ab = b.add(_request(sources, t_submit=fc()))
    assert ab is not None and ab.n_real == 1   # released immediately
    # the padded shape is still the STATIC max_batch (compile budget safe)
    assert ab.batch["species"].shape[0] == 8


# ---------------------------------------------------------------------------
# sharding rule + replica meshes on a 1-device host
# ---------------------------------------------------------------------------

def test_serve_batch_spec_rows_or_replicate():
    from jax.sharding import PartitionSpec as P

    from repro.configs.sharding import serve_batch_spec
    leaf = np.zeros((8, 4, 3))
    assert serve_batch_spec(leaf, 4) == P("data", None, None)
    assert serve_batch_spec(leaf, 3) == P(None, None, None)  # uneven: replicate
    assert serve_batch_spec(np.zeros(()), 2) == P()


def test_make_replica_meshes_partitions_the_pool():
    from repro.launch.mesh import make_replica_meshes
    meshes = make_replica_meshes(1)
    assert len(meshes) == 1 and meshes[0].shape == {"data": 1}
    if jax.device_count() < 2:
        with pytest.raises(AssertionError, match="devices"):
            make_replica_meshes(2)


def test_session_on_a_one_device_mesh_serves(served):
    """mesh= with a single device degenerates to device pinning — the
    replica building block. (The uneven-max_batch rejection needs >1
    device; the subprocess suite asserts it.)"""
    params, sources = served
    from repro.launch.mesh import make_replica_meshes
    from repro.serve import ServeSession
    mesh = make_replica_meshes(1)[0]
    with ServeSession(params, CFG, spec=SPEC, max_batch=3,
                      mesh=mesh) as srv:
        sm = _sample(sources, 0)
        got = srv.submit(sm, head=0).result(timeout=60)
        ref = srv.predict_one(sm, head=0)
        assert got["energy"] == ref["energy"]
        np.testing.assert_array_equal(got["forces"], ref["forces"])
        assert srv.stats()["plan"] == {"mode": "single", "devices": 1}


# ---------------------------------------------------------------------------
# ReplicaServeSession lifecycle (mesh-less replicas, one host device)
# ---------------------------------------------------------------------------

def test_replica_session_parity_and_routing(served):
    params, sources = served
    with ReplicaServeSession(params, CFG, meshes=[None, None], spec=SPEC,
                             max_batch=4, max_wait_ms=2.0) as srv:
        jobs = [(t, _sample(sources, t, i))
                for t in range(3) for i in range(3)]
        futs = [(t, sm, srv.submit(sm, head=t)) for t, sm in jobs]
        for t, sm, fut in futs:
            got = fut.result(timeout=60)
            ref = srv.predict_one(sm, head=t)
            assert got["energy"] == ref["energy"]
            np.testing.assert_array_equal(got["forces"], ref["forces"])
        st = srv.stats()
        assert st["counters"]["routed"] == len(jobs)
        assert st["plan"]["mode"] == "replica"
        assert st["executable_cache"]["compiled_shapes"] <= \
            st["executable_cache"]["compile_budget"] \
            == SPEC.n_shapes * 2


def _crash_replica(srv, r, sm):
    """Crash replica ``r`` deterministically (the resilience-test pattern):
    its next batcher.add raises, the worker fail-fast handler closes its
    queue. Blocks until the queue is observably closed."""
    def boom(req):
        raise RuntimeError("injected replica fault")
    srv.replicas[r].batcher.add = boom
    # route one trigger request at the doomed replica: it is the sticky /
    # least-loaded pick for a fresh key, and its future must FAIL (the
    # crash handler resolves everything the dead worker held)
    fut = srv.submit(sm, head=r % srv.n_heads)
    assert isinstance(fut.exception(timeout=60), RuntimeError)
    deadline = time.monotonic() + 10.0
    while not srv.replicas[r].queue.closed:
        assert time.monotonic() < deadline, "crashed queue never closed"
        time.sleep(0.005)


def test_replica_failover_then_all_dead_then_restart(served):
    params, sources = served
    srv = ReplicaServeSession(params, CFG, meshes=[None, None], spec=SPEC,
                              max_batch=8, max_wait_ms=1.0)
    try:
        sm = _sample(sources, 0)
        _crash_replica(srv, 0, sm)
        # the scheduler's sticky pick still points at replica 0: the next
        # submit's put fails, replica 0 is marked dead, and the request
        # fails over to replica 1 — and still serves correctly
        got = srv.submit(sm, head=0).result(timeout=60)
        assert got["energy"] == srv.predict_one(sm, head=0)["energy"]
        assert 0 in srv.scheduler.dead
        assert srv.metrics.counters["failovers"] >= 1
        # kill the last replica too -> no live replica to route to
        _crash_replica(srv, 1, sm)
        with pytest.raises(ServeClosedError, match="dead"):
            srv.submit(sm, head=0)
        # recovery: restart_workers rebuilds queue+batcher+worker per dead
        # replica (fresh batcher: the crash patch dies with the old one)
        assert srv.restart_workers() == 2
        assert srv.scheduler.dead == set()
        got = srv.submit(sm, head=0).result(timeout=60)
        assert got["energy"] == srv.predict_one(sm, head=0)["energy"]
    finally:
        srv.close()


def test_replica_shed_and_close_semantics(served):
    params, sources = served
    fc = FakeClock()
    srv = ReplicaServeSession(params, CFG, meshes=[None, None], spec=SPEC,
                              max_batch=4, max_queue_wait_ms=50.0, clock=fc)
    # quiesce replica 0's worker so _file is ours, then shed a stale request
    srv.replicas[0].close()
    req = srv._admission.make_request(_sample(sources, 0), 0)
    assert req.deadline == pytest.approx(fc() + 0.05)
    fc.advance(0.1)                        # aged past the deadline
    assert srv.replicas[0]._file(req) is None
    with pytest.raises(DeadlineExceededError):
        req.future.result(timeout=0)
    assert srv.metrics.counters["shed_deadline"] == 1
    srv.close()
    with pytest.raises(ServeClosedError):
        srv.submit(_sample(sources, 0), head=0)
    srv.close()                            # idempotent re-entry


# ---------------------------------------------------------------------------
# 8 forced host devices: parity + budgets + drain, in a subprocess
# ---------------------------------------------------------------------------

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.configs.base import ArchConfig
    from repro.core.mtl import make_gfm_mtl
    from repro.data.bucketing import BucketSpec
    from repro.data.synthetic_atoms import generate_mixture, source_dicts
    from repro.launch.mesh import make_replica_meshes
    from repro.serve import ReplicaServeSession, ServeSession

    assert jax.device_count() == 8
    cfg = ArchConfig(name="scaleout-sub", family="gnn", gnn_hidden=16,
                     gnn_layers=2, n_species=64, head_hidden=8,
                     head_layers=2, remat=False, compute_dtype=jnp.float32)
    spec = BucketSpec((8, 16), (32, 64))
    sources = source_dicts(generate_mixture(40, max_atoms=16, max_edges=64))
    model = make_gfm_mtl(cfg, len(sources))
    params = model.init(jax.random.PRNGKey(0))
    KEYS = ("species", "pos", "edge_src", "edge_dst", "node_mask",
            "edge_mask")
    def sample(t, i):
        s = sources[t]
        return {k: s[k][i % s["species"].shape[0]] for k in KEYS}
    jobs = [(t, sample(t, i)) for t in range(len(sources))
            for i in range(4)]

    def match(out, ref):
        return out["energy"] == ref["energy"] and \\
            np.array_equal(out["forces"], ref["forces"])

    res = {}
    # plain single-device session = the parity reference for everything
    ref_srv = ServeSession(params, cfg, spec=spec, max_batch=4)
    refs = [ref_srv.predict_one(sm, head=t) for t, sm in jobs]

    # --- replica mode: 8 engines, one per device ---------------------------
    rep = ReplicaServeSession(params, cfg,
                              meshes=make_replica_meshes(8), spec=spec,
                              max_batch=4, max_wait_ms=2.0)
    outs = [f.result(timeout=300)
            for f in [rep.submit(sm, head=t) for t, sm in jobs]]
    st = rep.stats()
    placements = set()
    for s in rep.replicas:
        leaf = jax.tree_util.tree_leaves(s._shared)[0]
        placements.add(tuple(str(d) for d in sorted(
            leaf.devices(), key=str)))
    res["replica"] = {
        "parity": all(match(o, r) for o, r in zip(outs, refs)),
        "routed": st["counters"]["routed"],
        "n_jobs": len(jobs),
        "compilations": st["counters"]["compilations"],
        "compile_budget": st["executable_cache"]["compile_budget"],
        "budget": st["executable_cache"]["budget"],
        "entries": st["executable_cache"]["entries"],
        "plan": st["plan"],
        "distinct_param_placements": len(placements),
        "outstanding_after": st["scheduler"]["outstanding"],
    }
    # close/drain under the replica workers: a burst submitted then closed
    # immediately must still fully resolve (no dropped futures)
    rep2 = ReplicaServeSession(params, cfg,
                               meshes=make_replica_meshes(4), spec=spec,
                               max_batch=4, max_wait_ms=100.0)
    futs2 = [rep2.submit(sm, head=t) for t, sm in jobs]
    rep2.close()
    res["close"] = {
        "all_done": all(f.done() for f in futs2),
        "all_ok": all(f.exception() is None for f in futs2),
    }
    try:
        rep2.submit(jobs[0][1], head=0)
        res["close"]["after_close"] = "accepted"
    except Exception as e:
        res["close"]["after_close"] = type(e).__name__
    rep.close()

    # --- sharded-forward mode: rows data-parallel over one 8-device mesh ---
    mesh8 = make_replica_meshes(1, devices_per_replica=8)[0]
    sh = ServeSession(params, cfg, spec=spec, max_batch=8, mesh=mesh8,
                      max_wait_ms=2.0)
    outs3 = [f.result(timeout=300)
             for f in [sh.submit(sm, head=t) for t, sm in jobs]]
    st3 = sh.stats()
    res["sharded"] = {
        "parity": all(match(o, r) for o, r in zip(outs3, refs)),
        "compilations": st3["counters"]["compilations"],
        "compiled_shapes": st3["executable_cache"]["compiled_shapes"],
        "n_shapes": spec.n_shapes,
        "plan": st3["plan"],
    }
    try:
        ServeSession(params, cfg, spec=spec, max_batch=6, mesh=mesh8)
        res["sharded"]["uneven_raises"] = False
    except ValueError:
        res["sharded"]["uneven_raises"] = True
    sh.close()
    # four rows per device: each shard has the reference's own batch shape
    sh4 = ServeSession(params, cfg, spec=spec, max_batch=8 * 4, mesh=mesh8,
                       max_wait_ms=2.0)
    outs4 = [f.result(timeout=300)
             for f in [sh4.submit(sm, head=t) for t, sm in jobs]]
    res["sharded_x4"] = {
        "parity": all(match(o, r) for o, r in zip(outs4, refs)),
        "plan": sh4.stats()["plan"],
    }
    sh4.close()
    ref_srv.close()
    print("RESULT " + json.dumps(res))
""")


@pytest.fixture(scope="module")
def result():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("RESULT ")][0]
    return json.loads(line[len("RESULT "):])


def test_replica_rows_bitwise_match_single_device(result):
    """Every replica-served row equals the plain single-device predict_one
    BITWISE: sharding/routing moves rows, it must not change a bit."""
    assert result["replica"]["parity"] is True
    assert result["replica"]["routed"] == result["replica"]["n_jobs"]


def test_replica_compile_budget_is_shapes_times_plans(result):
    rep = result["replica"]
    assert rep["compilations"] <= rep["compile_budget"] == SPEC.n_shapes * 8
    assert rep["entries"] <= rep["budget"]
    assert rep["plan"] == {"mode": "replica", "n_replicas": 8, "devices": 8}


def test_each_replica_owns_its_own_device(result):
    assert result["replica"]["distinct_param_placements"] == 8
    assert result["replica"]["outstanding_after"] == [0] * 8


def test_replica_close_drains_everything(result):
    assert result["close"] == {"all_done": True, "all_ok": True,
                               "after_close": "ServeClosedError"}


def test_sharded_rows_bitwise_match_single_device(result):
    assert result["sharded"]["parity"] is True
    assert result["sharded"]["plan"] == {"mode": "sharded", "devices": 8}


def test_sharded_four_rows_per_device_match_single_device(result):
    assert result["sharded_x4"]["parity"] is True
    assert result["sharded_x4"]["plan"] == {"mode": "sharded", "devices": 8}


def test_sharded_compile_budget_is_the_bucket_grid(result):
    sh = result["sharded"]
    assert sh["compilations"] <= sh["n_shapes"] == SPEC.n_shapes
    assert sh["compiled_shapes"] <= sh["n_shapes"]
    assert sh["uneven_raises"] is True
