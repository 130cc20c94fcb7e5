"""ServeSession — the request-serving engine for trained multi-head GNNs.

Turns a trained ``{"shared", "heads"}`` parameter tree (the MultiTaskModel
layout every ``repro.engine`` training session produces) into a
property-prediction server:

  caller threads ── submit(sample, head) ──► RequestQueue (bounded, admits
                                             via BucketSpec.bucket_for)
                                                 │
                               worker thread ────┤ SizeBinnedBatcher
                                                 │   coalesce per (bucket,
                                                 │   head); flush on full
                                                 │   batch or max_wait
                                                 ▼
                          compiled forward (jit egnn_apply + branch_apply)
                                                 │
                     scatter rows back to request futures + ServeMetrics

The executable cache is keyed per (bucket-shape, head): every (bucket,
head) pair binds the head's parameter slice to ONE shared jitted forward,
so XLA compiles at most one variant per bucket shape — head slices have
identical shapes/dtypes and hit the jit cache. The recompile budget is
therefore the bucket grid, exactly as in training (``len(atom_buckets) x
len(edge_buckets)`` compilations, <= grid x n_heads cache entries;
asserted by tests/test_serve_engine.py). A multi-device session is one
more PLAN, not more shapes: ``mesh=`` shards the batched forward's rows
data-parallel over a 1-axis serving mesh (params replicated, the
``configs.sharding.serve_batch_spec`` rule), so the budget generalizes to
``distinct bucket shapes x plans`` — see ``repro.serve.scaleout`` for the
replica-per-device mode on top.

The time base is ONE injected ``clock`` (default ``time.monotonic``)
threaded through queue, batcher, and metrics: ``t_submit``/``deadline``/
``next_deadline`` arithmetic never mixes clock bases (perf_counter vs
monotonic skew is unbounded across hosts/suspends).

Shutdown follows the ``Prefetcher`` discipline: ``close()`` stops
admissions, drains everything already queued or binned through the compiled
path (every accepted future resolves), joins the worker, and is an
idempotent no-op on re-entry.
"""
from __future__ import annotations

import threading
import time

import jax
import numpy as np

from repro.data.bucketing import BucketSpec
from repro.models import gnn, heads as heads_mod

from .batching import AdaptivePolicy, AssembledBatch, SizeBinnedBatcher
from .metrics import ServeMetrics
from .queue import DeadlineExceededError, RequestQueue, ServeClosedError

# head-parameter keys that are training-only (loss weighting), never part
# of the serving forward
_NON_FORWARD_HEAD_KEYS = ("log_sigma2",)


def _head_slices(head_params, n_heads: int) -> list:
    """Stacked (n_heads, ...) head tree -> per-head parameter trees with
    training-only leaves dropped."""
    fwd = {k: v for k, v in head_params.items()
           if k not in _NON_FORWARD_HEAD_KEYS}
    return [jax.tree_util.tree_map(lambda v: v[t], fwd)
            for t in range(n_heads)]


class ServeSession:
    """High-throughput property-prediction serving for one trained model.

    params: ``{"shared": egnn params, "heads": stacked branch params}``
        (leading head/task dim on every heads leaf).
    arch:   the ``ArchConfig`` the params were trained with.
    spec:   the ``BucketSpec`` coalescing grid; None = one bucket at
        (arch.max_atoms, arch.max_edges) — correct but pays worst-case pad.
    max_batch:    rows per compiled batch (static leading dim).
    max_wait_ms:  partial-batch flush deadline (tail-latency bound).
    queue_depth:  admission backpressure bound.
    max_queue_wait_ms: per-request queue-wait budget — a request that aged
        past it is SHED (its future fails with ``DeadlineExceededError``)
        instead of computed, so overload degrades by dropping stale work
        rather than serving every request late. None = never shed.
    admission_timeout_ms: bound on how long ``submit()`` blocks on
        backpressure before raising ``DeadlineExceededError`` in the
        caller's thread. None = block until a slot frees.
    mesh: optional 1-axis serving mesh (``make_replica_meshes`` /
        ``make_group_meshes``): the batched forward's rows are sharded
        data-parallel over its devices with params replicated
        (``serve_batch_spec``); ``max_batch`` must tile evenly. None keeps
        the single-device plan. Row results stay BITWISE equal either way —
        the forward is per-row independent, sharding only moves rows.
    adaptive: adapt the release knobs per (bucket, head) from measured
        arrival rate/occupancy (``AdaptivePolicy``) instead of serving the
        fixed ``max_batch``/``max_wait_ms`` knee. Padded shapes (and so the
        compile budget) are unchanged.
    clock: the session's single time base (monotonic-like callable),
        threaded through queue, batcher, and metrics.
    """

    def __init__(self, params: dict, arch, *, spec: BucketSpec | None = None,
                 max_batch: int = 8, max_wait_ms: float = 5.0,
                 queue_depth: int = 256,
                 max_queue_wait_ms: float | None = None,
                 admission_timeout_ms: float | None = None,
                 mesh=None, adaptive: bool = False,
                 metrics: ServeMetrics | None = None,
                 clock=time.monotonic, seed: int = 0):
        if not (isinstance(params, dict) and
                {"shared", "heads"} <= set(params)):
            raise ValueError('params must be the MultiTaskModel layout '
                             '{"shared": ..., "heads": ...}')
        leaves = jax.tree_util.tree_leaves(
            {k: v for k, v in params["heads"].items()
             if k not in _NON_FORWARD_HEAD_KEYS})
        n_heads = int(leaves[0].shape[0])
        assert all(int(l.shape[0]) == n_heads for l in leaves), \
            "heads leaves disagree on the leading head dim"
        if spec is None:
            assert arch.max_atoms > 0 and arch.max_edges > 0, \
                "spec=None needs arch.max_atoms/max_edges to form a bucket"
            spec = BucketSpec((arch.max_atoms,), (arch.max_edges,))
        self.arch = arch
        self.spec = spec
        self.n_heads = n_heads
        self.max_batch = max_batch
        self.mesh = mesh
        self._clock = clock
        self._shared = params["shared"]
        self._heads = _head_slices(params["heads"], n_heads)
        self.metrics = metrics if metrics is not None else \
            ServeMetrics(seed=seed, clock=clock)
        # retained so restart_worker() can rebuild the queue/batcher pair
        self._queue_depth = queue_depth
        self._max_queue_wait = None if max_queue_wait_ms is None \
            else max_queue_wait_ms * 1e-3
        self._admission_timeout = None if admission_timeout_ms is None \
            else admission_timeout_ms * 1e-3
        self._max_wait = max_wait_ms * 1e-3
        # the policy is measurement state (like the jit cache): it survives
        # restart_worker(), only the batcher it advises is rebuilt
        self._policy = AdaptivePolicy(max_batch=max_batch,
                                      max_wait=self._max_wait) \
            if adaptive else None
        self.queue = self._make_queue()
        self.batcher = self._make_batcher()

        def forward(shared, head, batch):
            feats = gnn.egnn_apply(shared, batch, cfg=arch)
            return heads_mod.branch_apply(head, feats, batch["node_mask"],
                                          cfg=arch)

        # ONE jitted callable shared by every (bucket, head) cache entry:
        # head slices are shape/dtype-identical, so only a new BUCKET shape
        # actually compiles
        if mesh is None:
            self.plan_devices = 1
            self._predict = jax.jit(forward)
        else:
            self.plan_devices = int(np.prod(list(mesh.shape.values())))
            self._predict = self._sharded_predict(forward, mesh)
        self._exec: dict[tuple, object] = {}   # (bucket, head) -> callable
        self._shapes_compiled: set = set()
        # the jitted forward's cache size when last counted (see
        # _count_compilations); the worker and predict_one both call it
        self._compiled = 0
        self._compile_lock = threading.Lock()
        self._closed = False
        self._worker_error: BaseException | None = None
        # requests dequeued but not yet filed into the batcher: on a worker
        # crash these are in NEITHER the queue nor the batcher, so the
        # fail-fast handler must fail their futures from here
        self._inflight: list = []
        self._closing = threading.Event()
        self._worker = threading.Thread(target=self._serve_loop,
                                        name="serve-worker", daemon=True)
        self._worker.start()

    def _make_queue(self) -> RequestQueue:
        return RequestQueue(self.spec, depth=self._queue_depth,
                            n_heads=self.n_heads, clock=self._clock,
                            metrics=self.metrics,
                            max_queue_wait=self._max_queue_wait,
                            admission_timeout=self._admission_timeout)

    def _make_batcher(self) -> SizeBinnedBatcher:
        return SizeBinnedBatcher(max_batch=self.max_batch,
                                 max_wait=self._max_wait,
                                 clock=self._clock, policy=self._policy,
                                 metrics=self.metrics)

    def _sharded_predict(self, forward, mesh):
        """jit the forward with rows data-parallel over the serving mesh and
        params replicated. Params are committed to the mesh once so every
        call reuses the on-device copies (no per-batch host transfer)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.configs.sharding import serve_batch_spec, tree_shardings

        ndev = self.plan_devices
        if self.max_batch % ndev != 0:
            raise ValueError(
                f"max_batch={self.max_batch} must tile evenly over the "
                f"{ndev}-device serving mesh (rows are data-parallel)")
        replicated = lambda path, leaf: P(*([None] * np.ndim(leaf)))  # noqa: E731
        shared_sh = tree_shardings(mesh, self._shared, replicated)
        head_sh = tree_shardings(mesh, self._heads[0], replicated)
        self._shared = jax.device_put(self._shared, shared_sh)
        self._heads = [jax.device_put(h, head_sh) for h in self._heads]
        # assembled-batch leaves are (max_batch, ...); ndim is fixed per key
        ndims = {"species": 2, "pos": 3, "edge_src": 2, "edge_dst": 2,
                 "node_mask": 2, "edge_mask": 2}
        batch_sh = {
            k: NamedSharding(mesh, serve_batch_spec(
                np.zeros((self.max_batch,) + (1,) * (nd - 1)), ndev))
            for k, nd in ndims.items()}
        out_sh = NamedSharding(mesh, P())   # tiny outputs: gather to all
        return jax.jit(forward, in_shardings=(shared_sh, head_sh, batch_sh),
                       out_shardings=out_sh)

    # -- construction helpers -----------------------------------------------

    @classmethod
    def from_checkpoint(cls, path: str, arch, *, model: str = "gfm-mtl",
                        n_heads: int | None = None, **kw) -> "ServeSession":
        """Load params written by ``Session``/``checkpoint.save`` (the
        ``{"params": ...}`` tree) and serve them. The template comes from
        the registry model's ``init`` under ``jax.eval_shape`` — zero
        allocation, restored leaves land as the checkpoint's values."""
        from repro.engine.registry import build_model
        from repro.train import checkpoint
        built = build_model(model, arch,
                            n_tasks=n_heads or arch.n_tasks or None)
        template = jax.eval_shape(built.init, jax.random.PRNGKey(0))
        params = checkpoint.restore(path, {"params": template})["params"]
        return cls(params, arch, **kw)

    # -- public API ----------------------------------------------------------

    def submit(self, sample: dict, head: int = 0):
        """Admit one structure; returns a Future resolving to
        ``{"energy": float, "forces": (n_atoms, 3) float32}``."""
        self._check_alive()
        return self.queue.submit(sample, head)

    def submit_many(self, samples, heads=0) -> list:
        self._check_alive()
        return self.queue.submit_many(samples, heads)

    def predict_one(self, sample: dict, head: int = 0) -> dict:
        """Synchronous single-request forward through the SAME executable a
        batched run uses (one real row, ``max_batch - 1`` inert pad rows) —
        the parity reference for the batched-and-scattered path, and a
        convenience for offline use. Bypasses the queue/worker."""
        from .queue import Request, _as_sample
        canon, n_atoms, n_edges = _as_sample(sample)
        bucket = self.spec.bucket_for(n_atoms, n_edges)
        req = Request(sample=canon, head=head, bucket=bucket,
                      n_atoms=n_atoms, n_edges=n_edges, future=None,
                      t_submit=self._clock())
        from .batching import assemble
        ab = assemble([req], bucket, self.max_batch)
        e, f = self._executable(bucket, head)(ab.batch)
        e, f = np.asarray(e), np.asarray(f)
        return {"energy": float(e[0]), "forces": f[0, :n_atoms]}

    def warmup(self, buckets=None) -> int:
        """Pre-compile executables (head 0) for the given buckets (default:
        the full grid) so first requests don't pay compile latency. Returns
        the number of compiled shapes afterwards."""
        if buckets is None:
            buckets = [(a, e) for a in self.spec.atom_buckets
                       for e in self.spec.edge_buckets]
        for bucket in buckets:
            a_pad, e_pad = bucket
            dummy = {"species": np.zeros((self.max_batch, a_pad), np.int32),
                     "pos": np.zeros((self.max_batch, a_pad, 3), np.float32),
                     "edge_src": np.full((self.max_batch, e_pad), a_pad,
                                         np.int32),
                     "edge_dst": np.full((self.max_batch, e_pad), a_pad,
                                         np.int32),
                     "node_mask": np.zeros((self.max_batch, a_pad), bool),
                     "edge_mask": np.zeros((self.max_batch, e_pad), bool)}
            e, f = self._executable(bucket, 0)(dummy)
            jax.block_until_ready((e, f))
        return len(self._shapes_compiled)

    def jit_functions(self):
        """The session's jitted callables — the probe seam for
        ``repro.analysis.RecompileSanitizer`` (tracks ``_predict``'s cache
        the same way ``tests/test_serve_engine.py`` asserts on it)."""
        return (self._predict,)

    def stats(self) -> dict:
        """Metrics snapshot + executable-cache occupancy (plain dict)."""
        out = self.metrics.snapshot()
        out["executable_cache"] = {
            "entries": len(self._exec),
            "compiled_shapes": len(self._shapes_compiled),
            "budget": self.spec.n_shapes * self.n_heads,
            # one plan (single jit cache) regardless of mesh width: XLA
            # compiles per distinct bucket shape, heads share the executable
            "compile_budget": self.spec.n_shapes,
        }
        out["plan"] = {"mode": "sharded" if self.plan_devices > 1
                       else "single", "devices": self.plan_devices}
        if self._policy is not None:
            out["adaptive"] = self._policy.snapshot()
        return out

    def close(self):
        """Graceful shutdown: stop admissions, drain every queued/binned
        request through the compiled path (all accepted futures resolve),
        join the worker. Idempotent no-op on re-entry."""
        if self._closed:
            return
        self._closed = True
        self.queue.close()
        self._closing.set()
        self._worker.join(timeout=60.0)
        if self._worker.is_alive():
            raise RuntimeError("serve worker did not drain within 60s")

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # -- worker ---------------------------------------------------------------

    def _check_alive(self):
        if self._closed:
            raise ServeClosedError("ServeSession is closed")
        if self._worker_error is not None:
            raise ServeClosedError(
                "serve worker died — session is closed to new work "
                "(restart_worker() recovers it)") from self._worker_error

    def _executable(self, bucket: tuple, head: int):
        """The per-(bucket, head) cache entry: the shared jitted forward
        with this head's parameter slice bound. Same-shape entries for
        other heads reuse the compiled executable; every call counts what
        it compiled (``_count_compilations``)."""
        key = (bucket, head)
        fn = self._exec.get(key)
        if fn is None:
            self._shapes_compiled.add(bucket)
            hp = self._heads[head]
            shared = self._shared

            def fn(batch, _p=self._predict, _s=shared, _h=hp):
                out = _p(_s, _h, batch)
                self._count_compilations()
                return out

            self._exec[key] = fn
        return fn

    def _count_compilations(self):
        """Add to the ``compilations`` counter what the jitted forward's
        own cache grew by since the last count: every XLA compilation, a
        recompile of a warmed shape (another dtype, say) included."""
        with self._compile_lock:
            n = self._predict._cache_size()
            grew, self._compiled = n - self._compiled, n
        if grew:
            self.metrics.inc("compilations", grew)

    def _execute(self, ab: AssembledBatch):
        """Run one assembled batch and scatter rows to futures."""
        m = self.metrics
        try:
            with m.stage("compute"):
                with m.stage("dispatch"):
                    e, f = self._executable(ab.bucket, ab.head)(ab.batch)
                with m.stage("readback"):
                    e, f = np.asarray(e), np.asarray(f)   # blocks until ready
        except BaseException as err:
            for r in ab.requests:
                r.future.set_exception(err)
            m.inc("failed", len(ab.requests))
            return
        with m.stage("scatter"):
            m.inc("batches")
            m.inc("batch_slots", self.max_batch)
            m.inc("batch_real", ab.n_real)
            for i, r in enumerate(ab.requests):
                r.t_done = self._clock()
                r.future.set_result(
                    {"energy": float(e[i]), "forces": f[i, :r.n_atoms]})
                m.observe("e2e", r.t_done - r.t_submit)
            m.inc("completed", ab.n_real)

    def _file(self, req) -> AssembledBatch | None:
        req.t_dequeue = self._clock()
        self.metrics.observe("queue_wait", req.t_dequeue - req.t_submit)
        if req.deadline is not None and req.t_dequeue > req.deadline:
            # stale request: under overload, computing it would only delay
            # every request behind it — shed instead (load shedding)
            req.future.set_exception(DeadlineExceededError(
                f"request waited {req.t_dequeue - req.t_submit:.3f}s in "
                f"queue, past its max_queue_wait deadline"))
            self.metrics.inc("shed_deadline")
            return None
        return self.batcher.add(req)

    def _serve_loop(self):
        try:
            while not self._closing.is_set():
                now = self._clock()
                deadline = self.batcher.next_deadline(now)
                # poll timeout: wake for the earliest bin deadline, else a
                # coarse tick so close() is observed promptly
                timeout = 0.05 if deadline is None \
                    else min(max(deadline, 0.0), 0.05)
                with self.metrics.stage("poll"):
                    req = self.queue.get(timeout=timeout)
                if req is not None:
                    # greedy drain: file the WHOLE backlog before computing.
                    # Under load, dequeued requests are usually already past
                    # their deadline (they aged in the queue), so filing one
                    # at a time would flush every bin one-deep; filing the
                    # backlog first lets bins reach max_batch occupancy.
                    with self.metrics.stage("file"):
                        self._inflight = [req] + self.queue.drain()
                        ready = []
                        while self._inflight:
                            ab = self._file(self._inflight[0])
                            self._inflight.pop(0)
                            if ab is not None:
                                ready.append(ab)
                    for ab in ready:
                        self._execute(ab)
                for ab in self.batcher.expired(self._clock()):
                    self._execute(ab)
            # graceful drain: admissions are closed, so the queue can only
            # shrink — run everything left through the compiled path
            for req in self.queue.drain():
                ab = self._file(req)
                if ab is not None:
                    self._execute(ab)
            for ab in self.batcher.flush():
                self._execute(ab)
        except BaseException as err:   # fail loudly, never hang futures
            self._worker_error = err
            # close admissions FIRST: a submit racing the drain below would
            # otherwise enqueue a request nobody will ever serve
            self.queue.close()
            self.metrics.inc("worker_failures")
            pending = (self._inflight + self.queue.drain() +
                       self.batcher.pending_requests())
            self._inflight = []
            for req in pending:
                req.future.set_exception(err)
            self.metrics.inc("failed", len(pending))

    # -- recovery -------------------------------------------------------------

    def restart_worker(self) -> bool:
        """Recover from a dead worker: clear the fail-fast state and stand
        up a fresh queue + batcher + worker thread. Compiled executables are
        retained, so recovery costs no recompilation. The crashed worker's
        pending futures were already failed — nothing is replayed. Returns
        True if a restart happened (False: worker was healthy)."""
        if self._closed:
            raise ServeClosedError("ServeSession is closed")
        if self._worker_error is None and self._worker.is_alive():
            return False
        self._worker.join(timeout=5.0)
        self._worker_error = None
        self._inflight = []
        self.queue = self._make_queue()
        self.batcher = self._make_batcher()
        self._closing = threading.Event()
        self._worker = threading.Thread(target=self._serve_loop,
                                        name="serve-worker", daemon=True)
        self._worker.start()
        self.metrics.inc("worker_restarts")
        return True
