"""Chip smoke test: the paper's graph foundation model end to end on a TPU.

    python3 chip_smoke.py              # one chip: train, serve, kernels
    python3 chip_smoke.py --chips 4    # four chips: hier training and
                                       # replica serving, each against
                                       # its one-device reference

One process drives every phase (a chip belongs to one process at a time).
The model is the published ``hydragnn-gfm`` config (H=866, 4 EGNN layers,
5 branches of 3x889) with random weights from ``--seed``, trained on five
seeded synthetic sources.

Phases on one chip:

  train    ``Session`` (model "gfm-mtl") takes a few steps at 16
           structures per task; every loss and per-task loss is finite.
  serve    a ``ServeSession`` over the trained params answers mixed-head
           requests; each batched row is bitwise ``predict_one``.
  kernels  forward and ``jax.grad`` of the trunk with
           ``segment_sum_impl="pallas"`` and ``"fused"``, compiled (a
           ``tpu_custom_call`` in the program), against ``"jnp"`` at
           ``Precision.HIGHEST``.

With ``--chips 4`` only these run:

  hier     a session with ``placement=4`` against the same seed and batches
           on one device; per-step losses agree within fp32 tolerance.
  replica  ``ReplicaServeSession`` over four one-chip replicas against
           ``predict_one``.

Any failed check raises, so the exit code is non-zero. No TPU, no run: the
script exits non-zero before any phase. The last line of standard output is
one JSON object, ``{"ok": true, "device": {...}}``; everything else comes
before it. Times printed here are smoke timings, not benchmark metrics.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# fp32 parity tolerances, as pinned by the CPU suites
PARITY_RTOL, PARITY_ATOL = 5e-5, 1e-6       # tests/test_parallel_parity.py
KERNEL_TOL = 1e-5                           # tests/test_egnn_paper_shape.py
# 80 structures per step: the train step's temporaries fit a 16 GB v5e
# with room (about 7 GB); 32 per task would come within 2 GB of the limit
BATCH_PER_TASK = 16
REQUEST_KEYS = ("species", "pos", "edge_src", "edge_dst", "node_mask",
                "edge_mask")


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(chips: int):
    """The device JAX reports, or exit non-zero: there is no CPU fallback."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found "
                 f"{devices[0].platform!r}; nothing was run")
    if len(devices) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU devices, "
                 f"JAX found {len(devices)}")
    return devices[0]


def make_sources(arch, n_per_source: int, seed: int):
    from repro.data.synthetic_atoms import generate_all, source_dicts
    data = generate_all(n_per_source, max_atoms=arch.max_atoms,
                        max_edges=arch.max_edges, seed=seed)
    return list(data), source_dicts(data)


def session_config(arch, *, steps: int, batch_per_task: int, seed: int,
                   **kw):
    from repro.engine import SessionConfig
    return SessionConfig(model="gfm-mtl", arch=arch, steps=steps,
                         batch_per_task=batch_per_task, lr=1e-3, seed=seed,
                         log_every=1, eval_every=steps, verbose=False, **kw)


def train(scfg, names, sources, label: str):
    """Run one session; print and check every step's losses. Returns
    (params, rows, session) with the session closed."""
    from repro.engine import Session
    with Session.from_config(scfg, sources=sources,
                             task_names=names) as session:
        result = session.run()
    rows = result.logger.history
    assert len(rows) == scfg.steps, (label, len(rows), scfg.steps)
    for row in rows:
        losses = [row["loss"]] + [row[n] for n in names]
        log(f"{label} step {row['step']}: loss={row['loss']!r} "
            + " ".join(f"{n}={row[n]!r}" for n in names))
        assert all(np.isfinite(losses)), (label, row)
    return result.params, rows, session


def phase_train(arch, names, sources, *, steps: int, batch_per_task: int,
                seed: int):
    scfg = session_config(arch, steps=steps, batch_per_task=batch_per_task,
                          seed=seed)
    params, rows, session = train(scfg, names, sources, "train")
    log(f"train: {session.n_params()} params, H={arch.gnn_hidden}, "
        f"layers={arch.gnn_layers}, heads={len(names)}x{arch.head_layers}"
        f"x{arch.head_hidden}, {batch_per_task} structures per task")
    # every step logs (a host sync on its loss), so row-to-row wall time is
    # one step; the first two rows carry compilation
    dts = np.diff([r["wall"] for r in rows])[1:]
    if len(dts):
        log(f"train: smoke timing, not a metric: median step "
            f"{statistics.median(dts):.4f} s after warm-up "
            f"({len(dts)} steps)")
    return params


def _requests(sources, n_per_head: int):
    return [(t, {k: s[k][i % s["species"].shape[0]] for k in REQUEST_KEYS})
            for t, s in enumerate(sources) for i in range(n_per_head)]


def check_serving(srv, sources, n_per_head: int, label: str):
    """Submit mixed-head requests together so the binner coalesces them;
    each answer must equal the same request run alone, bit for bit."""
    jobs = _requests(sources, n_per_head)
    futs = [(t, sm, srv.submit(sm, head=t)) for t, sm in jobs]
    for t, sm, fut in futs:
        got = fut.result(timeout=600)
        ref = srv.predict_one(sm, head=t)
        n_atoms = int(np.asarray(sm["node_mask"]).sum())
        assert np.isfinite(got["energy"]), (label, t, got)
        assert got["forces"].shape == (n_atoms, 3), (label, t)
        assert got["energy"] == ref["energy"], (label, t, got, ref)
        np.testing.assert_array_equal(got["forces"], ref["forces"])
    c = srv.stats()["counters"]
    log(f"{label}: {len(jobs)} requests over {len(sources)} heads equal "
        f"predict_one bitwise; batches={c['batches']} "
        f"completed={c['completed']} failed={c['failed']}")
    assert c["completed"] == len(jobs) and c["failed"] == 0, (label, c)


def phase_serve(params, arch, sources, *, n_per_head: int):
    from repro.serve import ServeSession
    with ServeSession(params, arch, max_batch=8, max_wait_ms=5.0) as srv:
        check_serving(srv, sources, n_per_head, "serve")


def _assert_close_scaled(got, ref, tol, name):
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    scale = max(1.0, float(np.abs(ref).max()))
    np.testing.assert_allclose(got, ref, atol=tol * scale, rtol=tol,
                               err_msg=name)
    return float(np.abs(got - ref).max()), scale


def assert_kernel_compiled(compiled, what: str):
    assert "tpu_custom_call" in compiled.as_text(), \
        f"{what}: no Pallas kernel (tpu_custom_call) in the compiled program"


def phase_kernels(arch, sources, *, n_structures: int, seed: int):
    """Trunk forward and grad through each Pallas path, compiled, against
    the one-hot jnp reference; every matmul outside the kernels runs at
    HIGHEST so both sides compute in fp32."""
    import jax
    import jax.numpy as jnp

    from repro.models import gnn

    src = sources[-1]
    batch = {k: jnp.asarray(src[k][:n_structures]) for k in REQUEST_KEYS}
    params = gnn.egnn_init(jax.random.PRNGKey(seed), arch)
    B, A = batch["species"].shape
    probe = jax.random.normal(jax.random.PRNGKey(seed + 1),
                              (B, A, arch.gnn_hidden), jnp.float32)

    def fwd(impl):
        return jax.jit(lambda p, b: gnn.egnn_apply(p, b, cfg=arch, impl=impl))

    def grad(impl):
        return jax.jit(jax.grad(lambda p, b: jnp.sum(
            gnn.egnn_apply(p, b, cfg=arch, impl=impl) * probe)))

    with jax.default_matmul_precision("highest"):
        ref_out = fwd("jnp")(params, batch)
        ref_grad = grad("jnp")(params, batch)
        for impl in ("pallas", "fused"):
            for what, make, ref in (("forward", fwd, ref_out),
                                    ("grad", grad, ref_grad)):
                t0 = time.perf_counter()
                compiled = make(impl).lower(params, batch).compile()
                t_compile = time.perf_counter() - t0
                assert_kernel_compiled(compiled, f"{impl} {what}")
                got = jax.block_until_ready(compiled(params, batch))
                errs = jax.tree_util.tree_map(
                    lambda g, r, w=f"{impl} {what}":
                    _assert_close_scaled(g, r, KERNEL_TOL, w)[0], got, ref)
                log(f"kernels: {impl} {what} compiled ({t_compile:.1f} s, "
                    f"tpu_custom_call present), max |err| vs jnp "
                    f"{max(jax.tree_util.tree_leaves(errs))!r} "
                    f"(tol {KERNEL_TOL} scaled by max |ref|), B={B} A={A} "
                    f"E={batch['edge_src'].shape[1]} H={arch.gnn_hidden}")


def _devices_of(tree):
    import jax
    return sorted({d.id for leaf in jax.tree_util.tree_leaves(tree)
                   for d in leaf.sharding.device_set})


def phase_hier(arch, names, sources, *, steps: int, batch_per_task: int,
               seed: int):
    """Hierarchical multi-task training over four chips against the same
    seed and batches on one device."""
    import jax

    from repro.launch.mesh import make_group_meshes

    ref_cfg = session_config(arch, steps=steps,
                             batch_per_task=batch_per_task, seed=seed)
    _, ref_rows, _ = train(ref_cfg, names, sources, "hier/1-device")
    hier_cfg = ref_cfg.replace(placement=4)
    params, rows, session = train(hier_cfg, names, sources, "hier/4-chip")
    placement = session.plan.placement
    for g, (heads, mesh) in enumerate(zip(placement.groups,
                                          make_group_meshes(placement))):
        log(f"hier: group {g} heads {[names[h] for h in heads]} -> devices "
            f"{[d.id for d in mesh.devices.flat]} (trunk + head params "
            f"placed there each step)")
    log(f"hier: state params on devices {_devices_of(session.state.params)}, "
        f"optimizer state on {_devices_of(session.state.opt_state)} "
        f"(the update step runs on the default device "
        f"{jax.devices()[0].id})")
    for key in ["loss"] + list(names):
        got = [r[key] for r in rows]
        ref = [r[key] for r in ref_rows]
        np.testing.assert_allclose(got, ref, rtol=PARITY_RTOL,
                                   atol=PARITY_ATOL, err_msg=key)
    worst = max(abs(r[k] - q[k]) / max(abs(q[k]), 1e-30)
                for r, q in zip(rows, ref_rows) for k in ["loss"] + names)
    log(f"hier: {steps} steps, loss and per-task losses match one device "
        f"(max rel diff {worst!r}; rtol {PARITY_RTOL}, atol {PARITY_ATOL})")
    return params


def phase_replicas(params, arch, sources, *, n_per_head: int):
    from repro.launch.mesh import make_replica_meshes
    from repro.serve import ReplicaServeSession
    with ReplicaServeSession(params, arch, meshes=make_replica_meshes(4),
                             max_batch=8, max_wait_ms=5.0) as srv:
        check_serving(srv, sources, n_per_head, "replica")
        routed = srv.stats()["scheduler"]
        log(f"replica: 4 one-chip replicas, scheduler {routed}")


def run_phase(name: str, fn, *args, **kw):
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    log(f"phase {name}: ok ({time.perf_counter() - t0:.1f} s)")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    device = require_tpu(args.chips)
    import jax

    from repro import configs
    from repro.launch import compile_cache

    log(f"chip_smoke: {len(jax.devices())} x {device.device_kind} "
        f"({device.platform}); compile cache {compile_cache.enable()}")
    arch = configs.get("hydragnn-gfm")
    t0 = time.perf_counter()
    names, sources = make_sources(arch, 64, args.seed)
    log(f"data: {len(names)} sources {names}, "
        f"{sources[0]['species'].shape[0]} structures each, "
        f"A={arch.max_atoms} E={arch.max_edges} "
        f"({time.perf_counter() - t0:.1f} s)")

    if args.chips == 1:
        params = run_phase("train", phase_train, arch, names, sources,
                           steps=8, batch_per_task=BATCH_PER_TASK,
                           seed=args.seed)
        run_phase("serve", phase_serve, params, arch, sources, n_per_head=8)
        run_phase("kernels", phase_kernels, arch, sources, n_structures=4,
                  seed=args.seed)
    else:
        # fp32 parity needs fp32 matmuls on both sides of the comparison
        jax.config.update("jax_default_matmul_precision", "highest")
        params = run_phase("hier", phase_hier, arch, names, sources,
                           steps=3, batch_per_task=BATCH_PER_TASK,
                           seed=args.seed)
        run_phase("replica", phase_replicas, params, arch, sources,
                  n_per_head=8)

    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
