"""The benchmark's one door into the system under test (``src/repro``).

Everything else under ``perfbench/`` is the yardstick and imports nothing of
the program; the runners reach the program only through these helpers.
"""
from __future__ import annotations

import dataclasses

from perfbench.weights import DTYPES


def set_precision(cfg: dict):
    """Float32 as the configuration states it: matrix products of float32
    operands at the configuration's ``matmul_precision`` (``highest``: full
    float32; a TPU's default takes one bfloat16 pass)."""
    import jax
    jax.config.update("jax_default_matmul_precision", cfg["matmul_precision"])


def arch(cfg: dict):
    """The program's ArchConfig for a configuration file: the program's own
    entry ``cfg["arch"]`` with every key of the file that names one of its
    fields, but ``name``; a ``*_dtype`` is named by a key of ``DTYPES``."""
    from repro import configs
    base = configs.get(cfg["arch"])
    fields = {f.name for f in dataclasses.fields(base)} - {"name"}
    return base.replace(**{k: DTYPES[v] if k.endswith("_dtype") else v
                           for k, v in cfg.items() if k in fields})


def session(cfg: dict, traffic: dict, sources, names, *, seed: int):
    """A training ``Session`` over ``sources`` as the traffic mix states."""
    from repro.engine import Session, SessionConfig
    set_precision(cfg)
    scfg = SessionConfig(
        model=cfg["model"], arch=arch(cfg), steps=traffic["schedule_steps"],
        batch_per_task=traffic["batch_per_task"], lr=traffic["lr"],
        warmup=traffic["warmup"], weight_decay=traffic["weight_decay"],
        mixing=float(traffic["mixing_temperature"]),
        bucketing=int(traffic["bucket_grid"]), prefetch=True,
        prefetch_depth=traffic["prefetch_depth"], seed=seed,
        placement=traffic.get("placement"), verbose=False,
        log_every=10 ** 9, eval_every=10 ** 9)
    multitask = cfg["n_tasks"] > 1
    return Session(scfg, sources=sources,
                   task_names=names if multitask else None)


def set_params(sess, params):
    """Start the session's state from ``params`` (fresh optimizer state)."""
    from repro.engine.state import TrainState
    sess.state = TrainState.create(params, sess.optimizer,
                                   rng=sess.state.rng)


def serve_session(cfg: dict, traffic: dict, params, sources):
    """A ``ServeSession`` with the traffic's bucket grid and release knobs,
    and the grid itself."""
    from repro.data.bucketing import BucketSpec
    from repro.serve import ServeSession
    set_precision(cfg)
    g = int(traffic["bucket_grid"])
    spec = BucketSpec.from_sources(sources, n_atom_buckets=g,
                                   n_edge_buckets=g)
    srv = ServeSession(params, arch(cfg), spec=spec,
                       max_batch=traffic["max_batch"],
                       max_wait_ms=traffic["max_wait_ms"],
                       queue_depth=traffic["queue_depth"])
    return srv, spec
