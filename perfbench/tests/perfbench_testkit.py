"""Small configurations and traffic for the benchmark's own CPU tests."""
from __future__ import annotations

import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from perfbench import harness  # noqa: E402


def tiny_config(name="hydragnn-gfm", **kw):
    """Configuration ``name`` at its reference's small sizes, with ``kw``
    over them."""
    cfg = harness.load_json(os.path.join(harness.BENCH_DIR, "configs",
                                         name + ".json"))
    return dict(harness.reference(cfg).tiny(cfg), **kw)


def tiny_sources(total=300):
    spec = harness.load_json(os.path.join(harness.BENCH_DIR, "traffic",
                                          "sources", "paper5.json"))
    spec["total"] = total
    return spec


def tiny_traffic(name, **kw):
    tr = harness.load_json(os.path.join(harness.BENCH_DIR, "traffic",
                                        name + ".json"))
    tr["sources"] = tiny_sources()
    if tr["runner"] == "train":
        tr.update(batch_per_task=4 if tr["batch_per_task"] == 16 else 20,
                  lookahead_steps=400)
    else:
        tr.update(rate_per_s=60.0, check_requests=16)
    tr.update(kw)
    return tr


def limits(cell):
    return harness.load_json(os.path.join(harness.BENCH_DIR, "cells",
                                          cell + ".json"))


def drive(cfg, traffic, cell, *, seed=2 ** 33 + 7, seconds=0.3, fault=None,
          devices=None):
    import jax
    runner = harness.load_module(os.path.join(
        harness.BENCH_DIR, "runners", traffic["runner"] + ".py"),
        "runner_" + traffic["runner"])
    return runner.run(config=cfg, traffic=traffic, limits=limits(cell),
                      seed=seed, seconds=seconds, trace=False,
                      devices=devices or jax.devices()[:1],
                      t_start=time.perf_counter(), log=lambda *a: None,
                      fault=fault)


def checks(rec):
    return {c["name"]: c["value"] for c in rec["checks"]}


def passes(rec, cell) -> bool:
    """Every number the run compared is within the cell's limit."""
    lim = limits(cell)
    return all(v <= lim[k] for k, v in checks(rec).items())
