"""What every cell's run shares: finding its files by name, the chip check,
the compile cache, spans, device readings and the result line.

A cell names a configuration and a traffic mix in ``BENCHMARK.json``. The
harness finds the rest by those names:

  * ``perfbench/configs/<config>.json``: the configuration as it is run;
    its ``reference`` key names the plain reference of its architecture
    (``perfbench/reference/common.py`` says what such a module brings),
    from which the weights, the operation count and the checks come;
  * ``perfbench/traffic/<traffic>.json``: the mix's parameters; its
    ``runner`` key names the general runner in ``perfbench/runners/``;
  * ``perfbench/cells/<workload>.json``: the cell's correctness limits;
  * ``perfbench/metrics/<metric>.py``: one reader per metric, end to end or
    per layer, each a ``read(record) -> float | None`` over the run's record.
"""
from __future__ import annotations

import contextlib
import functools
import importlib.util
import json
import math
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


class NoAccelerator(SystemExit):
    """Raised before any work when JAX finds no TPU or too few chips."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.lru_cache(maxsize=None)
def _reference_at(path: str):
    return load_module(path, os.path.splitext(os.path.basename(path))[0])


def reference(cfg: dict):
    """The plain reference module that configuration ``cfg`` names, loaded
    once per process."""
    return _reference_at(os.path.join(BENCH_DIR, cfg["reference"]))


def benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def cell_files(bench: dict, workload: str, root: str = ROOT) -> dict:
    """The cell's entry, configuration, traffic mix and limits, by name."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = load_json(os.path.join(root, cfg_entry["file"]))
    traffic = load_json(os.path.join(BENCH_DIR, "traffic",
                                     cell["traffic"] + ".json"))
    limits = load_json(os.path.join(BENCH_DIR, "cells", workload + ".json"))
    return {"cell": cell, "config": cfg, "traffic": traffic,
            "limits": limits}


def sources_spec(traffic: dict) -> dict:
    """The traffic's structure sources: a file ``traffic/sources/<name>.json``
    named by ``traffic["sources"]``, or the parameters themselves."""
    src = traffic["sources"]
    if isinstance(src, dict):
        return src
    return load_json(os.path.join(BENCH_DIR, "traffic", "sources",
                                  src + ".json"))


def metrics_for(bench: dict, workload: str, trace: bool) -> list:
    """The metric entries this cell reports: end to end without trace, per
    layer with it. An end-to-end metric without ``workloads`` is in every
    cell; a per-layer one without it is in every cell that reports the
    metric it moves."""
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if not trace:
        return e2e
    mine = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in mine)]


def read_metric(name: str, record: dict):
    mod = load_module(os.path.join(BENCH_DIR, "metrics", name + ".py"),
                      "metric_" + name.replace(".", "_").replace("-", "_"))
    return mod.read(record)


def require_tpu(chips: int):
    """The devices JAX reports, or exit non-zero before any work: there is
    no CPU fallback."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoAccelerator(f"perfbench: needs a TPU, JAX found "
                            f"{devices[0].platform!r}; nothing was run")
    if len(devices) < chips:
        raise NoAccelerator(f"perfbench: the cell needs {chips} TPU chips, "
                            f"JAX found {len(devices)}; nothing was run")
    return devices


def enable_compile_cache(root: str = ROOT) -> str:
    """JAX's persistent cache at a fixed path: ``JAX_COMPILATION_CACHE_DIR``
    where set, else ``<checkout>/.jax_cache``; every program is kept."""
    import jax
    path = os.environ.get(CACHE_ENV) or os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def spans(on: bool):
    """``span(name)``: a profiler annotation when tracing, else nothing."""
    if not on:
        return lambda name: contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation


def start_trace(trace_dir: str):
    """Start the profiler with device tracing and the harness's own spans,
    without Python function tracing (it would slow the host path the
    serving cell measures several times over)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def log(*parts):
    print(*parts, file=sys.stderr, flush=True)


def checks_ok(checks: list) -> bool:
    return all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks)


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: dict, device: dict, checks: list,
                breakdown: dict | None = None) -> str:
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                     for c in checks}
    return json.dumps(out)


def print_checks(checks: list):
    """The numbers compared, each beside its limit: the last lines of
    standard error."""
    for c in checks:
        ok = math.isfinite(c["value"]) and c["value"] <= c["limit"]
        log(f"check {c['name']}: {c['value']!r} limit {c['limit']!r} "
            f"{'ok' if ok else 'FAILED'}")
