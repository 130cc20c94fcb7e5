"""Where a cell's time goes, read from the program's own spans and scopes.

    python3 perfbench/breakdown.py --workload gfm_serve_screen \\
        --seeds 11 12 13 --seconds 5 --trace 1 0

Runs the cell's runner once per seed and per tracing mode, in one process,
every window ``--seconds`` long whether traced or not, and prints one JSON
line per run: the cell's end-to-end metrics, ``window_compilations`` (the
program's compile count after the window less the count before it) and,
traced, the harness's breakdown beside the program's
(``perfbench/progtrace.py``) with the per-layer readings of
``progtrace.READERS``. PERF.md's "where the time goes" and its cost of
tracing come from this tool; it is not part of a benchmark run.

The runners reduce their trace and delete it inside ``run()``, so the tool
hands them, for the length of a run, a ``devtrace.read_dir`` that also
reduces the program's spans and scopes, and takes the compile count where
the runner opens its window spans (``harness.spans``). The device ops'
name paths come from the training step's optimized HLO
(``progtrace.hlo_op_paths``), as a TPU trace does not carry them.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# libtpu would otherwise write its logs under a fixed path in /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from perfbench import devtrace, harness, progtrace  # noqa: E402


def read_trace(trace_dir: str):
    """The newest ``*.xplane.pb`` under ``trace_dir``, or None."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return ProfileData.from_file(files[-1]) if files else None


def _shapes(tree):
    """Abstract arguments that lower exactly as ``tree`` does (weak types
    kept; a sharding only where the array is committed to one), so the
    step's optimized HLO is the executable's and its ops' names match the
    trace's."""
    import jax
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, weak_type=x.weak_type,
            sharding=x.sharding if x.committed else None), tree)


class Probe:
    """What the runner hands its ``fault`` hook: the training step (kept,
    with the shapes of its first call) or the serving session."""

    def __init__(self, kind: str):
        self.kind = kind
        self.target = None
        self.shapes = None

    def attach(self, target):
        self.target = target
        if self.kind != "train":
            return None

        def step(state, batch):
            if self.shapes is None:
                self.shapes = _shapes((state, batch))
            return target(state, batch)
        return step

    def compilations(self) -> int:
        if self.kind == "train":
            return self.target.cache_size()
        return self.target.stats()["counters"]["compilations"]

    def op_paths(self) -> dict:
        """Instruction name -> name path of the training step's optimized
        HLO (empty for serving)."""
        if self.kind != "train" or self.shapes is None:
            return {}
        text = self.target.lower(*self.shapes).compile().as_text()
        return progtrace.hlo_op_paths(text)


def measure(files: dict, *, seed: int, seconds: float, trace: bool,
            devices, log=harness.log) -> dict:
    """One run of the cell with the program's breakdown: the runner's
    record, with ``window_compilations`` and, traced, the program's keys
    in its ``trace``."""
    traffic = dict(files["traffic"], trace_seconds=seconds)
    kind = traffic["runner"]
    runner = harness.load_module(
        os.path.join(harness.BENCH_DIR, "runners", kind + ".py"),
        "runner_" + kind)
    probe = Probe(kind)
    before = {}
    spans, read_dir = harness.spans, devtrace.read_dir

    def window_spans(on):
        before["compilations"] = probe.compilations()
        return spans(on)

    def read_both(trace_dir):
        space = read_trace(trace_dir)
        if space is None:
            return {}
        tr = devtrace.reduce_space(space)
        tr.update(progtrace.reduce_program(space, probe.op_paths()))
        return tr

    harness.spans, devtrace.read_dir = window_spans, read_both
    try:
        rec = runner.run(config=files["config"], traffic=traffic,
                         limits=files["limits"], seed=seed, seconds=seconds,
                         trace=trace, devices=devices,
                         t_start=time.perf_counter(), log=log,
                         fault=probe.attach)
    finally:
        harness.spans, devtrace.read_dir = spans, read_dir
    rec["window_compilations"] = probe.compilations() - before["compilations"]
    return rec


def summary(bench: dict, workload: str, rec: dict, trace: bool) -> dict:
    """The run's numbers: end-to-end metrics, and traced the per-layer
    readings and the breakdown PERF.md quotes."""
    out = {"workload": workload, "trace": trace,
           "correct": harness.checks_ok(rec["checks"]),
           "window_compilations": rec["window_compilations"],
           "metrics": {}}
    for m in harness.metrics_for(bench, workload, False):
        out["metrics"][m["name"]] = harness.read_metric(m["name"], rec)
    if rec["kind"] == "train" and rec["steps"]:
        out["metrics"]["ms_per_step"] = 1e3 * rec["window_s"] / rec["steps"]
    if not trace:
        return out
    for m in harness.metrics_for(bench, workload, True):
        out["metrics"][m["name"]] = harness.read_metric(m["name"], rec)
    for name, read in progtrace.READERS.items():
        value = read(rec)
        if value is not None:
            out["metrics"][name] = value
    tr = rec.get("trace") or {}
    busy = sum(tr.get("busy_s", {}).values())
    out["breakdown"] = {k: tr.get(k) for k in (
        "window_s", "busy_s", "device_ops", "idle_gaps", "host_spans",
        "span_cover_s", "idle_by_span", "device_scopes", "step_module",
        "step_executions")}
    scopes = tr.get("device_scopes")
    if scopes and tr.get("step_executions"):
        out["device_ms_per_step"] = {
            k: 1e3 * v / tr["step_executions"] for k, v in scopes.items()}
        out["busy_ms_per_step"] = 1e3 * busy / tr["step_executions"]
        out["unscoped_share_of_busy"] = scopes.get(progtrace.UNSCOPED, 0.0) \
            / busy if busy else None
    worker = tr.get("host_spans", {}).get("serve_worker")
    if worker:
        batches = worker.get("serve.dispatch", {}).get("count", 0)
        out["serve_ms_per_batch"] = {
            k: 1e3 * v["s"] / batches for k, v in worker.items()} \
            if batches else {}
        out["serve_worker_span_cover"] = \
            tr["span_cover_s"]["serve_worker"] / tr["window_s"]
    idle = tr.get("idle_by_span")
    if idle:
        total = sum(idle.values())
        out["idle_named_share"] = 1.0 - idle.get(progtrace.NO_SPAN, 0.0) \
            / total if total else None
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, nargs="+", choices=(0, 1),
                    default=[1])
    args = ap.parse_args(argv)
    bench = harness.benchmark()
    files = harness.cell_files(bench, args.workload)
    chips = int(files["cell"]["chips"])
    devices = harness.require_tpu(chips)[:chips]
    harness.enable_compile_cache()
    for seed in args.seeds:
        for trace in args.trace:
            rec = measure(files, seed=seed, seconds=args.seconds,
                          trace=bool(trace), devices=devices)
            row = summary(bench, args.workload, rec, bool(trace))
            row.update(seed=seed, device=devices[0].device_kind)
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
