"""Run one benchmark cell once on the chip and print its result line.

    python3 perfbench/run.py --workload gfm_pretrain --seed 7 --seconds 30 \\
        --trace 0

Everything the cell needs is found by name from ``BENCHMARK.json`` (see
``perfbench/harness.py``). The run makes its data and weights from
``--seed``, warms up every shape its traffic uses (set-up), measures for
``--seconds`` (``--trace 1``: the traffic's shorter ``trace_seconds``, under
the profiler), then checks what the timed path produced against the plain
reference. The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer ones), ``device``, with
``--trace 1`` a ``breakdown``, and last ``checks``: each number compared
beside its limit. The same numbers are the last lines of standard error.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# libtpu would otherwise write its logs under a fixed path in /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from perfbench import harness  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def run_cell(files: dict, *, seed: int, seconds: float, trace: bool,
             devices, t_start: float, log=harness.log) -> dict:
    """Drive one cell with its traffic's runner; returns the run's record
    (see ``perfbench/runners/``)."""
    traffic = files["traffic"]
    runner = harness.load_module(
        os.path.join(harness.BENCH_DIR, "runners", traffic["runner"] + ".py"),
        "runner_" + traffic["runner"])
    return runner.run(config=files["config"], traffic=traffic,
                      limits=files["limits"], seed=seed, seconds=seconds,
                      trace=trace, devices=devices, t_start=t_start, log=log)


def main(argv=None) -> int:
    args = parse(argv)
    bench = harness.benchmark()
    files = harness.cell_files(bench, args.workload)
    chips = int(files["cell"]["chips"])
    devices = harness.require_tpu(chips)[:chips]
    harness.log(f"perfbench: {args.workload} on {chips} x "
                f"{devices[0].device_kind}; compile cache "
                f"{harness.enable_compile_cache()}")
    rec = run_cell(files, seed=args.seed, seconds=args.seconds,
                   trace=bool(args.trace), devices=devices,
                   t_start=T_START)
    metrics = {}
    for m in harness.metrics_for(bench, args.workload, bool(args.trace)):
        value = harness.read_metric(m["name"], rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    import jax
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": rec["memory_peak_bytes"]}
    breakdown = None
    if args.trace:
        tr = rec.get("trace") or {}
        busy = list(tr.get("busy_s", {}).values())
        device["busy_s"] = sum(busy) / len(busy) if busy else 0.0
        device["window_s"] = tr.get("window_s", 0.0)
        breakdown = {"device_ops": tr.get("device_ops", []),
                     "idle_gaps": tr.get("idle_gaps", [])}
    checks = rec["checks"]
    harness.print_checks(checks)
    print(harness.result_line(
        correct=harness.checks_ok(checks), attempted=rec["attempted"],
        failed=rec["failed"], metrics=metrics, device=device, checks=checks,
        breakdown=breakdown), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
