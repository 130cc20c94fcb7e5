"""Offered-rate sweep of a serving cell, to find its knee once, on the chip.

    python3 perfbench/sweep.py --workload gfm_serve_screen --seed 5 \\
        --seconds 10 --rates 200 400 800 1600

Runs the cell's serving runner at each offered rate in one process and
prints, per rate, what was answered and how latency moved from the first
to the last tenth of the window. The knee is the highest rate whose
answered rate keeps up with the offered one and whose latency does not grow
over the window; the cell's traffic file then fixes a rate below it. Not
part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# libtpu would otherwise write its logs under a fixed path in /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402

from perfbench import harness  # noqa: E402
from perfbench.run import run_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    files = harness.cell_files(harness.benchmark(), args.workload)
    devices = harness.require_tpu(int(files["cell"]["chips"]))
    harness.enable_compile_cache()
    for rate in args.rates:
        files["traffic"] = dict(files["traffic"], rate_per_s=rate)
        rec = run_cell(files, seed=args.seed, seconds=args.seconds,
                       trace=False, devices=devices,
                       t_start=time.perf_counter())
        lat = rec["latency_ms"]
        tenth = max(1, len(lat) // 10)
        row = {"offered_per_s": rate,
               "answered_per_s": rec["answered_in_window"] / rec["window_s"],
               "p50_ms": float(np.percentile(lat, 50)),
               "p95_ms": float(np.percentile(lat, 95)),
               "p99_ms": float(np.percentile(lat, 99)),
               "first_tenth_p50_ms": float(np.median(lat[:tenth])),
               "last_tenth_p50_ms": float(np.median(lat[-tenth:])),
               "batch_fill": rec["batch_real"] / max(rec["batch_slots"], 1),
               "late_p99_ms": float(np.percentile(rec["generator_late_ms"],
                                                  99)),
               "failed": rec["failed"],
               "checks_ok": harness.checks_ok(rec["checks"])}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
