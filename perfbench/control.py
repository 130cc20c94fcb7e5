"""Readings that set a cell's correctness limits, on the chip.

    python3 perfbench/control.py --workload gfm_pretrain --variant program \\
        --seeds 11 12 13 --seconds 1

Runs the cell's runner at its own size once per seed, in one process, and
prints each number compared (one JSON line per seed). ``--variant``:

  * ``program``: the program as the configuration states it; the lower
    reading of each number;
  * ``control``: the program's own lower-precision path (the configuration
    with ``compute_dtype`` bfloat16), which the check has to fail;
  * ``half_batch`` (training): each step sees half of its batch, the mean
    taken over the rest;
  * ``wrong_head`` (serving): each batch is answered by the next head.

Not part of a benchmark run; the limits in ``perfbench/cells/`` come from
these readings (see PERF.md).
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

from perfbench import harness  # noqa: E402

FAULTS = {}


def fault(fn):
    FAULTS[fn.__name__] = fn
    return fn


@fault
def half_batch(step):
    """The step sees the first half of every source's rows."""
    def broken(state, batch):
        half = {k: v[:, :max(1, v.shape[1] // 2)] for k, v in batch.items()}
        return step(state, half)
    return broken


@fault
def wrong_head(srv):
    """Every batch is computed with the next head's parameters."""
    heads = list(srv._heads)
    srv._heads = heads[1:] + heads[:1]
    srv._exec.clear()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--variant", default="program",
                    choices=["program", "control"] + sorted(FAULTS))
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--out", default=os.path.join(ROOT, ".perfbench_out",
                                                  "control.jsonl"))
    args = ap.parse_args(argv)
    files = harness.cell_files(harness.benchmark(), args.workload)
    devices = harness.require_tpu(int(files["cell"]["chips"]))
    devices = devices[:int(files["cell"]["chips"])]
    harness.enable_compile_cache()
    if args.variant == "control":
        files["config"] = dict(files["config"], compute_dtype="bfloat16")
    runner = harness.load_module(os.path.join(
        harness.BENCH_DIR, "runners", files["traffic"]["runner"] + ".py"),
        "runner")
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    for seed in args.seeds:
        t0 = time.perf_counter()
        rec = runner.run(config=files["config"], traffic=files["traffic"],
                         limits=files["limits"], seed=seed,
                         seconds=args.seconds, trace=False, devices=devices,
                         t_start=t0, fault=FAULTS.get(args.variant))
        row = {"workload": args.workload, "variant": args.variant,
               "seed": seed, "setup_s": rec["setup_s"],
               "run_s": time.perf_counter() - t0,
               **{c["name"]: c["value"] for c in rec["checks"]}}
        print(json.dumps(row), flush=True)
        with open(args.out, "a") as f:
            f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
