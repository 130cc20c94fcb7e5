"""Benchmark harness — one entry per paper table/figure.

  table1/table2  -> bench_convergence  (cross-source MAE matrices, §5.1)
  fig4           -> bench_scaling      (weak/strong MTL-par vs MTL-base;
                                        subprocess on the CPU backend: needs
                                        512 host devices)
  roofline       -> roofline           (per arch x shape terms from the
                                        dry-run artifact, §Roofline)
  kernels        -> bench_kernels      (attention / segment-sum layers)

Prints ``name,us_per_call,derived`` CSV.

  PYTHONPATH=src python -m benchmarks.run [--only table1,fig4] [--fast]
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def run_convergence(fast: bool):
    from benchmarks import bench_convergence as bc
    import json
    res = bc.run(n_samples=96 if fast else 192, steps=80 if fast else 250,
                 hidden=32 if fast else 48, verbose=False)
    claims = bc.check_claims(res)
    os.makedirs("results", exist_ok=True)
    json.dump({"results": res, "claims": claims},
              open("results/convergence.json", "w"), indent=1)
    print(f"table1_energy_mae,{res['wall_s'] * 1e6:.0f},"
          f"mtl_wins={claims['mtl_wins_of_5']}/5;"
          f"offdiag_ratio={claims['offdiag_over_diag']:.1f}")
    print(f"table2_force_mae,{res['wall_s'] * 1e6:.0f},"
          f"worst_mtl_E={claims['worst_mtl_energy_mae']:.4f}")


def run_scaling():
    # a forced-host-device sweep: the child runs on the CPU backend (and its
    # output says so), so it never competes with this process for a chip
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    p = subprocess.run([sys.executable, "-m", "benchmarks.bench_scaling"],
                       env=env, capture_output=True, text=True,
                       cwd=os.path.join(os.path.dirname(__file__), ".."))
    if p.returncode != 0:
        print(f"fig4_scaling,0,FAILED:{p.stderr[-300:]}")
        return
    for line in p.stdout.splitlines():
        if line and not line.startswith("name,"):
            print(line)


def run_roofline():
    from benchmarks import roofline
    path = "results/dryrun.json"
    if not os.path.exists(path):
        print("roofline,0,SKIPPED(no results/dryrun.json — run repro.launch.dryrun)")
        return
    for mesh in ("pod", "pod32x8", "multipod"):
        for r in roofline.table(path, mesh=mesh):
            step = max(r["compute_s"], r["memory_s"], r["collective_s"])
            print(f"roofline[{mesh}]/{r['arch']}/{r['shape']},{step * 1e6:.1f},"
                  f"dominant={r['dominant']};useful={r['useful_ratio']:.2f}")


def run_kernels():
    from benchmarks import bench_kernels as bk
    bk.bench_attention()
    bk.bench_segment_sum()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: table1,fig4,roofline,kernels")
    ap.add_argument("--fast", action="store_true")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else \
        {"table1", "fig4", "roofline", "kernels"}
    print("name,us_per_call,derived")
    if {"table1", "table2"} & only:
        run_convergence(args.fast)
    if "kernels" in only:
        run_kernels()
    if "roofline" in only:
        run_roofline()
    if "fig4" in only:
        run_scaling()


if __name__ == "__main__":
    main()
