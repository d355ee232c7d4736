"""Benchmark harness entry point: one benchmark per paper table/figure plus
the roofline aggregation and the beyond-paper engineering tables.

  PYTHONPATH=src python -m benchmarks.run [--only fig4,fig5,...]

Prints `name,value,derived` CSV rows; details land in experiments/bench/.
Every benchmark runs in a child process of its own and this parent never
imports JAX: a process that has touched JAX holds the accelerator, and the
benchmarks that start their own children (serve, gossip) need it free.  A
child that fails makes the whole run exit non-zero.
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys
import time

from benchmarks.common import ROOT

MODULES = {
    "fig4": "fig4_convergence",
    "fig5": "fig5_denoise",
    "table3": "table3_auc",
    "table4": "table4_auc_huber",
    "kernel": "kernel_fusion",
    "gossip": "gossip_modes",
    "serve": "serve_throughput",
    "roofline": "roofline",
}
ALL = list(MODULES)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", type=str, default=None,
                    help=f"comma list from {ALL}")
    args = ap.parse_args()
    which = args.only.split(",") if args.only else ALL
    unknown = [w for w in which if w not in MODULES]
    if unknown:
        raise SystemExit(f"unknown benchmarks {unknown}; options: {ALL}")

    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (str(ROOT / "src"), str(ROOT), env.get("PYTHONPATH")) if p
    )
    print("name,value,derived", flush=True)
    failures = []
    for name in which:
        t0 = time.time()
        proc = subprocess.run(
            [sys.executable, "-m", f"benchmarks.{MODULES[name]}"],
            cwd=str(ROOT), env=env,
        )
        if proc.returncode != 0:
            failures.append(name)
            print(f"{name}/FAILED,{proc.returncode},", flush=True)
            continue
        print(f"{name}/elapsed_s,{time.time() - t0:.1f},", flush=True)
    if failures:
        raise SystemExit(f"benchmarks failed: {failures}")


if __name__ == "__main__":
    main()
