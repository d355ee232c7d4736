#!/usr/bin/env python3
"""Sweep of offered rates for an open-loop cell, to find its knee: the
highest rate at which the backlog does not grow over the window.  Run once
when the cell is defined; the rate the cell runs at is then written into
its traffic file as a number.

    python3 bench/knee.py --workload <cell> --rates 100 120 130 --seconds 30

For each rate, one run of the cell in this process (set-up compiles once),
printing the rate offered, the rate coded, p50/p95 latency, and the growth
of latency from the first to the last quarter of the window, which stays
near zero below the knee and grows with the window above it.
"""

from __future__ import annotations

import argparse
import json
import time

import jax

import run
from window import percentile


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=2**31 + 1000)
    args = ap.parse_args()
    man = run.manifest()
    cell, cfg, mix = run.cell_parts(args.workload, man)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit("knee: needs the TPU")
    run.use_compile_cache()
    for i, r in enumerate(args.rates):
        ctx = {}
        out = run.run_cell(cell, cfg, dict(mix, rate_per_s=r), args.seed + i, args.seconds,
                           False, [], devices[:cell["chips"]], t_process=time.perf_counter(),
                           ctx_out=ctx)
        lat = [d - s for s, d in zip(ctx["due"], ctx["done"]) if d is not None]
        due = ctx["due"]
        q = args.seconds / 4
        first = [d - s for s, d in zip(due, ctx["done"]) if d is not None and s < q]
        last = [d - s for s, d in zip(due, ctx["done"]) if d is not None and s >= 3 * q]
        print(json.dumps({
            "rate_offered": r, "samples": len(due), "correct": out["correct"],
            "coded_per_s": len(lat) / ctx["t_close"],
            "drain_s": ctx["t_close"] - args.seconds,
            "p50_ms": 1e3 * percentile(lat, 50), "p95_ms": 1e3 * percentile(lat, 95),
            "growth_ms": 1e3 * (sum(last) / len(last) - sum(first) / len(first)),
            "batch_fill": 100.0 * ctx["stats"]["coded"] / (ctx["stats"]["batches"] * cfg["micro_batch"]),
        }), flush=True)


if __name__ == "__main__":
    main()
