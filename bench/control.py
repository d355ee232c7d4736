#!/usr/bin/env python3
"""The control of the comparison that decides `correct`, as the
configuration's kind makes it (`control` of bench/kinds/<kind>.py; the
coding kind's is described here): the reference put in the program's
place, computed with products at the platform's HIGH
precision (three bfloat16 passes, the precision just below the
float32-at-HIGHEST the configurations state), then compared with the
reference at HIGHEST by the very numbers, limits and verdict a benchmark
run uses.  It has to come out as not correct.

    python3 bench/control.py --workload <cell> --seeds 1 2 3

Runs at the cell's own sizes, on the cell's chips: the samples a run
would re-solve and the first fit steps' batches, drawn from each seed as a
run draws them.  Prints one JSON line per seed with each number beside
its limit and the verdict, and exits non-zero if the control of any seed
came out correct.  The benchmark's own runs do not run this.

`control_numbers` also takes "3pass", the same three passes written out
(hi*hi + hi*lo + lo*hi with float32 accumulation), for the CPU tests: a
CPU has no HIGH.  On a TPU the written-out split reads like one bfloat16
pass, as if the compiler folded its low part away, so it is no control
there.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import numpy as np
from jax.sharding import Mesh

import run
from reference import judge, verdict


def control_numbers(cfg: dict, mix: dict, seed: int, devices, matmul: str = "high") -> dict:
    """The numbers of the configuration's kind's control (`control` of
    bench/kinds/<kind>.py) for one seed: W0 and the digested atoms as a run
    makes them, and as many fit steps followed as a run follows."""
    data, model = cfg["mesh"]
    mesh = Mesh(np.asarray(devices).reshape(data, model), ("data", "model"))
    kind = run.kind_of(cfg)
    if not hasattr(kind, "control"):
        raise SystemExit(f"control: kind {cfg.get('kind', run.DEFAULT_KIND)!r} of "
                         f"{cfg['name']} has no control")
    cols, _ = run.digest_atoms(cfg, seed)
    follow = run.FOLLOW_FITS if mix["learn"] else 0
    return kind.control(cfg, mix, seed, kind.w0(cfg, mesh, seed), cols, follow, matmul)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    cell, cfg, mix = run.cell_parts(args.workload, run.manifest())
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        raise SystemExit("control: needs the cell's TPU chips")
    run.use_compile_cache()
    passed = []
    for seed in args.seeds:
        t = time.perf_counter()
        checks = judge(control_numbers(cfg, mix, seed, devices[:cell["chips"]]), cfg["limits"])
        correct = verdict(checks)
        if correct:
            passed.append(seed)
        print(json.dumps({"workload": args.workload, "seed": seed, "correct": correct,
                          "seconds": time.perf_counter() - t, "checks": checks}), flush=True)
    if passed:
        print(f"control: came out correct on seeds {passed}", file=sys.stderr)
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
