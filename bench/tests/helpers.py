"""A tiny cell for CPU runs of the harness: the act configuration with its
widths cut to CPU size, everything else as committed."""

import json
import pathlib
import time

import jax

import run
import traffic
from conftest import BENCH


def tiny_config(model: int = 1, **over) -> dict:
    cfg = json.loads((BENCH / "configs" / "act-m8192-k65536.json").read_text())
    cfg.update({"m": 64, "atoms": 256, "iters": 60, "micro_batch": 16, "mesh": [1, model],
                **over})
    return cfg


def tiny_run(mix_name: str, model: int = 1, wrap=None, seconds: float = 2.0,
             seed: int = 2**31 + 99, cfg=None, bench: pathlib.Path = BENCH,
             cell=None, entries=None, mix_over=None) -> dict:
    cfg = cfg or tiny_config(model)
    mix = traffic.load(mix_name, bench)
    if mix["arrivals"] == "poisson":
        mix["rate_per_s"] = 150.0
    mix.update(mix_over or {})
    cell = cell or {"name": "tiny", "config": cfg["name"], "traffic": mix_name, "chips": model}
    return run.run_cell(cell, cfg, mix, seed, seconds, False, entries or [],
                        jax.devices()[:model], wrap=wrap, t_process=time.perf_counter(),
                        bench=bench)
