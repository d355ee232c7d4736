"""Streaming-service benchmark: samples/sec + latency percentiles of the
online dictionary service (repro.runtime.service) on a forced host mesh,
including one mid-stream elastic growth event — plus the serving-plane
scaling runs: the same stream through the Router front-end with 1 and 2
replicas (repro.runtime.serving), each with one rolling publish
mid-stream, recording aggregate samples/s and p99 vs replica count.

Runs `repro.launch.serve_dict --json` in subprocesses (the forced device
count must be set before jax initializes) and re-emits the BENCH payloads
as CSV rows + experiments/bench/serve_throughput.json with one entry per
configuration: "single" (the learner-on single-service drill, the
pre-serving-plane payload shape) and "replicas=1" / "replicas=2".

Reduced-size mode: set BENCH_SMOKE=1 (the CI benchmark smoke job does) to
cut samples/iterations so the perf path is exercised in seconds.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.common import ROOT, emit, save_json


def _serve_dict(extra_args, label: str):
    """One serve_dict --json subprocess; returns its BENCH payload and
    raises when the child failed (a run missing rows must not pass)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(ROOT / "src")
    cmd = [sys.executable, "-m", "repro.launch.serve_dict", "--json", *extra_args]
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True, timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(
            f"serve/{label}: serve_dict exited {proc.returncode}: "
            f"{proc.stderr[-2000:]}"
        )
    bench_lines = [l for l in proc.stdout.splitlines() if l.startswith("BENCH ")]
    return json.loads(bench_lines[-1][len("BENCH "):])


def run(smoke: bool | None = None):
    if smoke is None:
        smoke = os.environ.get("BENCH_SMOKE", "0").lower() not in ("", "0", "false")
    samples, iters, grow_at = (160, 60, 80) if smoke else (600, 150, 300)

    results = {}

    # -- single-service drill (learner on, one mid-stream growth) ---------
    out = _serve_dict([
        "--samples", str(samples), "--iters", str(iters),
        "--grow-at", str(grow_at), "--grow-model", "2",
        "--mesh", "1x2", "--micro-batch", "16",
    ], "single")
    results["single"] = out
    emit("serve/samples_per_s", f"{out['samples_per_s']:.1f}")
    for p in ("p50", "p95", "p99"):
        if p in out.get("latency_ms", {}):
            emit(f"serve/latency_{p}_ms", f"{out['latency_ms'][p]:.1f}")
    emit("serve/fit_steps", out["fit_steps"])
    emit("serve/grow_events", len(out["grow_events"]),
         "mid-stream model-axis growth" if out["grow_events"] else "")

    # -- serving-plane scaling: router with 1 and 2 replicas --------------
    # Same stream and per-replica mesh; one rolling publish mid-stream so
    # the fan-out path is always on the measured path.  8 forced host
    # devices carry 2 replicas x (1x2) with room to spare.
    for n in (1, 2):
        out = _serve_dict([
            "--samples", str(samples), "--iters", str(iters),
            "--grow-at", "0", "--mesh", "1x2", "--micro-batch", "16",
            "--replicas", str(n), "--router",
            "--publish-at", str(samples // 2),
        ], f"r{n}")
        results[f"replicas={n}"] = out
        emit(f"serve/r{n}/agg_samples_per_s", f"{out['agg_samples_per_s']:.1f}")
        if out.get("p99_ms") is not None:
            emit(f"serve/r{n}/latency_p99_ms", f"{out['p99_ms']:.1f}")
        emit(f"serve/r{n}/rerouted", out["rerouted"])
        emit(f"serve/r{n}/publishes", out["publishes"])

    save_json("serve_throughput", results)
    return results


if __name__ == "__main__":
    run()
