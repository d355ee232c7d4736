"""Beyond-paper engineering table: convergence-vs-communication of the
production gossip schedules (exact / exact_fista / ring / ring_q8 /
ring_async plus graph-topology, time-varying graph_tv, and hierarchical
two-pod hier rows) on a forced multi-device host mesh.

Reports, per mode (and per graph topology / combiner schedule): iterations
to reach the target SNR, the combiner's mixing rate (second-largest
singular value of A — the gossip contraction factor, so
convergence-vs-lambda_2 is measurable across topologies; time-varying rows
report the WINDOWED rate sigma_2(window product)^(1/period), hierarchical
rows the EFFECTIVE two-level rate), bytes-on-wire per iteration per device
(analytic; averaged over the period for time-varying schedules), and total
wire bytes to target — the quantity the int8 error-feedback and FISTA modes
exist to cut.  The static-vs-time-varying pairs (graph:ring_metropolis /
graph:torus vs graph_tv:*) make the cost of a changing network directly
readable; the hierarchical rows (two-level hier and the 3-level chain row)
additionally split the wire bytes PER LEVEL — `wire_bytes_per_iter_per_level`
lists one entry per chain level, innermost (model) first — since the outer
hops are the bandwidth-constrained links the q8 wire format and per-level
gossip strides exist to relieve.  Two-level rows keep the legacy per-axis
keys (model-axis / pod-axis) as aliases of levels 0 / 1.  The 3-level chain
row (strides 1/2/4, q8 on both outer hops) runs on a (2, 2, 1, 2) debug
mesh and is included in smoke mode so CI exercises the chain path.

Each row also reports the solve body's one-time XLA compile seconds and
its optimized-HLO FLOPs per gossip iteration (`launch/hlo_cost.
analyze_compiled` on the AOT-compiled first sweep point) — the
benchmark-scale companion of the probe-scale pins tools/analyze's
cost-budget gate enforces — saved as a side table to compile_cost.json.

The output schema of the saved JSONs is documented in docs/BENCHMARKS.md.

Reduced-size mode: set BENCH_SMOKE=1 (the CI benchmark smoke job does) for
a smaller problem, shorter sweep, a lower SNR target, and a single
two-level hierarchical row on the (2, 1, 2) pod mesh (plus the 3-level
chain row).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.common import ROOT, emit, save_json

SCRIPT = r"""
import dataclasses, json, sys, time
import jax, jax.numpy as jnp
from repro.core.conjugates import make_task
from repro.core.distributed import DistributedSparseCoder, DistConfig, make_debug_mesh
from repro.core.inference import fista_infer, snr_db
from repro.launch.hlo_cost import analyze_compiled

P = json.loads(sys.argv[1])

res, reg = make_task("nmf", gamma=0.05, delta=0.1)
mesh = make_debug_mesh(model=8, data=1)
# Hierarchical rows run on a multi-pod mesh: (pods, 1, model) with the same
# total agent count as the flat rows in full mode, (2, 1, 2) in smoke mode
# (the path the CI bench-smoke lane exercises).
hier_pods, hier_model = P["hier_mesh"]
hier_mesh = make_debug_mesh(model=hier_model, data=1, pods=hier_pods)
# The 3-level chain row runs on the (2, 2, 1, 2) debug mesh — axes
# ("pod2", "pod", "data", "model"), 8 devices like the flat rows.
chain_mesh = make_debug_mesh(model=2, data=1, pods=2, outer=(2,))
M, K, B = P["M"], P["K"], P["B"]
W = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (M, K)))
W = W / jnp.linalg.norm(W, axis=0)
x = jax.random.normal(jax.random.PRNGKey(2), (B, M))
nu_ref = fista_infer(res, reg, W, x, iters=P["ref_iters"])

# Row name -> DistConfig.  graph:* rows sweep the paper's Sec.-IV-B regime
# (arbitrary doubly-stochastic combiners); graph_tv:* rows sweep the
# time-varying regime of Daneshmand et al. (the combiner changes every
# iteration); hier* rows sweep the two-level (pod x model) Kronecker
# composition — dense torus intra-pod, sparse ring inter-pod — so static,
# time-varying, and hierarchical convergence can all be read against the
# (windowed / effective) mixing rate.
ROWS = {mode: DistConfig(mode=mode, iters=1) for mode in
        ["exact", "exact_fista", "ring", "ring_q8", "ring_async"]}
for t in ["ring_metropolis", "torus", "erdos"]:
    ROWS[f"graph:{t}"] = DistConfig(mode="graph", iters=1, topology=t)
ROWS["graph_tv:alternating"] = DistConfig(
    mode="graph_tv", iters=1,
    topology_schedule="alternating:ring_metropolis,torus")
ROWS["graph_tv:erdos_resampled"] = DistConfig(
    mode="graph_tv", iters=1, topology_schedule="erdos_resampled",
    schedule_period=4)
# graph_tv under seeded link failures: the alternating base degraded by a
# 30% per-step Bernoulli edge dropout (Metropolis-renormalized survivors).
# Read against graph_tv:alternating, the row prices CHURN: same base
# network, mixing_rate becomes the windowed rate of the realized failure
# trace and iters_to_target the convergence cost of the degradation.
ROWS["graph_tv:linkfail"] = DistConfig(
    mode="graph_tv", iters=1,
    topology_schedule="alternating:ring_metropolis,torus",
    failure_p=0.3, failure_seed=5, failure_steps=4)
# push-sum (ratio consensus) over the row-stochastic-only directed star:
# the weight channel adds 4 bytes per message next to the payload — the
# wire price of surviving directed-only communication windows.
ROWS["push:distar"] = DistConfig(mode="push", iters=1, topology="distar")
# hier: the pure Kronecker composition (pod hop every iteration);
# hier_q8: the full bandwidth-saving configuration — int8 wire format on
# the inter-pod hop AND a pod_gossip_every=2 sparse stride.
ROWS["hier:torus+ring_metropolis"] = DistConfig(
    mode="hier", iters=1, topology="torus", pod_topology="ring_metropolis")
if not P["smoke"]:
    ROWS["hier_q8"] = DistConfig(
        mode="hier_q8", iters=1, topology="torus",
        pod_topology="ring_metropolis", pod_gossip_every=2)
# chain: the 3-level (chip x pod x rack) Kronecker chain — fp32 model hop
# every iteration, q8 pod hop every 2nd, q8 rack hop every 4th.  Included
# in smoke mode so CI exercises the N-level path on every push.
ROWS["chain:3level"] = DistConfig(
    mode="chain", iters=1,
    levels="ring_metropolis,ring_metropolis:2:q8,full:4:q8")

out = {}
for name, base_cfg in ROWS.items():
    hier = base_cfg.mode in ("hier", "hier_q8", "chain")
    row_mesh = (chain_mesh if base_cfg.mode == "chain"
                else hier_mesh if hier else mesh)
    mix = None
    reached = None
    per_iter = None
    per_model = None
    per_pod = None
    per_level = None
    period = 1
    pod_every = 1
    compile_s = None
    flops_per_iter = None
    for iters in P["sweep"]:
        cfg = dataclasses.replace(base_cfg, iters=iters)
        coder = DistributedSparseCoder(row_mesh, res, reg, cfg)
        if mix is None:
            # static rows: sigma_2(A); time-varying rows: the windowed rate
            # sigma_2(window product)^(1/period); hier rows: the effective
            # two-level rate
            info = coder.combiner_info()
            mix = info["mixing_rate"]
            period = info.get("schedule_period", 1)
            pod_every = info.get("pod_gossip_every", 1)
            b_loc = B  # data=1 here
            # The engine's own analytic byte model — one (axis, bytes/iter)
            # pair per gossip level, strides and wire formats averaged in.
            # tools/analyze's jaxpr layer cross-checks these exact numbers
            # against the traced collectives (rule: wire-bytes), so this
            # table cannot silently drift from the compiled protocol.
            pairs = coder.wire_bytes_per_iter(b_loc, M)
            per_iter = sum(v for _, v in pairs)
            if hier:
                # per-level split, innermost (model) level first
                per_level = [v for _, v in pairs]
                if len(per_level) == 2:
                    # legacy per-axis aliases for the two-level rows
                    per_model, per_pod = per_level
        Ws, xs = coder.shard(W, x)
        if compile_s is None:
            # AOT-compile the solve body once (the first sweep point) and
            # price its optimized HLO — the same analyze_compiled numbers
            # tools/analyze's cost-budget gate pins in budgets.json, here
            # at benchmark scale and normalized per gossip iteration.
            t0c = time.perf_counter()
            compiled = coder._solve.lower(
                Ws, xs, jnp.asarray(0, jnp.int32)).compile()
            compile_s = time.perf_counter() - t0c
            costs = analyze_compiled(compiled)
            flops_per_iter = float(costs.flops) / iters
        nu, _ = coder.solve(Ws, xs)
        if float(snr_db(nu_ref, nu)) >= P["target_db"]:
            reached = iters
            break
    out[name] = {
        "iters_to_target": reached,
        "mixing_rate": mix,
        "schedule_period": period,
        "pod_gossip_every": pod_every,
        "wire_bytes_per_iter_per_dev": per_iter,
        "wire_bytes_per_iter_model_axis": per_model,
        "wire_bytes_per_iter_pod_axis": per_pod,
        "wire_bytes_per_iter_per_level": per_level,
        "wire_bytes_to_target": (reached * per_iter) if reached else None,
        "compile_s": round(compile_s, 3),
        "flops_per_iter": flops_per_iter,
    }
print(json.dumps(out))
"""


def run(smoke: bool | None = None):
    if smoke is None:
        smoke = os.environ.get("BENCH_SMOKE", "0").lower() not in ("", "0", "false")
    params = (
        {"M": 32, "K": 64, "B": 8, "ref_iters": 800, "target_db": 20.0,
         "sweep": [25, 50, 100, 200, 400, 800, 1600, 3200],
         "hier_mesh": [2, 2], "smoke": True}
        if smoke
        else {"M": 64, "K": 256, "B": 16, "ref_iters": 2000, "target_db": 40.0,
              "sweep": [25, 50, 100, 200, 400, 800, 1600, 3200, 6400, 12800],
              "hier_mesh": [2, 4], "smoke": False}
    )

    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(ROOT / "src")
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT, json.dumps(params)], env=env,
        capture_output=True, text=True, timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(
            f"gossip: child exited {proc.returncode}: {proc.stderr[-2000:]}"
        )
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    base = out["exact"]["wire_bytes_to_target"]
    for mode, r in out.items():
        emit(f"gossip/{mode}/iters_to_{params['target_db']:.0f}db", r["iters_to_target"])
        emit(f"gossip/{mode}/mixing_rate", f"{r['mixing_rate']:.4f}")
        if r["wire_bytes_per_iter_pod_axis"] is not None:
            # two-level hierarchical rows: the legacy per-axis split (the
            # pod axis is the bandwidth-constrained inter-pod link)
            emit(f"gossip/{mode}/wire_bytes_per_iter_model_axis",
                 r["wire_bytes_per_iter_model_axis"])
            emit(f"gossip/{mode}/wire_bytes_per_iter_pod_axis",
                 r["wire_bytes_per_iter_pod_axis"])
        if r.get("wire_bytes_per_iter_per_level"):
            # hierarchical family: one entry per chain level, innermost
            # (model) level first
            for i, v in enumerate(r["wire_bytes_per_iter_per_level"]):
                emit(f"gossip/{mode}/wire_bytes_per_iter_level{i}", v)
        if r["wire_bytes_to_target"]:
            emit(f"gossip/{mode}/wire_bytes_to_{params['target_db']:.0f}db",
                 r["wire_bytes_to_target"],
                 f"{base / r['wire_bytes_to_target']:.1f}x fewer than exact" if base else "")
        emit(f"gossip/{mode}/compile_s", r["compile_s"])
        emit(f"gossip/{mode}/flops_per_iter", f"{r['flops_per_iter']:.0f}")
    save_json("gossip_modes", out)
    # compile-cost side table (schema: docs/BENCHMARKS.md) — the benchmark-
    # scale companion of tools/analyze/budgets.json's probe-scale pins
    save_json("compile_cost", {
        mode: {"compile_s": r["compile_s"],
               "flops_per_iter": r["flops_per_iter"]}
        for mode, r in out.items()
    })
    return out


if __name__ == "__main__":
    run()
