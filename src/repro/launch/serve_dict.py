"""Online streaming dictionary service launcher.

Streams synthetic samples through the continuously-learning dictionary
service (repro.runtime.service): micro-batched coding against a
double-buffered snapshot, online `fit_batch` on the live copy, one
optional mid-stream elastic growth of the `model` axis, and one optional
mid-stream agent DRAIN (the inverse: departing ranks leave, survivors
keep their atom shards).

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
  PYTHONPATH=src python -m repro.launch.serve_dict \\
      --samples 600 --mesh 1x2 --grow-at 300 --grow-model 2

Churn drills compose: a time-varying run with seeded link failures that
drains agent 1 mid-stream (push-sum directed gossip works the same way
via --mode push --topology distar):

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
  PYTHONPATH=src python -m repro.launch.serve_dict \\
      --mode graph_tv --mesh 1x4 --fail-p 0.25 --fail-steps 6 \\
      --grow-at 0 --drain-at 300 --drain 1

Hierarchical (multi-pod) gossip takes a 3-D mesh 'PxDxM' plus the
inter-pod combiner kind and optional sparse-gossip stride:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
  PYTHONPATH=src python -m repro.launch.serve_dict \\
      --mode hier --mesh 2x1x4 --topology torus \\
      --pod-topology ring_metropolis --pod-gossip-every 2 --grow-at 0

An N-level Kronecker chain takes `--mode chain` with a `--levels` spec
(comma-separated `kind[:stride][:wire][:stale]`, innermost/model level
first) and a mesh with one leading dim per OUTER level, outermost first
('PxQxDxM' for three levels):

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
  PYTHONPATH=src python -m repro.launch.serve_dict \\
      --mode chain --mesh 2x2x1x2 \\
      --levels ring_metropolis,ring_metropolis:2:q8,full:4:q8 --grow-at 0

`--replicas N` (or `--router`) switches to the multi-replica serving
plane (repro.runtime.serving): N DictionaryService replicas on DISJOINT
device pools (each its own `--mesh`), fronted by the freshness-aware
Router; `--publish-at` triggers one rolling snapshot fan-out mid-stream.
Replicas serve a published snapshot, so fleet mode implies --no-learn
and disables the grow/drain drills:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
  PYTHONPATH=src python -m repro.launch.serve_dict \\
      --replicas 2 --mesh 1x2 --samples 400 --publish-at 200 --grow-at 0

On a chip, the same entry point runs a real-width deployment; `--platform
tpu` makes it refuse to run anywhere else.  A sparse dictionary over
Llama-3-70B-width residual activations, K = 8x M, on one chip:

  PYTHONPATH=src python -m repro.launch.serve_dict --platform tpu \\
      --m 8192 --atoms-per-agent 65536 --mesh 1x1 --iters 100 \\
      --gamma 0.05 --delta 0.2 --micro-batch 256 --max-wait-ms 1000 \\
      --samples 2048 --grow-at 0

Prints throughput (samples/s), per-sample latency percentiles, learner
progress, and the growth event; `--json` additionally emits one
machine-readable line (consumed by benchmarks/serve_throughput.py).  With
learning on, a run whose learner failed a step or took none exits
non-zero.  JAX's compile cache is `<repo>/.jax_cache` unless
JAX_COMPILATION_CACHE_DIR names another.
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.conjugates import make_task
from repro.core.distributed import DistConfig, DistributedSparseCoder
from repro.data.synthetic import sparse_stream
from repro.launch.mesh import require_platform, use_repo_compile_cache
from repro.runtime import dist
from repro.runtime.service import DictionaryService, ServiceConfig
from repro.runtime.serving import ReplicaSet, Router, RouterConfig, device_pools


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--task", type=str, default="sparse_svd")
    ap.add_argument("--gamma", type=float, default=0.25)
    ap.add_argument("--delta", type=float, default=0.05)
    ap.add_argument("--mode", type=str, default="exact_fista",
                    choices=["exact", "exact_fista", "ring", "ring_q8", "ring_async",
                             "graph", "graph_q8", "graph_async",
                             "graph_tv", "graph_tv_q8", "push", "push_q8",
                             "hier", "hier_q8", "chain"])
    ap.add_argument("--topology", type=str, default="ring_metropolis",
                    choices=["ring", "ring_metropolis", "torus", "erdos", "full",
                             "dicycle", "distar"],
                    help="graph-mode combiner kind (core/topology.make_topology); "
                         "the INTRA-POD kind for the hier modes; the directed "
                         "row-stochastic-only kinds (dicycle, distar) are for "
                         "the push-sum modes")
    ap.add_argument("--pod-topology", type=str, default="",
                    choices=["", "ring", "ring_metropolis", "torus", "erdos", "full"],
                    help="hier modes: INTER-POD combiner kind over the pod axis "
                         "(required for --mode hier/hier_q8)")
    ap.add_argument("--pod-gossip-every", type=int, default=1,
                    help="hier modes: fire the inter-pod hop every k-th "
                         "iteration (1 = every iteration)")
    ap.add_argument("--levels", type=str, default="",
                    help="chain mode: comma-separated level specs "
                         "'kind[:stride][:wire][:stale]', innermost (model) "
                         "level first — e.g. "
                         "'ring_metropolis,ring_metropolis:2:q8,full:4:q8' "
                         "(core/topology.parse_level_specs)")
    ap.add_argument("--topology-p", type=float, default=0.5,
                    help="erdos edge probability")
    ap.add_argument("--topology-seed", type=int, default=0,
                    help="erdos graph / time-varying sequence seed")
    ap.add_argument("--topology-schedule", type=str,
                    default="alternating:ring_metropolis,torus",
                    help="graph_tv modes: core/topology.make_topology_schedule "
                         "spec ('fixed:<kind>' | 'alternating:<k1>,<k2>,...' | "
                         "'erdos_resampled')")
    ap.add_argument("--schedule-period", type=int, default=2,
                    help="period of the erdos_resampled schedule")
    ap.add_argument("--fail-p", type=float, default=0.0,
                    help="graph_tv modes: per-step per-edge link-failure "
                         "probability; every realized step is Metropolis-"
                         "renormalized over the surviving links "
                         "(core/topology.link_failure_schedule)")
    ap.add_argument("--fail-seed", type=int, default=0,
                    help="seed of the per-step failure draws")
    ap.add_argument("--fail-steps", type=int, default=0,
                    help="distinct failure realizations before the trace "
                         "repeats (0 = the base schedule's own period)")
    ap.add_argument("--iters", type=int, default=150, help="dual iterations per solve")
    ap.add_argument("--m", type=int, default=32, help="data dimension")
    ap.add_argument("--atoms-per-agent", type=int, default=8)
    ap.add_argument("--mesh", type=str, default="1x2",
                    help="'DxM' (data x model), 'PxDxM' (pod x data x model "
                         "— required for the hier modes), or one leading dim "
                         "per outer chain level, outermost first (e.g. "
                         "'PxQxDxM' for a 3-level --levels spec)")
    ap.add_argument("--samples", type=int, default=600)
    ap.add_argument("--micro-batch", type=int, default=16)
    ap.add_argument("--max-wait-ms", type=float, default=20.0)
    ap.add_argument("--mu-w", type=float, default=0.1)
    ap.add_argument("--grow-at", type=int, default=300,
                    help="sample index of the elastic growth event (0 = never)")
    ap.add_argument("--grow-model", type=int, default=2,
                    help="extra model-axis agents added at --grow-at")
    ap.add_argument("--drain-at", type=int, default=0,
                    help="sample index of the agent-drain event (0 = never)")
    ap.add_argument("--drain", type=str, default="",
                    help="comma-separated model ranks decommissioned at "
                         "--drain-at (survivors keep their atom shards)")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="submit rate in samples/s (0 = as fast as possible)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="replica count for the multi-replica serving plane "
                         "(each replica gets its own --mesh on a DISJOINT "
                         "device pool; >1 implies --router)")
    ap.add_argument("--router", action="store_true",
                    help="front the fleet with the freshness-aware Router "
                         "even for --replicas 1 (measures the router's own "
                         "overhead against the single-service baseline)")
    ap.add_argument("--publish-at", type=int, default=0,
                    help="fleet mode: sample index of one rolling snapshot "
                         "publish (a perturbed dictionary fans out to the "
                         "replicas one at a time; 0 = never)")
    ap.add_argument("--no-learn", action="store_true")
    ap.add_argument("--use-kernel", action="store_true",
                    help="run the engine's hot loop through the fused Pallas "
                         "dict_dual_step kernel")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", action="store_true",
                    help="emit a single BENCH json line at the end")
    ap.add_argument("--platform", type=str, default="", choices=["", "cpu", "tpu"],
                    help="refuse to run unless JAX's devices are on this "
                         "platform ('' = whatever JAX finds)")
    return ap.parse_args(argv)


def run(argv=None) -> dict:
    """One serving run from command-line arguments; prints its report and
    returns a record of it: the service or fleet stats, and for a single
    service also the served (nu, y) per sample, the stream X, the initial
    dictionary W0 and the coder (for reference checks)."""
    args = parse_args(argv)
    if args.platform:
        require_platform(args.platform)
    use_repo_compile_cache()

    dims = [int(v) for v in args.mesh.split("x")]
    # How many AGENT levels the mesh must carry (model + outer levels):
    # the --levels spec length for chain mode, 2 for the hier shim, 1 flat.
    if args.mode == "chain":
        if not args.levels:
            raise SystemExit(
                "--mode chain needs a --levels spec "
                "(e.g. 'ring_metropolis,ring_metropolis:2:q8,full:4:q8')"
            )
        n_agent_levels = len([s for s in args.levels.split(",") if s.strip()])
    elif args.mode in ("hier", "hier_q8"):
        n_agent_levels = 2
    else:
        n_agent_levels = 1
    if len(dims) != n_agent_levels + 1:
        want = (
            "'DxM'" if n_agent_levels == 1
            else "'PxDxM'" if n_agent_levels == 2
            else f"{n_agent_levels + 1} dims (one per outer level, outermost "
                 f"first, then data x model)"
        )
        raise SystemExit(
            f"--mode {args.mode} needs a --mesh of {want}, got {args.mesh!r}"
        )
    *outer_dims, d, m_axis = dims  # outer levels OUTERMOST first
    outer = 1
    for v in outer_dims:
        outer *= v
    if args.grow_at >= args.samples:
        args.grow_at = 0  # growth point past the stream: never fires
    drain_ranks = [int(v) for v in args.drain.split(",") if v.strip()]
    if args.drain_at >= args.samples:
        args.drain_at = 0  # drain point past the stream: never fires
    if bool(args.drain_at) != bool(drain_ranks):
        raise SystemExit("--drain-at and --drain must be given together")
    if args.drain_at and args.grow_at and args.drain_at <= args.grow_at:
        raise SystemExit("--drain-at must come after --grow-at (the drain "
                         "ranks refer to the then-current model axis)")
    if args.replicas < 1:
        raise SystemExit(f"--replicas must be >= 1, got {args.replicas}")
    fleet_mode = args.replicas > 1 or args.router
    if fleet_mode:
        # Replicas serve a PUBLISHED snapshot (new dictionaries arrive via
        # the rolling publish fan-out, not per-replica learning), and the
        # grow/drain drills are single-service lifecycle drills.
        if args.grow_at or args.drain_at:
            print("fleet mode: disabling the grow/drain drills "
                  "(single-service lifecycle drills; see tests/test_serving.py "
                  "for the fleet lifecycle)")
            args.grow_at, args.drain_at, drain_ranks = 0, 0, []
        if not args.no_learn:
            print("fleet mode: replicas serve the published snapshot "
                  "(learning off; snapshots arrive via publish fan-out)")
            args.no_learn = True
        if args.publish_at >= args.samples:
            args.publish_at = 0  # publish point past the stream: never fires
    per_replica = outer * d * m_axis
    need = args.replicas * per_replica + (
        outer * d * args.grow_model if args.grow_at else 0
    )
    if jax.device_count() < need:
        raise SystemExit(
            f"need {need} devices for mesh {args.mesh} x {args.replicas} "
            f"replica(s) + growth; have {jax.device_count()} "
            f"(set XLA_FLAGS=--xla_force_host_platform_device_count=N)"
        )

    def build_mesh(devices=None):
        if outer_dims:
            # Axis names match DistConfig.level_axis: level 1 is the pod
            # axis, level i>=2 is "pod<i>"; mesh order is outermost-major.
            outer_names = tuple(
                dist.POD_AXIS if i == 1 else f"{dist.POD_AXIS}{i}"
                for i in range(n_agent_levels - 1, 0, -1)
            )
            return dist.make_mesh(
                (*outer_dims, d, m_axis),
                (*outer_names, dist.DATA_AXIS, dist.MODEL_AXIS),
                devices=devices,
            )
        return dist.make_mesh(
            (d, m_axis), (dist.DATA_AXIS, dist.MODEL_AXIS), devices=devices
        )

    res, reg = make_task(args.task, gamma=args.gamma, delta=args.delta)
    # one atom block per AGENT: the hierarchical family shards atoms over
    # (all outer levels) x model.
    k0 = args.atoms_per_agent * m_axis * outer
    dist_cfg = DistConfig(
        mode=args.mode, iters=args.iters, topology=args.topology,
        topology_p=args.topology_p, topology_seed=args.topology_seed,
        topology_schedule=args.topology_schedule,
        schedule_period=args.schedule_period,
        failure_p=args.fail_p, failure_seed=args.fail_seed,
        failure_steps=args.fail_steps,
        pod_topology=args.pod_topology,
        pod_gossip_every=args.pod_gossip_every,
        levels=args.levels,
        use_kernel=args.use_kernel,
    )
    svc_cfg = ServiceConfig(
        micro_batch=args.micro_batch,
        max_wait_s=args.max_wait_ms / 1e3,
        learn=not args.no_learn,
        mu_w=args.mu_w,
    )
    X = sparse_stream(args.samples, m=args.m, k_true=k0, nonneg=reg.nonneg,
                      seed=args.seed + 1)
    if fleet_mode:
        return _run_fleet(args, res, reg, dist_cfg, svc_cfg, build_mesh,
                          per_replica, k0, X)
    coder = DistributedSparseCoder(build_mesh(), res, reg, dist_cfg)
    W0 = coder.init_dictionary(jax.random.PRNGKey(args.seed), args.m, k0)
    comb = coder.combiner_info()

    print(f"serve_dict: task={args.task} mode={args.mode} mesh={args.mesh} "
          f"M={args.m} K={k0} micro_batch={args.micro_batch} "
          f"samples={args.samples} grow_at={args.grow_at or 'never'} "
          f"topology={comb['topology']} mixing_rate={comb['mixing_rate']:.3f} "
          f"schedule_period={comb.get('schedule_period', 1)} "
          f"pod_gossip_every={comb.get('pod_gossip_every', 1)}")
    for lv in comb.get("levels") or []:
        print(f"  level axis={lv['axis']} kind={lv['kind']} n={lv['n']} "
              f"stride={lv['gossip_every']} wire={lv['wire']} "
              f"stale={lv['stale']}")

    futures = []
    grow_fut = None
    drain_fut = None
    t0 = time.perf_counter()
    with DictionaryService(coder, W0, svc_cfg) as svc:
        for i in range(args.samples):
            if args.grow_at and i == args.grow_at:
                # let the pre-growth stream drain so the event lands truly
                # mid-stream (coding continues against the old snapshot
                # until the new coder/snapshot pair is published)
                futures[-1].result(timeout=600)
                grow_fut = svc.grow(args.grow_model, jax.random.PRNGKey(args.seed + 2))
            if args.drain_at and i == args.drain_at:
                # same mid-stream discipline for the decommission: drain is
                # a learner-thread swap, coding never stalls
                futures[-1].result(timeout=600)
                drain_fut = svc.drain(drain_ranks)
            if grow_fut is not None and i == args.samples - args.micro_batch:
                # overlap growth with the stream, but make sure the final
                # micro-batch is coded by the grown network
                grow_fut.result(timeout=600)
            futures.append(svc.submit(X[i]))
            if args.rate > 0:
                time.sleep(1.0 / args.rate)
        results = [f.result(timeout=600) for f in futures]
        if grow_fut is not None:
            grow_info = grow_fut.result(timeout=600)
            print(f"growth applied: {grow_info}")
        if drain_fut is not None:
            drain_info = drain_fut.result(timeout=600)
            print(f"drain applied: {drain_info}")
    # read after stop(): it returns once the learner has fit every batch
    # it was offered, so fit_steps is the run's whole count
    stats = svc.stats()
    wall_s = time.perf_counter() - t0

    # Coding quality: for the l2-residual tasks nu* IS the fit residual
    # (paper Eq. 53), so mean ||nu|| tracks how well the stream is coded.
    pre = np.mean([np.linalg.norm(nu) for nu, _ in results[: args.micro_batch]])
    post = np.mean([np.linalg.norm(nu) for nu, _ in results[-args.micro_batch:]])
    k_dims = sorted({r[1].shape[0] for r in results})
    assert len(results) == args.samples, "dropped samples!"

    lat = stats.get("latency_ms", {})
    print("compile s: " + "  ".join(
        f"{k} {v:.2f}" for k, v in stats["compile_s"].items()))
    print(f"coded {stats['coded']}/{args.samples} samples in "
          f"{stats['batches']} micro-batches, {wall_s:.2f}s "
          f"({stats['coded'] / wall_s:.1f} samples/s)")
    print(f"latency ms: p50 {lat.get('p50', float('nan')):.1f}  "
          f"p95 {lat.get('p95', float('nan')):.1f}  "
          f"p99 {lat.get('p99', float('nan')):.1f}")
    print(f"fit_steps {stats['fit_steps']}  fit_failures {stats['fit_failures']}  "
          f"published {stats['published']}  "
          f"grow_events {len(stats['grow_events'])}  "
          f"drain_events {len(stats['drain_events'])}  y dims seen {k_dims}")
    print(f"mean ||nu||: first batch {pre:.4f} -> last batch {post:.4f}")

    if args.json:
        payload = {
            "samples": args.samples,
            "replicas": 1,
            "topology": stats["topology"],
            "mixing_rate": stats["mixing_rate"],
            "schedule": stats.get("schedule"),
            "schedule_period": stats.get("schedule_period", 1),
            "active_schedule": stats.get("active_schedule", 0),
            "pod_topology": stats.get("pod_topology"),
            "pod_gossip_every": stats.get("pod_gossip_every", 1),
            "levels": stats.get("levels"),
            "wall_s": wall_s,
            "samples_per_s": stats["coded"] / wall_s,
            # same fields the fleet payload carries, so one consumer
            # (benchmarks/serve_throughput, CI asserts) reads both shapes
            "agg_samples_per_s": stats["coded"] / wall_s,
            "p99_ms": lat.get("p99"),
            "latency_ms": lat,
            "fit_steps": stats["fit_steps"],
            "published": stats["published"],
            "grow_events": stats["grow_events"],
            "drain_events": stats["drain_events"],
            "y_dims": k_dims,
            "residual_first": float(pre),
            "residual_last": float(post),
        }
        print("BENCH " + json.dumps(payload))
    return {"mode": "single", "args": args, "stats": stats, "wall_s": wall_s,
            "results": results, "X": X, "W0": W0, "coder": coder,
            "res": res, "reg": reg}


def _run_fleet(args, res, reg, dist_cfg, svc_cfg, build_mesh, per_replica,
               k0, X) -> dict:
    """Fleet-mode serving loop: N replicas on disjoint device pools behind
    the freshness-aware Router, with one optional rolling publish."""
    pools = device_pools(args.replicas, per_replica)
    coders = [DistributedSparseCoder(build_mesh(p), res, reg, dist_cfg)
              for p in pools]
    W0 = coders[0].init_dictionary(jax.random.PRNGKey(args.seed), args.m, k0)
    comb = coders[0].combiner_info()
    print(f"serve_dict[fleet]: task={args.task} mode={args.mode} "
          f"replicas={args.replicas} mesh={args.mesh}/replica "
          f"M={args.m} K={W0.shape[1]} micro_batch={args.micro_batch} "
          f"samples={args.samples} publish_at={args.publish_at or 'never'} "
          f"topology={comb['topology']} mixing_rate={comb['mixing_rate']:.3f}")

    services = [DictionaryService(c, W0, svc_cfg) for c in coders]
    router_cfg = RouterConfig(
        micro_batch=args.micro_batch,
        max_wait_s=args.max_wait_ms / 1e3,
        seed=args.seed,
    )
    futures = []
    published = {}
    t0 = time.perf_counter()
    with ReplicaSet(services) as fleet:
        with Router(fleet, router_cfg) as router:
            for i in range(args.samples):
                if args.publish_at and i == args.publish_at:
                    # rolling publish truly mid-stream: let the pre-publish
                    # tail land, then fan a perturbed dictionary out replica
                    # by replica while the stream keeps flowing
                    futures[-1].result(timeout=600)
                    rng = np.random.default_rng(args.seed + 3)
                    W1 = np.asarray(W0) + 0.01 * rng.standard_normal(
                        W0.shape, dtype=np.float32)
                    if reg.nonneg:
                        W1 = np.maximum(W1, 0.0)
                    W1 /= np.maximum(
                        1.0, np.linalg.norm(W1, axis=0, keepdims=True))
                    published = fleet.publish(W1)
                futures.append(router.submit(X[i]))
                if args.rate > 0:
                    time.sleep(1.0 / args.rate)
            results = [f.result(timeout=600) for f in futures]
            rstats = router.stats()
        fstats = fleet.stats()
    wall_s = time.perf_counter() - t0

    assert len(results) == args.samples, "dropped samples!"
    lat = rstats.get("latency_ms", {})
    agg = args.samples / wall_s
    per_rep = {
        name: {
            "coded": st["coded"],
            "snapshot_version": st["snapshot_version"],
            "serving_version": st["serving_version"],
            "samples_per_s": st["samples_per_s"],
        }
        for name, st in fstats["replicas"].items()
    }
    print(f"coded {args.samples} samples in {wall_s:.2f}s "
          f"({agg:.1f} samples/s aggregate over {args.replicas} replica(s))")
    print(f"latency ms: p50 {lat.get('p50', float('nan')):.1f}  "
          f"p95 {lat.get('p95', float('nan')):.1f}  "
          f"p99 {lat.get('p99', float('nan')):.1f}")
    print("coded per replica: " + "  ".join(
        f"{name} {r['coded']}" for name, r in per_rep.items()))
    print(f"routed {rstats['routed']}  rerouted {rstats['rerouted']}  "
          f"failed {rstats['failed']}  publishes {fstats['publishes']} "
          f"{published}")

    if args.json:
        payload = {
            "samples": args.samples,
            "replicas": args.replicas,
            "topology": comb["topology"],
            "mixing_rate": comb["mixing_rate"],
            "wall_s": wall_s,
            "agg_samples_per_s": agg,
            "samples_per_s": agg,
            "p99_ms": lat.get("p99"),
            "latency_ms": lat,
            "routed": rstats["routed"],
            "rerouted": rstats["rerouted"],
            "failed": rstats["failed"],
            "publishes": fstats["publishes"],
            "publish_versions": published,
            "per_replica": per_rep,
        }
        print("BENCH " + json.dumps(payload))
    return {"mode": "fleet", "args": args, "wall_s": wall_s,
            "router": rstats, "fleet": fstats, "per_replica": per_rep}


def learner_failure(stats: dict, learn: bool) -> str:
    """Why a learning run must not count as a success ('' when it may): a
    failed fit step, or learning on and no fit step taken."""
    if stats["fit_failures"]:
        return (f"{stats['fit_failures']} fit step(s) failed; first error: "
                f"{stats['fit_first_error']}")
    if learn and stats["fit_steps"] == 0:
        return "learning was on but the learner took no fit step"
    return ""


def main(argv=None) -> None:
    out = run(argv)
    if out["mode"] == "single":
        why = learner_failure(out["stats"], not out["args"].no_learn)
        if why:
            raise SystemExit(f"serve_dict: {why}")


if __name__ == "__main__":
    main()
