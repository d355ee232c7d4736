"""Bring-up smoke test of the streaming dictionary service on a TPU.

    python chip_smoke.py             # one chip: the activation-scale deployment
    python chip_smoke.py --chips 4   # four chips: the agent mesh and the fleet

One chip.  A sparse dictionary over transformer residual activations:
M=8192 (the Llama-3-70B hidden size), K=65536 atoms (8x M), task
sparse_svd, mode exact_fista, 100 dual iterations, mesh 1x1, learning on,
micro-batches of 256, 2048 seeded samples.  It runs through
`repro.launch.serve_dict` (the service entry point) twice, with the jnp hot
loop and with the fused Pallas kernel, and checks the first micro-batch's
served (nu, y) against the plain reference `core.inference.fista_infer`
run on the same chip, snapshot and samples, plus the l2 optimality
certificate ||nu - (x - W y)|| / ||x|| of paper Eq. 53.

Four chips (`--chips 4`), and nothing else: (a) the agent network, mesh
1x4 with K=262144 atoms sharded over `model`, coding through the service
in exact_fista and ring modes, checked against the reference on one chip
(when the compiler's memory analysis says it fits there) and ring against
exact_fista; (b) the fleet, four one-chip replicas behind the Router with
one mid-stream publish, each of which must code.

Matmul precision.  The engine traces every program with f32 matmuls at
HIGHEST precision (`core.distributed.MATMUL_PRECISION`) and the reference
runs under the same setting, so both compute f32 products; every bound
below is derived from that and says why next to it.

Exits non-zero, and prints no result line, when JAX finds no TPU, when
`repro` cannot be imported, or when any check fails.  The last line of a
passing run is `{"ok": true, "device": {...}}`.
"""

from __future__ import annotations

import argparse
import json
import math
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.distributed import MATMUL_PRECISION  # noqa: E402
from repro.core.inference import fista_infer, power_sigma2  # noqa: E402
from repro.launch import serve_dict  # noqa: E402
from repro.launch.mesh import use_repo_compile_cache  # noqa: E402
from repro.runtime.serving import device_pools  # noqa: E402

EPS = float(np.finfo(np.float32).eps)

# The one-chip deployment.  gamma is 2.5 standard deviations of a random
# unit atom's correlation with a sample (||x|| ~ 2, so 2.5 * 2/sqrt(8192)),
# which leaves a few hundred atoms active per sample; delta=0.2 puts the
# dual's condition number near 75, so 100 iterations converge.
DEPLOYMENT = dict(m=8192, atoms=65536, iters=100, gamma=0.05, delta=0.2,
                  micro_batch=256, samples=2048, seed=0)

# FISTA's strongly convex rate is rho = 1 - sqrt(c_f / L) per iteration; at
# reduced width (M=512 and 2048, K/M = 8 and 32, this data) the measured
# certificate was 7-13 x rho^k, hence 30.  Its f32 floor, the error of a
# K-term reconstruction W y, measured 0.4-0.6 x eps * sqrt(K) on the CPU;
# TPU HIGHEST products are 6-pass bf16, close to but not quite f32, hence 4.
CERT_RATE_C = 30.0
CERT_FLOOR_C = 4.0
# Same iterates, same step: on the CPU the engine and the reference agree
# bit for bit.  On the chip they differ only in f32 summation order
# (~1e-6 per product at HIGHEST), and the FISTA map does not expand the
# error, so 100 iterations stay within 100 x 1e-6.
NU_SAME_ITERATES_RTOL = 1e-4


class Checks:
    """Each check prints its value beside its bound and the bound's reason."""

    def __init__(self):
        self.failed = []

    def le(self, name: str, value: float, bound: float, why: str) -> None:
        ok = bool(value <= bound)  # NaN fails
        print(f"  check {name}: {value:.6g} <= {bound:.6g}  "
              f"{'ok' if ok else 'FAIL'}  ({why})", flush=True)
        if not ok:
            self.failed.append(name)

    def ge(self, name: str, value: float, bound: float, why: str) -> None:
        ok = bool(value >= bound)
        print(f"  check {name}: {value:.6g} >= {bound:.6g}  "
              f"{'ok' if ok else 'FAIL'}  ({why})", flush=True)
        if not ok:
            self.failed.append(name)


def deployment_argv(d: dict, *, mesh: str, atoms_per_agent: int, mode: str,
                    iters: int, samples: int, platform: str,
                    learn: bool = True, extra=()) -> list:
    argv = ["--platform", platform, "--task", "sparse_svd",
            "--m", str(d["m"]), "--atoms-per-agent", str(atoms_per_agent),
            "--mesh", mesh, "--mode", mode, "--iters", str(iters),
            "--gamma", str(d["gamma"]), "--delta", str(d["delta"]),
            "--micro-batch", str(d["micro_batch"]), "--max-wait-ms", "1000",
            "--samples", str(samples), "--grow-at", "0",
            "--seed", str(d["seed"]), *extra]
    return argv + ([] if learn else ["--no-learn"])


@jax.jit
def _certificate(W, x, nu, y):
    """Per-sample l2 optimality certificate ||nu - (x - W y)|| / ||x||
    (paper Eq. 53: at the optimum nu* = x - W y*)."""
    with jax.default_matmul_precision(MATMUL_PRECISION):
        r = nu - (x - y @ W.T)
    return jnp.linalg.norm(r, axis=1) / jnp.linalg.norm(x, axis=1)


def _reference(res, reg, W, x, iters):
    """fista_infer and its primal code, with the engine's matmul precision."""
    with jax.default_matmul_precision(MATMUL_PRECISION):
        fn = jax.jit(lambda W, x: fista_infer(res, reg, W, x, iters=iters))
        nu = fn(W, x)
        y = jax.jit(lambda W, nu: reg.ystar(nu @ W))(W, nu)
        sig2 = jax.jit(power_sigma2)(W)
    return np.asarray(nu), np.asarray(y), float(sig2)


def _cert_bound(L: float, iters: int, k: int) -> float:
    rho = 1.0 - math.sqrt(1.0 / L)
    return CERT_RATE_C * rho ** iters + CERT_FLOOR_C * EPS * math.sqrt(k)


def _served(out: dict, n: int):
    nu = np.stack([r[0] for r in out["results"][:n]])
    y = np.stack([r[1] for r in out["results"][:n]])
    return out["X"][:n], nu, y


def _peak_bytes(devices) -> str:
    peaks = []
    for d in devices:
        st = d.memory_stats() or {}
        peaks.append(f"{d.id}:{st['peak_bytes_in_use']}" if "peak_bytes_in_use" in st
                     else f"{d.id}:not reported")
    return "  ".join(peaks)


def _report_run(out: dict, label: str) -> None:
    st = out["stats"]
    lat = st.get("latency_ms", {})
    print(f"[{label}] compile s: solve {st['compile_s'].get('solve', 0.0):.2f}  "
          f"fit {st['compile_s'].get('fit', 0.0):.2f}", flush=True)
    print(f"[{label}] one smoke run, not a benchmark: coded {st['coded']} samples "
          f"in {st['batches']} micro-batches, {out['wall_s']:.2f} s, "
          f"{st['coded'] / out['wall_s']:.2f} samples/s, "
          f"p50 {lat.get('p50', float('nan')):.1f} ms, "
          f"p99 {lat.get('p99', float('nan')):.1f} ms", flush=True)
    print(f"[{label}] fit_steps {st['fit_steps']}  fit_failures {st['fit_failures']}",
          flush=True)


def one_chip(checks: Checks, d: dict = DEPLOYMENT, platform: str = "tpu") -> None:
    """The deployment through serve_dict, jnp hot loop then fused kernel,
    each checked against the reference on the first micro-batch."""
    b, k, iters = d["micro_batch"], d["atoms"], d["iters"]
    ref = None
    for use_kernel in (False, True):
        label = "kernel" if use_kernel else "jnp"
        t0 = time.perf_counter()
        out = serve_dict.run(deployment_argv(
            d, mesh="1x1", atoms_per_agent=k, mode="exact_fista", iters=iters,
            samples=d["samples"], platform=platform,
            extra=["--use-kernel"] if use_kernel else []))
        print(f"[{label}] serve_dict run took {time.perf_counter() - t0:.1f} s "
              f"(M={d['m']} K={k})", flush=True)
        _report_run(out, label)
        st = out["stats"]
        full = d["samples"] // b
        checks.ge(f"{label}/batches", st["batches"], 8,
                  "at least 8 coded micro-batches")
        checks.le(f"{label}/batches_full", st["batches"], full,
                  "every micro-batch full, so the first one is samples "
                  "0..B-1, coded against the initial snapshot W0")
        checks.ge(f"{label}/coded", st["coded"], d["samples"], "no sample dropped")
        checks.ge(f"{label}/fit_steps", st["fit_steps"], 2, "the learner stepped")
        checks.le(f"{label}/fit_failures", st["fit_failures"], 0,
                  "no fit step failed")

        coder, res, reg, W0 = out["coder"], out["res"], out["reg"], out["W0"]
        x, nu, y = _served(out, b)
        if ref is None:
            nu_r, y_r, sig2 = _reference(res, reg, W0, x, iters)
            L = 1.0 + sig2 / d["delta"]  # c_f = 1 for the l2 residual
            cert_r = float(np.max(np.asarray(_certificate(W0, x, nu_r, y_r))))
            bound = _cert_bound(L, iters, k)
            print(f"[reference] fista_infer {iters} iterations: L {L:.4g}, "
                  f"active atoms per sample {float((y_r != 0).sum(1).mean()):.1f}",
                  flush=True)
            checks.le("reference/certificate", cert_r, bound,
                      f"30 rho^{iters} + 4 eps sqrt(K), rho = 1 - sqrt(1/L)")
            checks.ge("reference/active_atoms", float((y_r != 0).sum(1).mean()), 1.0,
                      "the codes are not all zero, so y is compared for real")
            ref = (nu_r, y_r, sig2, L, bound)
        nu_r, y_r, sig2, L, bound = ref
        mu = float(np.asarray(coder.adaptive_mu(W0))[0])
        checks.le(f"{label}/step_size_rel", abs(1.0 / mu - L) / L, 4 * EPS * math.sqrt(k),
                  "the engine's 1/mu is the reference's L up to f32 rounding "
                  "of a K-term power iteration")
        cert = float(np.max(np.asarray(_certificate(W0, x, nu, y))))
        checks.le(f"{label}/certificate", cert, bound,
                  f"30 rho^{iters} + 4 eps sqrt(K), rho = 1 - sqrt(1/L)")
        dnu = float(np.linalg.norm(nu - nu_r))
        checks.le(f"{label}/nu_vs_reference_rel", dnu / float(np.linalg.norm(nu_r)),
                  NU_SAME_ITERATES_RTOL,
                  "same iterates and step; f32 summation order only")
        dy = float(np.linalg.norm(y - y_r))
        y_bound = math.sqrt(sig2) / d["delta"] * (
            dnu + CERT_FLOOR_C * EPS * math.sqrt(d["m"]) * float(np.linalg.norm(nu_r)))
        checks.le(f"{label}/y_vs_reference_abs", dy, y_bound,
                  "y = soft(W^T nu)/delta is sigma_max/delta-Lipschitz in nu, "
                  "plus the f32 error of the M-term product W^T nu")
        print(f"[{label}] device peak bytes in use: {_peak_bytes(jax.devices()[:1])}",
              flush=True)
        del out, coder, W0


def agent_mesh(checks: Checks, d: dict = DEPLOYMENT, platform: str = "tpu",
               n: int = 4, exact_iters: int = 300, ring_iters: int = 1500) -> None:
    """Mesh 1xN, K = N x 65536 atoms sharded over `model`: exact_fista and
    ring coding through the service, the reference on one chip where it
    fits, and ring against exact_fista."""
    b, k = d["micro_batch"], n * d["atoms"]
    runs = {}
    for mode, iters, samples in (("exact_fista", exact_iters, 4 * b),
                                 ("ring", ring_iters, b)):
        t0 = time.perf_counter()
        out = serve_dict.run(deployment_argv(
            d, mesh=f"1x{n}", atoms_per_agent=d["atoms"], mode=mode, iters=iters,
            samples=samples, platform=platform, learn=False))
        print(f"[mesh 1x{n} {mode}] serve_dict run took "
              f"{time.perf_counter() - t0:.1f} s (M={d['m']} K={k}, {iters} iterations)",
              flush=True)
        _report_run(out, f"mesh {mode}")
        checks.ge(f"mesh/{mode}/coded", out["stats"]["coded"], samples, "no sample dropped")
        runs[mode] = out
    ex, rg = runs["exact_fista"], runs["ring"]
    res, reg, W0 = ex["res"], ex["reg"], ex["W0"]
    x, nu_e, y_e = _served(ex, b)
    mu_e = float(np.asarray(ex["coder"].adaptive_mu(W0))[0])
    L_e = 1.0 / mu_e
    cert_e = np.asarray(_certificate(W0, x, nu_e, y_e))
    checks.le("mesh/exact_fista/certificate", float(cert_e.max()),
              _cert_bound(L_e, exact_iters, k),
              f"30 rho^{exact_iters} + 4 eps sqrt(K), rho from the engine's L "
              f"(sum of shard sigma^2 bound)")

    # Ring diffusion contracts its slowest (curvature c_f = 1) direction by
    # 1 - mu/N per iteration, mu the pmax'd safe step: 20 log10(e) mu/N dB
    # per iteration.  10 dB below that covers the start-up transient and the
    # O(mu) fixed-point bias (at reduced width: +3 dB over the rate at 1000
    # iterations, no visible bias up to 68 dB).
    mu_r = float(np.asarray(rg["coder"].adaptive_mu(W0))[0])
    predicted = 20.0 * math.log10(math.e) * ring_iters * mu_r / n
    _, nu_g, _ = _served(rg, b)
    snr = 10.0 * math.log10(float(np.sum(nu_e ** 2)) / float(np.sum((nu_e - nu_g) ** 2)))
    checks.ge("mesh/ring_vs_exact_snr_db", snr, predicted - 10.0,
              f"rate predicts {predicted:.1f} dB after {ring_iters} iterations, "
              f"less 10 dB")
    del runs["ring"], rg  # its copy of W0 would crowd the reference's chip

    # The reference on one chip, where the compiler says W and its
    # temporaries fit beside what device 0 already holds.
    dev0 = jax.devices()[0]
    W_one = jax.ShapeDtypeStruct(W0.shape, W0.dtype,
                                 sharding=jax.sharding.SingleDeviceSharding(dev0))
    x_one = jax.ShapeDtypeStruct(x.shape, x.dtype,
                                 sharding=jax.sharding.SingleDeviceSharding(dev0))
    with jax.default_matmul_precision(MATMUL_PRECISION):
        ma = jax.jit(lambda W, x: fista_infer(res, reg, W, x, iters=exact_iters)).lower(
            W_one, x_one).compile().memory_analysis()
    need = ma.argument_size_in_bytes + ma.output_size_in_bytes + ma.temp_size_in_bytes
    st = dev0.memory_stats() or {}
    free = st.get("bytes_limit", 0) - st.get("bytes_in_use", 0) if st else float("inf")
    print(f"[mesh reference] one-chip fista_infer needs {need / 1e9:.2f} GB; "
          f"device 0 has {free / 1e9:.2f} GB free", flush=True)
    if need < free:
        W_dev0 = jax.device_put(W0, dev0)
        nu_r, y_r, sig2 = _reference(res, reg, W_dev0, x, exact_iters)
        cert_r = np.asarray(_certificate(W_dev0, x, nu_r, y_r))
        del W_dev0
        checks.le("mesh/reference/certificate", float(cert_r.max()),
                  _cert_bound(1.0 + sig2 / d["delta"], exact_iters, k),
                  f"30 rho^{exact_iters} + 4 eps sqrt(K)")
        # The dual is c_f-strongly convex (c_f = 1) and the certificate is
        # its gradient, so ||nu - nu*|| <= cert * ||x|| for each of the two.
        gap = np.linalg.norm(nu_e - nu_r, axis=1) / np.linalg.norm(x, axis=1)
        slack = gap - (cert_e + cert_r + CERT_FLOOR_C * EPS * math.sqrt(k))
        checks.le("mesh/nu_vs_reference_strong_convexity", float(slack.max()), 0.0,
                  "||nu_e - nu_r|| / ||x|| <= cert_e + cert_r + 4 eps sqrt(K)")
        dnu = float(np.linalg.norm(nu_e - nu_r))
        y_bound = math.sqrt(sig2) / d["delta"] * (
            dnu + CERT_FLOOR_C * EPS * math.sqrt(d["m"]) * float(np.linalg.norm(nu_r)))
        checks.le("mesh/y_vs_reference_abs", float(np.linalg.norm(y_e - y_r)), y_bound,
                  "y is sigma_max/delta-Lipschitz in nu")
    else:
        print("[mesh reference] does not fit one chip: the certificate above "
              "is the check", flush=True)
    print(f"[mesh] device peak bytes in use: {_peak_bytes(jax.devices()[:n])}",
          flush=True)


def fleet(checks: Checks, d: dict = DEPLOYMENT, platform: str = "tpu",
          n: int = 4) -> None:
    """N one-chip replicas of the deployment behind the Router, one rolling
    publish mid-stream; every replica must code."""
    samples = d["samples"]
    pools = device_pools(n, 1)
    print("[fleet] replica devices: " + "  ".join(
        f"r{i}:{p[0].id}" for i, p in enumerate(pools)), flush=True)
    t0 = time.perf_counter()
    out = serve_dict.run(deployment_argv(
        d, mesh="1x1", atoms_per_agent=d["atoms"], mode="exact_fista",
        iters=d["iters"], samples=samples, platform=platform, learn=False,
        extra=["--replicas", str(n), "--publish-at", str(samples // 2)]))
    print(f"[fleet] serve_dict run took {time.perf_counter() - t0:.1f} s", flush=True)
    lat = out["router"].get("latency_ms", {})
    print(f"[fleet] one smoke run, not a benchmark: {samples} samples in "
          f"{out['wall_s']:.2f} s, {samples / out['wall_s']:.2f} samples/s, "
          f"p50 {lat.get('p50', float('nan')):.1f} ms, "
          f"p99 {lat.get('p99', float('nan')):.1f} ms", flush=True)
    per = out["per_replica"]
    print("[fleet] coded per replica: " + "  ".join(
        f"{name} {r['coded']}" for name, r in per.items()), flush=True)
    checks.ge("fleet/replicas", len(per), n, "one replica per chip")
    for name, r in per.items():
        checks.ge(f"fleet/{name}/coded", r["coded"], 1, "every replica coded")
    checks.ge("fleet/coded", sum(r["coded"] for r in per.values()), samples,
              "no sample dropped")
    checks.le("fleet/failed", out["router"]["failed"], 0, "no request failed")
    checks.ge("fleet/publishes", out["fleet"]["publishes"], 1, "the publish landed")
    print(f"[fleet] device peak bytes in use: {_peak_bytes(jax.devices()[:n])}",
          flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="1: the one-chip deployment; 4: the agent mesh and "
                         "the fleet across four chips, and nothing else")
    args = ap.parse_args()
    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform {dev.platform}  kind {dev.device_kind}  "
          f"count {len(devices)}", flush=True)
    if dev.platform != "tpu":
        raise SystemExit("chip_smoke: JAX found no TPU; this smoke test runs "
                         "only on the chip")
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but JAX sees "
                         f"{len(devices)} device(s)")
    use_repo_compile_cache()
    checks = Checks()
    t0 = time.perf_counter()
    if args.chips == 1:
        one_chip(checks)
    else:
        agent_mesh(checks)
        fleet(checks)
    print(f"total {time.perf_counter() - t0:.1f} s", flush=True)
    if checks.failed:
        raise SystemExit(f"chip_smoke: failed checks: {checks.failed}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}}))


if __name__ == "__main__":
    main()
