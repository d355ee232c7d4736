#!/usr/bin/env python3
"""One benchmark run of one cell, on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (an entry of `workloads` in BENCHMARK.json) names a configuration,
`bench/configs/<config>.json`, and a traffic mix, `bench/traffic/<mix>.json`;
each metric is read by `bench/metrics/<metric>.py`.  Nothing here knows a
cell, a configuration, a mix or a metric by name.

The run drives the program's own serving path: `DictionaryService`
(runtime/service.py) over `DistributedSparseCoder` (core/distributed.py),
fed by a client thread that submits samples as the mix says.

  set-up   process start to the window's open: JAX and the chip, the mesh,
           W0 drawn on the device from the seed in the engine's sharding,
           the service started (its solve, and with learning its fit,
           compiled and run once), the first samples drawn.
  window   opens when the first sample is due; load is offered for
           --seconds.  A rate's window closes at the first completion of
           its unit (a coded micro-batch, a fit step) at or after
           --seconds; a latency is taken over every sample due in it.
  check    after the window: the device's peak memory is read, the
           program's state freed, and the plain reference
           (bench/reference.py) follows the program's first fit steps
           and re-solves a seeded sample of the served samples.

Prints the device, then as the last line of standard output one JSON
object: correct, attempted, failed, metrics, device (and with --trace 1 a
breakdown), and last `checks`, each number compared beside its limit.
Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from typing import Callable, Optional  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

import reference  # noqa: E402
import trace as trace_mod  # noqa: E402
import traffic  # noqa: E402
import work  # noqa: E402
from probe import EngineProbe  # noqa: E402

CHECK_ROWS = 64  # served samples re-solved by the reference
DIGEST_ATOMS = 512  # atoms whose change over the first fits is compared
FOLLOW_FITS = 3  # fit steps the reference follows
CLOSE_TIMEOUT_S = 120.0  # past --seconds, a window that has not closed fails
# The compile cache sits at a fixed path inside the checkout, so every run
# after a checkout's first finds its programs.
CACHE_DIR = ROOT / ".jax_cache"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# -- finding things by name -------------------------------------------------


def manifest(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell_parts(name: str, man: dict, bench: pathlib.Path = HERE):
    """(cell, configuration, mix) of the cell `name`."""
    cells = {w["name"]: w for w in man["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in man["configs"]}[cell["config"]]
    cfg = json.loads((bench.parent / conf["file"]).read_text())
    return cell, cfg, traffic.load(cell["traffic"], bench)


def metrics_of(cell: dict, man: dict, traced: bool) -> list:
    """The metric entries the cell reports: its end-to-end metrics, or with
    a trace its per-layer ones; an entry without `workloads` is for all."""
    group = man["per_layer" if traced else "end_to_end"]
    return [m for m in group if cell["name"] in m.get("workloads", [cell["name"]])]


def reader(name: str, bench: pathlib.Path = HERE) -> Callable:
    """`read(ctx)` of bench/metrics/<name>.py."""
    path = bench / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# -- the system under test --------------------------------------------------


def _key(seed: int, tag: int) -> jax.Array:
    word = np.random.SeedSequence([seed % 2**63, tag]).generate_state(1)[0]
    return jax.random.PRNGKey(int(word))


def w0_maker(cfg: dict, mesh, seed: int) -> Callable:
    """W0 on the device in one jitted call from the seed: unit-norm Gaussian
    atoms, float32, in the engine's W sharding (atoms over `model`)."""
    m, k = cfg["m"], cfg["atoms"]
    sharding = NamedSharding(mesh, P(None, "model"))

    @jax.jit
    def draw(key):
        W = jax.random.normal(key, (m, k), jnp.float32)
        W = W / jnp.maximum(jnp.linalg.norm(W, axis=0, keepdims=True), 1e-12)
        return jax.lax.with_sharding_constraint(W, sharding)

    return lambda: draw(_key(seed, 4))


def digest_atoms(cfg: dict, seed: int):
    """(cols, digest): the atoms whose change the check compares, one
    contiguous block at a seeded offset in each agent's shard (static
    slices, so no agent's shard is gathered), and a jitted W -> W[:, cols]."""
    agents = cfg["mesh"][1]
    kb = cfg["atoms"] // agents
    per = min(DIGEST_ATOMS // agents, kb)
    offs = np.random.default_rng(np.random.SeedSequence([seed % 2**63, 6])).integers(
        0, kb - per + 1, agents)
    blocks = [(a * kb + int(o), a * kb + int(o) + per) for a, o in enumerate(offs)]
    cols = np.concatenate([np.arange(lo, hi) for lo, hi in blocks])
    digest = jax.jit(lambda W: jnp.concatenate([W[:, lo:hi] for lo, hi in blocks], axis=1))
    return cols, digest


def build(cfg: dict, devices, seed: int):
    from repro.core.conjugates import make_task
    from repro.core.distributed import DistConfig, DistributedSparseCoder
    from repro.runtime import dist

    data, model = cfg["mesh"]
    mesh = dist.make_mesh((data, model), (dist.DATA_AXIS, dist.MODEL_AXIS),
                          devices=np.asarray(devices))
    res, reg = make_task(cfg["task"], gamma=cfg["gamma"], delta=cfg["delta"])
    coder = DistributedSparseCoder(mesh, res, reg,
                                   DistConfig(mode=cfg["mode"], iters=cfg["iters"]))
    return mesh, coder


# -- one run ------------------------------------------------------------------


class Client(threading.Thread):
    """Submits the mix's samples; records due, submit and done times
    (seconds from the window's open), the micro-batch each sample was
    coded in, and each sample's result."""

    def __init__(self, svc, probe, stream, mix, cfg, seed, seconds, prefill):
        super().__init__(name="bench-client", daemon=True)
        self.svc, self.probe, self.stream = svc, probe, stream
        self.stop = threading.Event()
        self.lock = threading.Lock()
        self.x, self.due, self.sub, self.done, self.batch, self.futs = [], [], [], [], [], []
        self.t_open = None
        if mix["arrivals"] == "poisson":
            self.schedule = traffic.due_times(mix, seed, seconds)
            self.cap = None
        else:
            self.schedule = None
            self.cap = threading.Semaphore(int(mix["outstanding_batches"]) * cfg["micro_batch"])
        self.ready = [stream.next() for _ in range(prefill)]

    def _next_x(self):
        return self.ready.pop(0) if self.ready else self.stream.next()

    def _on_done(self, i):
        def cb(fut):
            t = time.perf_counter() - self.t_open
            bid = self.probe.solves_done - 1
            with self.lock:
                self.done[i] = t
                self.batch[i] = bid
            if self.cap is not None:
                self.cap.release()
        return cb

    def _submit(self, i, x, due):
        with self.lock:
            self.x.append(x)
            self.due.append(due)
            self.sub.append(None)
            self.done.append(None)
            self.batch.append(None)
        fut = self.svc.submit(x)
        self.sub[i] = time.perf_counter() - self.t_open
        self.futs.append(fut)
        fut.add_done_callback(self._on_done(i))

    def run(self):
        try:
            if self.schedule is not None:
                for i, due in enumerate(self.schedule):
                    x = self._next_x()
                    wait = self.t_open + due - time.perf_counter()
                    if wait > 0 and self.stop.wait(wait):
                        return
                    if self.stop.is_set():
                        return
                    self._submit(i, x, float(due))
            else:
                i = 0
                while not self.stop.is_set():
                    x = self._next_x()
                    while not self.cap.acquire(timeout=0.05):
                        if self.stop.is_set():
                            return
                    self._submit(i, x, 0.0)
                    i += 1
        except RuntimeError:
            if not self.stop.is_set():  # the service stopped under the client
                raise


def peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    return max((p for p in peaks if p is not None), default=0)


def run_cell(cell: dict, cfg: dict, mix: dict, seed: int, seconds: float, traced: bool,
             entries: list, devices, *, wrap: Optional[Callable] = None,
             trace_dir: Optional[pathlib.Path] = None, t_process: float = T_PROCESS,
             bench: pathlib.Path = HERE, ctx_out: Optional[dict] = None) -> dict:
    """One run; returns the result object (without printing it).  `wrap`
    replaces the engine under the service (tests plant faults with it);
    `ctx_out`, when given, receives what the metric readers read."""
    from repro.runtime.service import DictionaryService, ServiceConfig

    learn = bool(mix["learn"])
    mesh, coder = build(cfg, devices, seed)
    if wrap is not None:
        coder = wrap(coder)
    W0_fn = w0_maker(cfg, mesh, seed)
    rng = np.random.default_rng(np.random.SeedSequence([seed % 2**63, 5]))
    cols, digest = digest_atoms(cfg, seed)
    probe = EngineProbe(coder, digest, FOLLOW_FITS if learn else 0)
    W0 = W0_fn()
    if learn:
        jax.block_until_ready(digest(W0))  # compiled here, not in the window
    svc = DictionaryService(probe, W0, ServiceConfig(
        micro_batch=cfg["micro_batch"], max_wait_s=cfg["max_wait_ms"] / 1e3,
        learn=learn, mu_w=cfg["mu_w"]))
    del W0
    svc.start()
    stream = traffic.Stream(cfg["m"], cfg["atoms"], mix, seed)
    prefill = (int(mix["outstanding_batches"]) * cfg["micro_batch"]
               if mix["arrivals"] == "backlog" else 8)
    client = Client(svc, probe, stream, mix, cfg, seed, seconds, prefill)
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    setup_s = time.perf_counter() - t_process

    # -- the window ----------------------------------------------------------
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **_: compiles.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    probe.arm()
    win_span = jax.profiler.TraceAnnotation("bench.window")
    win_span.__enter__()
    t_open = time.perf_counter()
    client.t_open = t_open
    client.start()
    deadline = seconds + CLOSE_TIMEOUT_S
    closed = False
    while time.perf_counter() - t_open < deadline:
        time.sleep(0.02)
        now = time.perf_counter() - t_open
        if mix["arrivals"] == "poisson":
            with client.lock:
                n_due = len(client.schedule)
                all_done = (len(client.done) == n_due
                            and all(d is not None for d in client.done))
            closed = now >= seconds and all_done
        else:
            coded = any(s["t1"] - t_open >= seconds for s in list(probe.solves))
            fitted = (not learn) or any(f["t1"] - t_open >= seconds for f in list(probe.fits))
            closed = coded and fitted
        if closed:
            break
    t_close = time.perf_counter() - t_open
    win_span.__exit__(None, None, None)
    log(f"window: open {setup_s:.3f} s after start, closed after {t_close:.3f} s, "
        f"{len(compiles)} compiles inside it")
    stats = svc.stats()
    client.stop.set()
    if traced:
        jax.profiler.stop_trace()
    client.join(timeout=30)
    if mix["arrivals"] == "backlog":
        svc.kill()  # what is still queued was offered after the window closed
    else:
        svc.stop()

    # -- what the window did -------------------------------------------------
    memory_peak = peak_bytes(devices)
    with client.lock:
        due, sub, done, bid = list(client.due), list(client.sub), list(client.done), list(client.batch)
    solves = [dict(s, t0=s["t0"] - t_open, t1=s["t1"] - t_open) for s in probe.solves]
    fits = [dict(f, t0=f["t0"] - t_open, t1=f["t1"] - t_open, x=np.asarray(f["x"]))
            for f in probe.fits]
    for f in fits:
        f["rows"] = len(reference.real_rows(f["x"]))
    per_batch = {}
    for i, b in enumerate(bid):
        if b is not None and done[i] is not None and client.futs[i].exception() is None:
            t, n = per_batch.get(b, (0.0, 0))
            per_batch[b] = (max(t, done[i]), n + 1)
    # Samples offered in the window; a backlog's samples still queued at
    # the close were offered past it and are not counted.
    in_window = [i for i in range(len(due)) if due[i] < seconds and sub[i] is not None
                 and sub[i] <= t_close]
    if mix["arrivals"] == "backlog":
        in_window = [i for i in in_window if done[i] is not None and done[i] <= t_close]
    failed = sum(1 for i in in_window
                 if done[i] is None or client.futs[i].exception() is not None)
    ctx = dict(
        seconds=seconds, cfg=cfg, mix=mix, cell=cell, chips=len(devices),
        kind=devices[0].device_kind, setup_s=setup_s, t_close=t_close, stats=stats,
        batches=sorted(per_batch.values()), fits=[(f["t1"], f["rows"]) for f in fits],
        due=[due[i] for i in in_window],
        submitted=[sub[i] for i in in_window],
        done=[done[i] if client.futs[i].exception() is None else None for i in in_window],
    )
    if ctx_out is not None:
        ctx_out.update(ctx)

    # -- the check -------------------------------------------------------------
    n_follow = min(FOLLOW_FITS, len(fits)) if learn else 0
    version = [solves[b]["version"] if b is not None and b < len(solves) else -1 for b in bid]
    ok_rows = [i for i in range(len(bid)) if done[i] is not None and version[i] >= 0
               and version[i] <= n_follow and client.futs[i].exception() is None]
    pick = sorted(rng.choice(ok_rows, min(CHECK_ROWS, len(ok_rows)), replace=False)) if ok_rows else []
    sampled = {}
    for i in pick:
        nu, y = client.futs[i].result()
        s = sampled.setdefault(version[i], {"x": [], "nu": [], "y": []})
        s["x"].append(client.x[i])
        s["nu"].append(np.asarray(nu))
        s["y"].append(np.asarray(y))
    sampled = {v: {k: np.stack(a) for k, a in s.items()} for v, s in sampled.items()}
    fit_inputs = [f["x"] for f in fits[:n_follow]]
    foreign = reference.foreign_rows(fit_inputs, {hash(x.tobytes()) for x in client.x})
    digests = [None] + probe.digests[:n_follow]
    fit_failures = stats["fit_failures"]
    del svc, probe, coder, client, solves, fits
    gc.collect()
    numbers = reference.compare(cfg, W0_fn, sampled, fit_inputs, cols, digests, CHECK_ROWS)
    checks = reference.judge(numbers, cfg["limits"])
    # each count below must be 0: a window that never closed, samples the
    # check could not draw, fit steps that failed, fit steps the reference
    # had none of to follow while learning was on, and rows of the followed
    # fits that are not distinct samples the client sent
    checks["unclosed_window"] = {"value": int(not closed), "limit": 0}
    checks["samples_unchecked"] = {"value": CHECK_ROWS - len(pick), "limit": 0}
    checks["fit_failures"] = {"value": int(fit_failures), "limit": 0}
    checks["fits_unfollowed"] = {"value": int(learn and n_follow == 0), "limit": 0}
    checks["fit_rows_foreign"] = {"value": int(foreign), "limit": 0}
    correct = reference.verdict(checks)

    # -- metrics -----------------------------------------------------------------
    tr = None
    if traced:
        tr = trace_mod.load(str(trace_dir))
        ctx["trace"] = tr
        ctx["trace_window"] = trace_mod.window(tr)
    metrics = {}
    for m in entries:
        value = reader(m["name"], bench)(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = devices[0]
    result = dict(
        correct=bool(correct), attempted=len(in_window), failed=int(failed), metrics=metrics,
        device=dict(platform=dev.platform, kind=dev.device_kind, count=len(devices),
                    memory_peak_bytes=int(memory_peak)),
    )
    if traced:
        win = ctx["trace_window"]
        busy = trace_mod.busy(tr, win) if win else {}
        result["device"]["busy_s"] = (sum(busy.values()) / len(busy) / 1e9) if busy else 0.0
        result["device"]["window_s"] = ((win[1] - win[0]) / 1e9) if win else 0.0
        if win:
            result["breakdown"] = dict(device_ops=trace_mod.top_ops(tr, win),
                                       idle_gaps=trace_mod.idle_gaps(tr, win))
        for name, lines in tr["planes"]:
            log(f"trace plane {name}: {lines}")
        shutil.rmtree(trace_dir, ignore_errors=True)
    result["checks"] = checks
    return result


def use_compile_cache() -> None:
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)
    man = manifest()
    cell, cfg, mix = cell_parts(args.workload, man)
    devices = jax.devices()
    dev = devices[0]
    log(f"device: platform {dev.platform}  kind {dev.device_kind}  count {len(devices)}")
    if dev.platform != "tpu":
        raise SystemExit("bench: JAX found no TPU; the benchmark runs only on the chip")
    if len(devices) < cell["chips"]:
        raise SystemExit(f"bench: {args.workload} needs {cell['chips']} chips, "
                         f"JAX sees {len(devices)}")
    work.peaks(dev.device_kind)  # an unknown chip is an error before any work
    use_compile_cache()
    result = run_cell(cell, cfg, mix, args.seed, args.seconds, bool(args.trace),
                      metrics_of(cell, man, bool(args.trace)), devices[:cell["chips"]],
                      trace_dir=ROOT / ".bench_trace" / args.workload)
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']:.6g} limit {c['limit']:.6g}")
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
