#!/usr/bin/env python3
"""One benchmark run of one cell, on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (an entry of `workloads` in BENCHMARK.json) names a configuration,
`bench/configs/<config>.json`, and a traffic mix, `bench/traffic/<mix>.json`;
each metric is read by `bench/metrics/<metric>.py`.  The configuration's
`kind` (default "coding") names `bench/kinds/<kind>.py`, which brings what
is the deployment's own: W0, the requests and their samples, how a request
is submitted, and the reference and numbers that decide `correct` (see
kinds/coding.py).
Every other configuration key that names a field of the engine's
`DistConfig`, the service's `ServiceConfig` or a parameter of `make_task`
is passed to it as it stands (`max_wait_ms` as `max_wait_s`).  Nothing
here knows a cell, a configuration, a kind, a mix or a metric by name.

The run drives the program's own serving path: `DictionaryService`
(runtime/service.py) over `DistributedSparseCoder` (core/distributed.py),
fed by a client thread that submits the kind's requests as the mix says.
A request is due at one moment and holds n >= 1 samples, the rows the
engine codes; every time and count below is per sample (a request that the
kind answers with one future gives each of its samples the request's
times).

  set-up   process start to the window's open: JAX and the chip, the mesh,
           W0 made on the device from the seed in the engine's sharding,
           the service started (its solve, and with learning its fit,
           compiled and run once), the first requests drawn.
  window   opens when the first request is due; load is offered for
           --seconds.  A rate's window closes at the first completion of
           its unit (a coded micro-batch, a fit step) at or after
           --seconds; a latency is taken over every sample due in it.
  check    after the window: the device's peak memory is read, the kind
           picks the served samples it checks, the program's state is
           freed, and the kind's plain reference follows the program's
           first fit steps and compares.  The harness adds its own counts:
           an unclosed window, samples unchecked, failed, unfollowed or
           foreign fits.

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
import dataclasses  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import inspect  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from concurrent.futures import Future  # noqa: E402
from types import ModuleType  # noqa: E402
from typing import Callable, NamedTuple, Optional  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import reference  # noqa: E402
import trace as trace_mod  # noqa: E402
import traffic  # noqa: E402
import work  # noqa: E402
from probe import EngineProbe  # noqa: E402

DEFAULT_KIND = "coding"
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
    kind_of(cfg, bench)  # a kind that is missing, or does not follow the task, fails here
    return cell, cfg, traffic.load(cell["traffic"], bench)


def metrics_of(cell: dict, man: dict, traced: bool) -> list:
    """The metric entries the cell reports: its end-to-end metrics, or with
    a trace its per-layer ones; an entry without `workloads` is for all."""
    group = man["per_layer" if traced else "end_to_end"]
    return [m for m in group if cell["name"] in m.get("workloads", [cell["name"]])]


@functools.lru_cache(maxsize=None)
def _module(path: pathlib.Path, name: str) -> ModuleType:
    """The Python file at `path`, loaded once per process."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str, bench: pathlib.Path = HERE) -> Callable:
    """`read(ctx)` of bench/metrics/<name>.py."""
    return _module(bench / "metrics" / f"{name}.py", f"bench_metric_{name}").read


def kind_of(cfg: dict, bench: pathlib.Path = HERE) -> ModuleType:
    """The configuration's kind, bench/kinds/<kind>.py.  Its `FOLLOWS` maps
    "task" and each `DistConfig` field that its reference follows to the
    values it follows (None: any); a configuration that sets another task,
    another value, or any other `DistConfig` field away from its default is
    refused."""
    from repro.core.distributed import DistConfig

    name = cfg.get("kind", DEFAULT_KIND)
    kind = _module(bench / "kinds" / f"{name}.py", f"bench_kind_{name}")
    fields = {f.name: f for f in dataclasses.fields(DistConfig)}
    for key, value in {"task": cfg["task"], **options(cfg, fields)}.items():
        if key in kind.FOLLOWS:
            allowed = kind.FOLLOWS[key]
            if allowed is None or value in allowed:
                continue
            why = f"{key} in {sorted(allowed)}"
        elif key == "task":
            why = "no task"
        else:
            field = fields[key]
            default = (field.default if field.default is not dataclasses.MISSING
                       else field.default_factory())
            if value == default:
                continue
            why = f"{key} only at its default {default!r}"
        raise ValueError(f"configuration {cfg['name']}: kind {name!r} follows {why}, "
                         f"not {value!r}")
    return kind


# -- the system under test --------------------------------------------------


def options(cfg: dict, names) -> dict:
    """The configuration's values of the keys in `names`, a list as a tuple
    (JSON has none)."""
    return {k: tuple(v) if isinstance(v, list) else v for k, v in cfg.items() if k in names}


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


def build(cfg: dict, devices):
    """(mesh, coder): the engine as the configuration states it."""
    from repro.core.conjugates import make_task
    from repro.core.distributed import DistConfig, DistributedSparseCoder
    from repro.runtime import dist

    data, model = cfg["mesh"]
    mesh = dist.make_mesh((data, model), (dist.DATA_AXIS, dist.MODEL_AXIS),
                          devices=np.asarray(devices))
    task_args = set(inspect.signature(make_task).parameters) - {"name"}
    res, reg = make_task(cfg["task"], **options(cfg, task_args))
    engine = DistConfig(**options(cfg, {f.name for f in dataclasses.fields(DistConfig)}))
    return mesh, DistributedSparseCoder(mesh, res, reg, engine)


def service_config(cfg: dict, learn: bool):
    """The service's ServiceConfig: the configuration's keys that name its
    fields, `max_wait_ms` in seconds, and learning as the mix says."""
    from repro.runtime.service import ServiceConfig

    opts = options(cfg, {f.name for f in dataclasses.fields(ServiceConfig)})
    if "max_wait_ms" in cfg:
        opts["max_wait_s"] = cfg["max_wait_ms"] / 1e3
    opts["learn"] = learn
    return ServiceConfig(**opts)


# -- one run ------------------------------------------------------------------


class Served(NamedTuple):
    """What the service answered, as a kind's `pick` sees it: its request's
    index, its place in that request, the sample, the dictionary version it
    was coded against, and its future.  A request that the kind answers with
    one future is one entry: place 0, `x` the request as sent, and the
    version of the last micro-batch that held one of its samples."""
    request: int
    pos: int
    x: np.ndarray
    version: int
    future: object


def _own_samples(cfg: dict, request: np.ndarray) -> np.ndarray:
    """A request that is its own samples: an (n, M) array."""
    return request


class Client(threading.Thread):
    """Submits the kind's requests as the mix says, and records per future
    it gets back its request, the first of that request's samples it
    answers and how many, its due, submit and done times (seconds from the
    window's open), and the micro-batch that resolved it.  A request that
    is answered sample by sample has one record per sample; one that the
    kind's `submit` answers with one future has one record for all its
    samples, which `per_sample` repeats for each of them at the close.

    A request's samples, the rows the engine codes (the kind's `samples`,
    by default the request itself), are taken where the request is drawn,
    ahead of its due time; past its submission the client keeps the
    request alone.  The backlog's cap counts samples.

    Each callback is attached while the probe's gate is held, so no solve
    that holds a sample starts before its callback is in place: the
    callback runs in the batcher as the future resolves, and reads the done
    time and the solve it came from then.  A kind without `submit` is
    submitted sample by sample, the gate held for one sample at a time, and
    a solve that waits goes before the next sample; a kind's own `submit`
    holds it for the whole request, and so the client first waits, outside
    the gate, until the service's queue has room for the whole request."""

    def __init__(self, svc, probe, kind, requests, mix, cfg, seed, seconds):
        super().__init__(name="bench-client", daemon=True)
        self.svc, self.probe, self.kind, self.requests = svc, probe, kind, requests
        self.samples = functools.partial(getattr(kind, "samples", _own_samples), cfg)
        self.stop = threading.Event()
        self.lock = threading.Lock()
        self.sent = []  # per request, the request as drawn
        # per record: its request, first sample, sample count, whether it
        # answers its whole request, times, micro-batch and future
        self.req, self.pos, self.n, self.whole = [], [], [], []
        self.due, self.sub, self.done, self.batch, self.futs = [], [], [], [], []
        self.n_submitted = 0  # requests whose every future is attached
        self.t_open = None
        if mix["arrivals"] == "poisson":
            self.schedule = traffic.due_times(mix, seed, seconds)
            self.cap = None
            prefill = 8
        else:
            self.schedule = None
            self.cap_samples = prefill = int(mix["outstanding_batches"]) * cfg["micro_batch"]
            self.cap = threading.Semaphore(self.cap_samples)
        # (request, samples) drawn ahead of the window: `prefill` samples or more
        self.ready = []
        while sum(len(rows) for _, rows in self.ready) < prefill:
            self.ready.append(self._draw())

    def _draw(self):
        request = next(self.requests)
        return request, self.samples(request)

    def _next_request(self):
        request, rows = self.ready.pop(0) if self.ready else self._draw()
        if self.cap is not None and len(rows) > self.cap_samples:
            raise ValueError(f"a request of {len(rows)} samples exceeds the backlog's "
                             f"cap of {self.cap_samples}")
        return request, rows

    def _on_done(self, i, n):
        def cb(fut):
            t = time.perf_counter() - self.t_open
            failed = fut.cancelled() or fut.exception() is not None
            bid = None if failed else self.probe.solves_done - 1
            with self.lock:
                self.done[i] = t
                self.batch[i] = bid
            if self.cap is not None:
                self.cap.release(n)
        return cb

    def _attach(self, r, pos, n, whole, due, fut, t_sub):
        with self.lock:
            i = len(self.futs)
            for column, value in ((self.req, r), (self.pos, pos), (self.n, n),
                                  (self.whole, whole), (self.due, due), (self.sub, t_sub),
                                  (self.done, None), (self.batch, None), (self.futs, fut)):
                column.append(value)
        fut.add_done_callback(self._on_done(i, n))

    def _room(self, n) -> bool:
        """Wait, outside the probe's gate, until the service's queue has room
        for n more samples; False if the client is stopped meanwhile.  A
        request that filled the queue while its submission held the gate
        would keep the batcher, which waits at the gate with the batch it
        took, from ever making more room."""
        cap = self.svc.cfg.queue_capacity
        if cap <= 0:
            return True
        if n > cap:
            raise ValueError(f"a request of {n} samples exceeds the service's queue of {cap}")
        while self.svc.load()["queue_depth"] > cap - n:
            if self.stop.wait(0.001):
                return False
        return True

    def _submit(self, request, rows, due):
        with self.lock:
            r = len(self.sent)
            self.sent.append(request)
        submit = getattr(self.kind, "submit", None)
        if submit is None:
            for j, x in enumerate(rows):
                with self.probe.gate.held():
                    fut = self.svc.submit(x)
                    self._attach(r, j, 1, False, due, fut, time.perf_counter() - self.t_open)
        else:
            if not self._room(len(rows)):
                return
            with self.probe.gate.held():
                got = submit(self.svc, request)
                t_sub = time.perf_counter() - self.t_open
                if isinstance(got, Future):
                    self._attach(r, 0, len(rows), True, due, got, t_sub)
                elif len(got) != len(rows):
                    raise RuntimeError(f"the kind gave {len(got)} futures for {len(rows)} "
                                       f"samples")
                else:
                    for j, fut in enumerate(got):
                        self._attach(r, j, 1, False, due, fut, t_sub)
        with self.lock:
            self.n_submitted += 1

    def run(self):
        try:
            if self.schedule is not None:
                for due in self.schedule:
                    request, rows = self._next_request()
                    wait = self.t_open + due - time.perf_counter()
                    if wait > 0 and self.stop.wait(wait):
                        return
                    if self.stop.is_set():
                        return
                    self._submit(request, rows, float(due))
            else:
                while not self.stop.is_set():
                    request, rows = self._next_request()
                    for _ in range(len(rows)):
                        while not self.cap.acquire(timeout=0.05):
                            if self.stop.is_set():
                                return
                    self._submit(request, rows, 0.0)
        except RuntimeError:
            if not self.stop.is_set():  # the service stopped under the client
                raise

    def per_sample(self):
        """(request, due, submitted, done, batch), each a list with one entry
        per sample submitted: a record's values repeated for every sample it
        answers, in the order of the requests' samples."""
        with self.lock:
            cols = [list(c) for c in (self.req, self.due, self.sub, self.done, self.batch)]
            n = list(self.n)
        return tuple([v for v, k in zip(c, n) for _ in range(k)] for c in cols)

    def rows(self, r: int) -> np.ndarray:
        """The samples of request r, taken again from the request."""
        return self.samples(self.sent[r])


def peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    return max((p for p in peaks if p is not None), default=0)


def run_cell(cell: dict, cfg: dict, mix: dict, seed: int, seconds: float, traced: bool,
             entries: list, devices, *, wrap: Optional[Callable] = None,
             trace_dir: Optional[pathlib.Path] = None, t_process: float = T_PROCESS,
             bench: pathlib.Path = HERE, ctx_out: Optional[dict] = None) -> dict:
    """One run; returns the result object (without printing it).  `wrap`
    replaces the engine under the service (tests plant faults with it);
    `ctx_out`, when given, receives what the metric readers read, and
    `samples` (per sample its row, request, due time, solve index and done
    time) and `solves` (per solve its times and input rows, read to the
    host); only then does the probe keep each solve's input."""
    from repro.runtime.service import DictionaryService

    kind = kind_of(cfg, bench)
    learn = bool(mix["learn"])
    mesh, coder = build(cfg, devices)
    if wrap is not None:
        coder = wrap(coder)
    W0_fn = kind.w0(cfg, mesh, seed)
    rng = np.random.default_rng(traffic.seed_words(seed, 5))
    cols, digest = digest_atoms(cfg, seed)
    probe = EngineProbe(coder, digest, FOLLOW_FITS if learn else 0,
                        keep_solve_inputs=ctx_out is not None)
    W0 = W0_fn()
    if learn:
        jax.block_until_ready(digest(W0))  # compiled here, not in the window
    svc = DictionaryService(probe, W0, service_config(cfg, learn))
    del W0
    svc.start()
    client = Client(svc, probe, kind, kind.requests(cfg, mix, seed), mix, cfg, seed, seconds)
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
                all_done = (client.n_submitted == len(client.schedule)
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
    req, due, sub, done, bid = client.per_sample()
    solves = [dict(s, t0=s["t0"] - t_open, t1=s["t1"] - t_open) for s in probe.solves]
    fits = [dict(f, t0=f["t0"] - t_open, t1=f["t1"] - t_open, x=np.asarray(f["x"]))
            for f in probe.fits]
    for f in fits:
        f["rows"] = len(reference.real_rows(f["x"]))
    # a sample that failed took no micro-batch
    per_batch = {}
    for i, b in enumerate(bid):
        if b is not None:
            t, n = per_batch.get(b, (0.0, 0))
            per_batch[b] = (max(t, done[i]), n + 1)
    # Samples offered in the window; a backlog's samples still queued at
    # the close were offered past it and are not counted.
    in_window = [i for i in range(len(due)) if due[i] < seconds and sub[i] <= t_close]
    if mix["arrivals"] == "backlog":
        in_window = [i for i in in_window if done[i] is not None and done[i] <= t_close]
    failed = sum(1 for i in in_window if bid[i] is None)
    ctx = dict(
        seconds=seconds, cfg=cfg, mix=mix, cell=cell, chips=len(devices),
        kind=devices[0].device_kind, setup_s=setup_s, t_close=t_close, stats=stats,
        batches=sorted(per_batch.values()), fits=[(f["t1"], f["rows"]) for f in fits],
        due=[due[i] for i in in_window],
        submitted=[sub[i] for i in in_window],
        done=[done[i] if bid[i] is not None else None for i in in_window],
    )
    rows = functools.lru_cache(maxsize=1)(client.rows)  # a request's records are adjacent
    if ctx_out is not None:
        x = [row for r, p, n in zip(client.req, client.pos, client.n) for row in rows(r)[p:p + n]]
        ctx_out.update(ctx, samples=dict(x=x, request=req, due=due, batch=bid, done=done),
                       solves=[dict(s, x=np.asarray(s["x"])) for s in solves])

    # -- the check -------------------------------------------------------------
    n_follow = min(FOLLOW_FITS, len(fits)) if learn else 0
    served = []
    for i, (r, b, d, fut) in enumerate(zip(client.req, client.batch, client.done, client.futs)):
        version = solves[b]["version"] if b is not None and b < len(solves) else -1
        if d is not None and 0 <= version <= n_follow:
            x = client.sent[r] if client.whole[i] else rows(r)[client.pos[i]]
            served.append(Served(r, client.pos[i], x, version, fut))
    picked, unchecked = kind.pick(cfg, served, rng)
    fit_inputs = [f["x"] for f in fits[:n_follow]]
    foreign = reference.foreign_rows(
        fit_inputs, {hash(x.tobytes()) for r in range(len(client.sent)) for x in rows(r)})
    digests = [None] + probe.digests[:n_follow]
    fit_failures = stats["fit_failures"]
    del svc, probe, coder, client, solves, fits, served, rows
    gc.collect()
    numbers = kind.check(cfg, seed, W0_fn, picked, fit_inputs, cols, digests)
    checks = reference.judge(numbers, cfg["limits"])
    # each count below must be 0: a window that never closed, samples the
    # check could not draw, fit steps that failed, fit steps the reference
    # had none of to follow while learning was on, and rows of the followed
    # fits that are not distinct samples the client sent
    checks["unclosed_window"] = {"value": int(not closed), "limit": 0}
    checks["samples_unchecked"] = {"value": int(unchecked), "limit": 0}
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
