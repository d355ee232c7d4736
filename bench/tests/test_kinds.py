"""A configuration's kind (`bench/kinds/<kind>.py`) brings what is its
deployment's own: W0, requests of one or more samples, how a request is
submitted, and the reference that decides `correct`.  Adding a kind, a
configuration, a mix and a manifest entry is enough, with no edit to the
code; the coding kind draws what the harness drew before kinds existed."""

import hashlib
import json
import pathlib
import shutil
import time

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

import run
import traffic
from conftest import BENCH
from helpers import tiny_config, tiny_run

TILES_KIND = pathlib.Path(__file__).resolve().parent / "fixtures" / "tiles_kind.py"


def _sha(a) -> str:
    return hashlib.sha256(np.ascontiguousarray(a).tobytes()).hexdigest()[:16]


def test_coding_kind_draws_the_harness_stream():
    """The digests of the samples, W0 and due times that the harness drew
    for this seed before the coding kind held them."""
    seed = 2**31 + 12345
    cfg = tiny_config()
    kind = run.kind_of(cfg)
    requests = kind.requests(cfg, traffic.load("learn.backlog"), seed)
    first = [next(requests) for _ in range(50)]
    assert {r.shape for r in first} == {(1, 64)}
    assert _sha(np.concatenate(first)) == "6406ccb5debf7cc3"
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    assert _sha(np.asarray(kind.w0(cfg, mesh, seed)())) == "2c1901f8468382d3"
    assert _sha(traffic.due_times({"rate_per_s": 100.0}, seed, 5.0)) == "5b2fe90fcfa14cdc"


@pytest.mark.parametrize("task,mode", [("nmf_huber", "exact_fista"), ("sparse_svd", "ring")])
def test_a_kind_refuses_arithmetic_its_reference_does_not_follow(task, mode):
    with pytest.raises(ValueError, match="follows"):
        run.kind_of(tiny_config(task=task, mode=mode))


@pytest.mark.parametrize("option", [{"use_kernel": True}, {"beta": 0.25}, {"mu": 0.5},
                                    {"topology": "torus"}])
def test_a_kind_refuses_engine_options_its_reference_does_not_follow(option):
    with pytest.raises(ValueError, match="follows"):
        run.kind_of(tiny_config(**option))
    run.kind_of(tiny_config(iters=37))  # the reference follows iters at any value


def test_control_names_a_kind_without_one(monkeypatch):
    import control

    monkeypatch.setattr(run, "kind_of", lambda cfg: object())
    with pytest.raises(SystemExit, match="has no control"):
        control.control_numbers(tiny_config(), {"learn": True}, 1, jax.devices()[:1])


def test_engine_and_service_options_come_from_the_configuration():
    cfg = tiny_config(use_kernel=True, beta=0.25, publish_every=2, eta=0.3, learn=False)
    _, coder = run.build(cfg, jax.devices()[:1])
    assert coder.cfg.use_kernel is True and coder.cfg.beta == 0.25
    assert (coder.cfg.mode, coder.cfg.iters) == (cfg["mode"], cfg["iters"])
    svc = run.service_config(cfg, learn=True)
    assert svc.publish_every == 2 and svc.micro_batch == cfg["micro_batch"]
    assert svc.max_wait_s == cfg["max_wait_ms"] / 1e3 and svc.mu_w == cfg["mu_w"]
    assert svc.learn is True  # the mix says whether the service learns


def _files(root: pathlib.Path) -> dict:
    return {p.relative_to(root): p.read_bytes() for p in root.rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


# Appended to the tiles kind: its `submit` answers with the patches' futures
# gathered in reverse, so each tile is rebuilt from another patch's answer.
REVERSED = """

def submit(svc, request):
    patches = _dc_removed(request, math.isqrt(svc.sample_dim))
    return _gather(svc.submit_many(patches)[::-1])
"""
# Appended last: counts the done callbacks attached to each request's future.
COUNTED = """

CALLBACKS = []  # per request submitted, the callbacks attached to its future
_uncounted_submit = submit


def submit(svc, request):
    fut = _uncounted_submit(svc, request)
    attached = []
    CALLBACKS.append(attached)
    add = fut.add_done_callback

    def counted(fn):
        attached.append(fn)
        add(fn)

    fut.add_done_callback = counted
    return fut
"""
PATCHES = (64 - 10 + 1) ** 2  # 3,025 overlapping 10x10 patches of a 64x64 image


@pytest.mark.parametrize("fault,arrivals", [(None, "poisson"), ("patches_reversed", "poisson"),
                                            (None, "backlog")])
def test_a_new_kind_runs_by_new_files_alone(tmp_path, fault, arrivals):
    """A toy denoising deployment at the paper's shapes (Sec. V): 64x64
    images, 10x10 patches (M=100) against a K=196 DCT W0, its own
    reference.  A request is the image; its 3,025 DC-removed patches are
    its samples, and the kind answers it with one future, so the client
    attaches one callback per request, and every sample of a request
    carries the request's due and done times.  The solve runs 10
    iterations, not the paper's count: the reference runs as many, so the
    comparison holds at any count, and the CPU run stays short.  With its
    answers handed to the wrong patches it must not be correct.  Under a
    backlog the cap counts samples: 24 micro-batches of 256 hold two
    requests, and the service's queue room for one and a part, so the
    client has to wait for room before it takes the probe's gate (holding
    it on a full queue, it would keep the batcher from making room)."""
    bench = tmp_path / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = _files(bench)

    source = TILES_KIND.read_text() + (REVERSED if fault == "patches_reversed" else "")
    (bench / "kinds" / "tiles.py").write_text(source + COUNTED)
    cfg = {"name": "tiles-m100-k196", "kind": "tiles", "image": 64, "patch": 10,
           "noise": 0.1, "m": 100, "atoms": 196, "mesh": [1, 1], "task": "sparse_svd",
           "gamma": 0.05, "delta": 0.2, "mode": "exact_fista", "iters": 10,
           "micro_batch": 256, "max_wait_ms": 20, "mu_w": 0.1, "queue_capacity": 4096,
           "dtype": "float32", "limits": {"tile_gap": 1e-4}}
    (bench / "configs" / "tiles-m100-k196.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "tiles.rate.json").write_text(json.dumps(
        {"arrivals": arrivals, "rate_per_s": 3.0, "outstanding_batches": 24, "learn": False}))
    man = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    man["configs"].append({"name": "tiles-m100-k196", "source": "https://arxiv.org/abs/1402.1515",
                           "file": "bench/configs/tiles-m100-k196.json", "reduced": [],
                           "why": "test"})
    man["workloads"].append({"name": "tiles.rate", "config": "tiles-m100-k196",
                             "traffic": "tiles.rate", "chips": 1, "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))

    man = run.manifest(tmp_path)
    cell, found, mix = run.cell_parts("tiles.rate", man, bench)
    entries = [{"name": n, "unit": "ms"} for n in ("latency_p95_ms", "client.late_p95_ms")]
    seed, seconds, ctx = 2**31 + 99, 2.0, {}
    out = run.run_cell(cell, found, mix, seed, seconds, False, entries, jax.devices()[:1],
                       t_process=time.perf_counter(), bench=bench, ctx_out=ctx)

    assert _files(bench).items() >= before.items()  # no file that was there is edited
    callbacks = [len(c) for c in run.kind_of(found, bench).CALLBACKS]
    samples = ctx["samples"]
    times = {}
    for r, due, done in zip(samples["request"], samples["due"], samples["done"]):
        times.setdefault(r, []).append((due, done))
    assert callbacks == [1] * len(times)  # one callback per request, not per sample
    assert all(len(t) == PATCHES and len(set(t)) == 1 for t in times.values())
    if arrivals == "poisson":  # every request due in the window, each counted whole
        offered = len(traffic.due_times(mix, seed, seconds))
    else:  # the requests answered by the window's close
        offered = sum(t[0][1] is not None and t[0][1] <= ctx["t_close"] for t in times.values())
    assert offered >= 2 and out["attempted"] == PATCHES * offered
    assert out["checks"]["samples_unchecked"]["value"] == 0
    assert out["metrics"]["latency_p95_ms"]["value"] > 0
    if fault is None:
        assert out["correct"], out["checks"]
    else:
        assert not out["correct"], out["checks"]
        assert out["checks"]["tile_gap"]["value"] > out["checks"]["tile_gap"]["limit"]


# Seven coding samples to a request; with `slow`, the kind submits them
# itself, 5 ms apart, so that solves would finish while it submits.
SEVEN = """

import time

_single = requests


def requests(cfg, mix, seed):
    singles = _single(cfg, mix, seed)
    while True:
        yield np.concatenate([next(singles) for _ in range(7)])
"""
SLOW = """

def submit(svc, request):
    futs = []
    for x in request:
        futs.append(svc.submit(x))
        time.sleep(0.005)
    return futs
"""


@pytest.mark.parametrize("slow", [False, True])
def test_each_sample_of_a_request_is_timed_by_the_solve_that_held_it(tmp_path, slow):
    """A kind whose requests are seven samples due at once, learning on:
    each sample's micro-batch id is the solve whose input held its row, and
    its done time falls between that solve's end and the next solve's
    start.  The slow case submits through the kind's own `submit` and
    flushes at 1 ms, so without the probe's gate many solves would end
    while one request is being submitted; it codes with learning off, as
    batches of a sample or two would leave the reference too few samples
    coded against the dictionaries it follows."""
    bench = tmp_path / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("__pycache__", "tests"))
    source = (BENCH / "kinds" / "coding.py").read_text() + SEVEN + (SLOW if slow else "")
    (bench / "kinds" / "coding7.py").write_text(source)
    cfg = tiny_config(kind="coding7", **({"max_wait_ms": 1} if slow else {}))
    mix = dict(traffic.load("learn.backlog", bench), learn=not slow)
    ctx = {}
    out = run.run_cell({"name": "tiny", "config": cfg["name"], "traffic": "learn.backlog",
                        "chips": 1}, cfg, mix, 2**31 + 7, 2.0, False, [], jax.devices()[:1],
                       t_process=time.perf_counter(), bench=bench, ctx_out=ctx)
    assert out["correct"], [(k, c) for k, c in out["checks"].items() if c["value"] > c["limit"]]
    solves, samples = ctx["solves"], ctx["samples"]
    timed = [i for i, b in enumerate(samples["batch"]) if b is not None]
    assert len(timed) >= 5 * 7 and len(solves) >= 5
    for i in timed:
        b, x, done = samples["batch"][i], samples["x"][i], samples["done"][i]
        assert np.any(np.all(solves[b]["x"] == x, axis=1)), (i, b)
        assert solves[b]["t1"] <= done, (i, b)
        if b + 1 < len(solves):
            assert done <= solves[b + 1]["t0"], (i, b)


@pytest.mark.parametrize("asked", [False, True])
def test_the_probe_keeps_a_solve_input_only_when_asked(monkeypatch, asked):
    """A run keeps no solve's input to the window's close, where it would
    count in the peak memory, unless the caller asks for the solves
    (`ctx_out`); every fit keeps its input, which the reference follows
    and `learned_per_s` counts the rows of."""
    from probe import EngineProbe

    probes = []

    class Kept(EngineProbe):
        def __init__(self, *args, **kw):
            super().__init__(*args, **kw)
            probes.append(self)

    monkeypatch.setattr(run, "EngineProbe", Kept)
    cfg, mix = tiny_config(), traffic.load("learn.backlog")
    out = run.run_cell({"name": "tiny", "config": cfg["name"], "traffic": "learn.backlog",
                        "chips": 1}, cfg, mix, 2**31 + 11, 2.0, False, [], jax.devices()[:1],
                       t_process=time.perf_counter(), ctx_out={} if asked else None)
    assert out["correct"], out["checks"]
    (probe,) = probes
    assert probe.solves and all(("x" in s) == asked for s in probe.solves)
    assert probe.fits and all("x" in f for f in probe.fits)


def test_a_waiting_solve_goes_before_the_clients_next_sample():
    """A client that submits without pause holds a solve back for one
    sample at most, not for its whole burst."""
    import threading

    from probe import Gate

    gate, stop, count = Gate(), threading.Event(), [0]

    def client():
        while not stop.is_set():
            with gate.held():
                count[0] += 1
                time.sleep(1e-4)

    thread = threading.Thread(target=client)
    thread.start()
    try:
        time.sleep(0.01)
        for _ in range(20):
            before = count[0]
            gate.pass_through()
            assert count[0] - before <= 1
    finally:
        stop.set()
        thread.join()
