"""Streaming dictionary-service smoke tests: micro-batched coding against a
double-buffered snapshot, online learning, the streaming tail (a submit
count that does not divide the micro-batch), and one mid-stream elastic
growth of the model axis — on a forced multi-device host mesh."""

import subprocess
import sys
import textwrap

import pytest

from conftest import REPO, subprocess_env


def _run(code: str, n_devices: int = 8, timeout: int = 900):
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        env=subprocess_env(n_devices), cwd=str(REPO),
        capture_output=True, text=True, timeout=timeout,
    )
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr[-4000:]}"
    return proc.stdout


@pytest.mark.slow
def test_service_streams_learns_and_grows():
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.conjugates import make_task
        from repro.core.dictionary import init_dictionary
        from repro.core.distributed import DistConfig, DistributedSparseCoder
        from repro.data.synthetic import sparse_stream
        from repro.runtime import dist
        from repro.runtime.service import DictionaryService, ServiceConfig

        res, reg = make_task("sparse_svd", gamma=0.25, delta=0.05)
        mesh = dist.make_mesh((1, 2), (dist.DATA_AXIS, dist.MODEL_AXIS))
        M, K0 = 16, 12
        W0 = init_dictionary(jax.random.PRNGKey(0), M, K0)
        # graph mode end to end: growth must RE-DERIVE the Metropolis
        # combiner for the larger model axis (2 agents -> full exchange,
        # mixing rate 0; 4 agents -> a true ring, mixing rate 1/3).
        coder = DistributedSparseCoder(
            mesh, res, reg,
            DistConfig(mode="graph", topology="ring_metropolis", iters=60))
        X = sparse_stream(70, m=M, k_true=K0, seed=3)

        svc = DictionaryService(coder, W0, ServiceConfig(micro_batch=8, mu_w=0.1))
        with svc:
            futs = [svc.submit(x) for x in X[:30]]
            # every pre-growth sample must resolve with the original K
            pre = [f.result(timeout=300) for f in futs]
            gf = svc.grow(2, jax.random.PRNGKey(4))
            info = gf.result(timeout=300)
            # 70 total: 40 post-growth = 5 micro-batches, no tail drop
            futs2 = [svc.submit(x) for x in X[30:]]
            post = [f.result(timeout=300) for f in futs2]
            stats = svc.stats()
            W_pub = svc.dictionary()

        assert info["model_old"] == 2 and info["model_new"] == 4
        assert info["k_old"] == K0 and info["k_new"] == 2 * K0
        assert len(pre) == 30 and len(post) == 40
        assert all(y.shape == (K0,) for _, y in pre)
        assert all(y.shape == (2 * K0,) for _, y in post)
        assert all(np.isfinite(nu).all() and np.isfinite(y).all()
                   for nu, y in pre + post)
        # 30 submits / micro_batch 8 -> the 6-sample tail was coded, not dropped
        assert stats["coded"] == 70 and stats["submitted"] == 70
        assert stats["fit_steps"] > 0 and stats["published"] > 0
        assert len(stats["grow_events"]) == 1
        # topology identity rides stats + the growth event, and growth
        # RE-DERIVED the combiner for the larger axis: the 2-agent
        # Metropolis ring is full exchange (mixing rate 0), the grown
        # 4-agent ring mixes at 1/3.
        assert stats["topology"] == "ring_metropolis"
        assert abs(stats["mixing_rate"] - 1.0 / 3.0) < 1e-6, stats["mixing_rate"]
        assert info["topology"] == "ring_metropolis"
        assert abs(info["mixing_rate"] - 1.0 / 3.0) < 1e-6, info["mixing_rate"]
        # published dictionary reflects the growth and stays unit-norm
        assert W_pub.shape == (M, 2 * K0)
        assert float(np.max(np.linalg.norm(W_pub, axis=0))) <= 1.0 + 1e-5
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_service_time_varying_schedule_clock_and_growth():
    """A graph_tv coder behind the service: the schedule clock advances with
    every engine execution (the stream runs ONE continuous time-varying
    network, not a restart at A_0 per micro-batch), stats carry the schedule
    spec / period / windowed mixing rate / active index, and growth
    re-derives the SEQUENCE for the larger axis."""
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.conjugates import make_task
        from repro.core.dictionary import init_dictionary
        from repro.core.distributed import DistConfig, DistributedSparseCoder
        from repro.data.synthetic import sparse_stream
        from repro.runtime import dist
        from repro.runtime.service import DictionaryService, ServiceConfig

        res, reg = make_task("sparse_svd", gamma=0.25, delta=0.05)
        mesh = dist.make_mesh((1, 2), (dist.DATA_AXIS, dist.MODEL_AXIS))
        M, K0 = 16, 12
        W0 = init_dictionary(jax.random.PRNGKey(0), M, K0)
        ITERS = 25  # odd vs period 2: the active index actually alternates
        coder = DistributedSparseCoder(
            mesh, res, reg,
            DistConfig(mode="graph_tv", iters=ITERS,
                       topology_schedule="alternating:ring_metropolis,torus",
                       topology_seed=5))
        X = sparse_stream(40, m=M, k_true=K0, seed=3)

        svc = DictionaryService(coder, W0, ServiceConfig(micro_batch=8, mu_w=0.1))
        with svc:
            pre = [f.result(timeout=300) for f in [svc.submit(x) for x in X[:24]]]
            info = svc.grow(2, jax.random.PRNGKey(4)).result(timeout=300)
            post = [f.result(timeout=300) for f in [svc.submit(x) for x in X[24:]]]
        stats = svc.stats()  # after stop(): workers joined, counters final

        assert len(pre) == 24 and len(post) == 16
        assert all(np.isfinite(nu).all() for nu, _ in pre + post)
        # schedule identity in stats: spec, period, windowed mixing rate
        assert stats["topology"] == "tv:alternating:ring_metropolis,torus"
        assert stats["schedule"] == "alternating:ring_metropolis,torus"
        assert stats["schedule_period"] == 2
        assert 0.0 < stats["mixing_rate"] < 1.0
        # the schedule clock advanced in whole solves/fits: every EXECUTED
        # engine program consumed exactly ITERS steps of the network
        # sequence (>= 5 coding micro-batches happened, plus every
        # successful fit; failed fits roll their claimed window back), and
        # the reported active index is where the clock stands now.
        assert svc._sched_t % ITERS == 0, svc._sched_t
        assert svc._sched_t >= ITERS * (5 + stats["fit_steps"]), \
            (svc._sched_t, stats["fit_steps"])
        assert stats["active_schedule"] == svc._sched_t % 2
        # growth re-derived the sequence at the larger axis
        assert info["model_new"] == 4
        assert info["schedule"] == "alternating:ring_metropolis,torus"
        assert info["schedule_period"] == 2
        assert 0.0 < info["mixing_rate"] < 1.0
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_service_hier_schedule_clock_and_growth():
    """A hier coder with pod_gossip_every=2 behind the service: the
    schedule clock threads the pod-hop PHASE across micro-batches (the
    coder is time-varying, so every execution claims its cfg.iters window),
    stats carry the hier identity (pod_topology / pod_gossip_every /
    effective mixing rate), and growth stays model-axis-only — the pod
    count is fixed, the inter-pod combiner carried verbatim."""
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.conjugates import make_task
        from repro.core.dictionary import init_dictionary
        from repro.core.distributed import DistConfig, DistributedSparseCoder
        from repro.data.synthetic import sparse_stream
        from repro.runtime import dist
        from repro.runtime.service import DictionaryService, ServiceConfig

        res, reg = make_task("sparse_svd", gamma=0.25, delta=0.05)
        mesh = dist.debug_mesh(model=2, data=1, pods=2)   # 4 agents, 2 pods
        M, K0 = 16, 16  # 4 atoms per (pod, model) agent
        W0 = init_dictionary(jax.random.PRNGKey(0), M, K0)
        ITERS = 25  # odd vs period 2: the pod-hop phase actually alternates
        coder = DistributedSparseCoder(
            mesh, res, reg,
            DistConfig(mode="hier", iters=ITERS, topology="ring_metropolis",
                       pod_topology="ring_metropolis", pod_gossip_every=2,
                       topology_seed=5))
        assert coder.is_time_varying and coder.schedule_period == 2
        X = sparse_stream(40, m=M, k_true=K0, seed=3)

        svc = DictionaryService(coder, W0, ServiceConfig(micro_batch=8, mu_w=0.1))
        with svc:
            pre = [f.result(timeout=300) for f in [svc.submit(x) for x in X[:24]]]
            info = svc.grow(1, jax.random.PRNGKey(4)).result(timeout=300)
            post = [f.result(timeout=300) for f in [svc.submit(x) for x in X[24:]]]
        stats = svc.stats()  # after stop(): workers joined, counters final

        assert len(pre) == 24 and len(post) == 16
        assert all(np.isfinite(nu).all() for nu, _ in pre + post)
        # hier identity in stats
        assert stats["topology"] == "hier:ring_metropolis+ring_metropolis"
        assert stats["pod_topology"] == "ring_metropolis"
        assert stats["pod_gossip_every"] == 2
        assert stats["schedule"] is None and stats["schedule_period"] == 2
        # the clock advanced in whole executed windows and the reported
        # phase is where it stands now
        assert svc._sched_t % ITERS == 0, svc._sched_t
        assert svc._sched_t >= ITERS * (3 + stats["fit_steps"])
        assert stats["active_schedule"] == svc._sched_t % 2
        # growth: model axis only — pod count fixed, every pod gained one
        # agent (K grows by pods * kb), combiner re-derived for 2x3
        assert info["model_old"] == 2 and info["model_new"] == 3
        assert info["k_old"] == K0 and info["k_new"] == K0 + 2 * 4
        assert info["pod_topology"] == "ring_metropolis"
        assert info["pod_gossip_every"] == 2
        assert all(y.shape == (K0,) for _, y in pre)
        assert all(y.shape == (K0 + 8,) for _, y in post)
        print("OK")
    """, n_devices=8)
    assert "OK" in out


@pytest.mark.slow
def test_snapshot_double_buffer_isolation():
    """fit_batch on the live copy must never mutate a published snapshot:
    readers coding against the snapshot see identical results before and
    after learner steps (consistency model of the service README section)."""
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.conjugates import make_task
        from repro.core.dictionary import init_dictionary
        from repro.core.distributed import DistConfig, DistributedSparseCoder
        from repro.runtime import dist

        res, reg = make_task("sparse_svd", gamma=0.25, delta=0.05)
        mesh = dist.make_mesh((1, 2), (dist.DATA_AXIS, dist.MODEL_AXIS))
        W0 = init_dictionary(jax.random.PRNGKey(0), 16, 12)
        coder = DistributedSparseCoder(
            mesh, res, reg, DistConfig(mode="exact_fista", iters=80))
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 16))

        snap = coder.snapshot(W0)
        nu_before, y_before = coder.solve(snap, x)
        live = snap
        for _ in range(3):
            live = coder.fit_batch(live, x, 0.1)   # learner advances the live copy
        nu_after, y_after = coder.solve(snap, x)   # reader still on the snapshot
        np.testing.assert_array_equal(np.asarray(nu_before), np.asarray(nu_after))
        np.testing.assert_array_equal(np.asarray(y_before), np.asarray(y_after))
        # and the live copy did actually move
        assert float(jnp.max(jnp.abs(live - snap))) > 0.0
        print("OK")
    """)
    assert "OK" in out


# ---------------------------------------------------------------------------
# full lifecycle (stream -> grow -> stream -> drain -> stream) per registry
# FAMILY — parametrized so a new mode family cannot silently skip the
# elastic-lifecycle contract
# ---------------------------------------------------------------------------

# family -> (mesh expression, DistConfig expression, grow count, drain ranks).
# Every family uses its most constrained representative: tv is
# failure-injected (drain of a degraded schedule end to end), push runs the
# row-stochastic-only directed combiner, chain is the 2-level hier coder
# (drains the innermost model level only).
_FAMILY_LIFECYCLE = {
    "exact": (
        "dist.make_mesh((1, 2), (dist.DATA_AXIS, dist.MODEL_AXIS))",
        'DistConfig(mode="exact", iters=60)', 2, [1, 2]),
    "ring": (
        "dist.make_mesh((1, 2), (dist.DATA_AXIS, dist.MODEL_AXIS))",
        'DistConfig(mode="ring", iters=120)', 2, [1, 2]),
    "graph": (
        "dist.make_mesh((1, 2), (dist.DATA_AXIS, dist.MODEL_AXIS))",
        'DistConfig(mode="graph", topology="ring_metropolis", iters=120)',
        2, [1, 2]),
    "tv": (
        "dist.make_mesh((1, 2), (dist.DATA_AXIS, dist.MODEL_AXIS))",
        'DistConfig(mode="graph_tv", iters=30, topology_seed=5,\n'
        '                   topology_schedule="alternating:ring_metropolis,full",\n'
        '                   failure_p=0.25, failure_seed=11, failure_steps=6)',
        2, [1, 2]),
    "push": (
        "dist.make_mesh((1, 2), (dist.DATA_AXIS, dist.MODEL_AXIS))",
        'DistConfig(mode="push", topology="distar", iters=120)', 2, [1, 2]),
    "chain": (
        "dist.debug_mesh(model=2, data=1, pods=2)",
        'DistConfig(mode="hier", iters=25, topology="ring_metropolis",\n'
        '                   pod_topology="ring_metropolis", pod_gossip_every=2,\n'
        '                   topology_seed=5)', 1, [1]),
}


def test_lifecycle_params_cover_every_registry_family():
    """The parametrization below must stay in lockstep with MODE_REGISTRY:
    adding a mode family without a lifecycle case is an error here, not a
    silent skip."""
    from repro.core.distributed import MODE_REGISTRY

    families = {caps.family for caps in MODE_REGISTRY.values()}
    assert set(_FAMILY_LIFECYCLE) == families


@pytest.mark.slow
@pytest.mark.parametrize("family", sorted(_FAMILY_LIFECYCLE))
def test_service_lifecycle_grow_then_drain(family):
    """stream -> grow -> stream -> drain -> stream for one registry family:
    every sample resolves finite with the K of its era, the grow and drain
    events carry consistent bookkeeping, and the schedule clock of a
    time-varying coder never resets across either swap."""
    mesh_expr, cfg_expr, grow_n, drain_ranks = _FAMILY_LIFECYCLE[family]
    out = _run(f"""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.conjugates import make_task
        from repro.core.dictionary import init_dictionary
        from repro.core.distributed import DistConfig, DistributedSparseCoder
        from repro.data.synthetic import sparse_stream
        from repro.runtime import dist
        from repro.runtime.service import DictionaryService, ServiceConfig

        res, reg = make_task("sparse_svd", gamma=0.25, delta=0.05)
        mesh = {mesh_expr}
        M, K0 = 16, 16
        W0 = init_dictionary(jax.random.PRNGKey(0), M, K0)
        cfg = {cfg_expr}
        coder = DistributedSparseCoder(mesh, res, reg, cfg)
        X = sparse_stream(72, m=M, k_true=K0, seed=3)

        svc = DictionaryService(coder, W0, ServiceConfig(micro_batch=8, mu_w=0.1))
        with svc:
            pre = [f.result(timeout=300) for f in [svc.submit(x) for x in X[:24]]]
            info_g = svc.grow({grow_n}, jax.random.PRNGKey(4)).result(timeout=300)
            mid = [f.result(timeout=300)
                   for f in [svc.submit(x) for x in X[24:48]]]
            info_d = svc.drain({drain_ranks!r}).result(timeout=300)
            post = [f.result(timeout=300) for f in [svc.submit(x) for x in X[48:]]]
        stats = svc.stats()

        # every sample of every era resolved, finite, with that era's K
        assert len(pre) == len(mid) == len(post) == 24
        assert all(np.isfinite(nu).all() and np.isfinite(y).all()
                   for nu, y in pre + mid + post)
        assert all(y.shape == (K0,) for _, y in pre)
        assert all(y.shape == (info_g["k_new"],) for _, y in mid)
        assert all(y.shape == (info_d["k_new"],) for _, y in post)

        # grow/drain bookkeeping is consistent and K tracks the model axis
        assert info_g["model_new"] == info_g["model_old"] + {grow_n}
        assert info_d["model_old"] == info_g["model_new"]
        assert info_d["model_new"] == info_g["model_new"] - {len(drain_ranks)}
        assert info_d["departed"] == {sorted(drain_ranks)!r}
        assert info_d["k_new"] < info_g["k_new"]
        assert len(stats["grow_events"]) == 1
        assert len(stats["drain_events"]) == 1
        assert stats["coded"] == stats["submitted"] == 72
        assert stats["fit_failures"] == 0, stats["fit_first_error"]
        W_pub = svc.dictionary()
        assert W_pub.shape == (M, info_d["k_new"])
        assert np.isfinite(W_pub).all()

        # the schedule clock of a time-varying coder threads both swaps
        # monotonically and is never reset (static families sit at 0)
        if getattr(coder, "is_time_varying", False):
            assert info_d["sched_t"] > 0
            assert svc._sched_t >= info_d["sched_t"]
        else:
            assert info_d["sched_t"] == 0
        print("OK")
    """, n_devices=8)
    assert "OK" in out


# -- spans under a profiler (one CPU device, in this process) ----------------


SERVICE_SPANS = ("service.collect", "service.exec_wait.solve", "service.exec.solve",
                 "engine.solve", "service.resolve", "service.exec_wait.fit",
                 "service.exec.fit", "engine.fit")


def test_service_spans_land_in_the_profiler_trace(tmp_path):
    """A tiny learning service under jax.profiler.trace: every span is on a
    host line of the trace, each engine.solve lies inside a
    service.exec.solve on its line, one engine.solve per coded batch, and
    serving after the warm-up compiled nothing."""
    import glob
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.conjugates import make_task
    from repro.core.distributed import DistConfig, DistributedSparseCoder
    from repro.runtime import dist
    from repro.runtime.service import DictionaryService, ServiceConfig

    res, reg = make_task("sparse_svd", gamma=0.25, delta=0.05)
    mesh = dist.make_mesh((1, 1), (dist.DATA_AXIS, dist.MODEL_AXIS))
    M, K = 8, 16
    W0 = jax.random.normal(jax.random.PRNGKey(0), (M, K))
    W0 = W0 / jnp.linalg.norm(W0, axis=0)
    coder = DistributedSparseCoder(mesh, res, reg, DistConfig(mode="exact_fista", iters=20))
    X = np.random.default_rng(0).normal(size=(18, M)).astype(np.float32)
    svc = DictionaryService(coder, W0, ServiceConfig(micro_batch=4, max_wait_s=0.01, mu_w=0.1))
    svc.start()
    try:
        with jax.profiler.trace(str(tmp_path)):
            for f in [svc.submit(x) for x in X]:
                f.result(timeout=120)
            deadline = time.monotonic() + 120
            while svc.stats()["fit_steps"] < svc.stats()["batches"]:
                assert time.monotonic() < deadline, "the learner did not catch up"
                time.sleep(0.01)
            stats = svc.stats()
    finally:
        svc.stop()

    assert stats["coded"] == 18 and stats["batches"] >= 5
    assert stats["counters"]["compiles"] == 0
    assert stats["spans"]["engine.solve"]["count"] == stats["batches"]
    assert stats["spans"]["engine.fit"]["count"] == stats["fit_steps"]
    assert set(stats["queue_wait_ms"]) == {"p50", "p95", "p99", "max"}

    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert path, "the profiler wrote no trace"
    pd = jax.profiler.ProfileData.from_file(path[0])
    lines = []  # per host line: name -> [(start, end)]
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                ev = {}
                for e in line.events:
                    ev.setdefault(e.name, []).append((e.start_ns, e.start_ns + e.duration_ns))
                lines.append(ev)
    seen = {name for ev in lines for name in ev}
    assert set(SERVICE_SPANS) <= seen, sorted(set(SERVICE_SPANS) - seen)
    solves = 0
    for ev in lines:
        for t0, t1 in ev.get("engine.solve", []):
            solves += 1
            assert any(a <= t0 and t1 <= b for a, b in ev.get("service.exec.solve", []))
    assert solves == stats["batches"]


# -- reservoir backpressure (fast: the reservoir is pure host code) ---------


def test_learn_reservoir_kept_set_is_uniform_over_submission_index():
    """Algorithm R under a full learner stall: offer 10x cap batches with
    no takes and chi-square the kept submission indices over deciles.  The
    pre-reservoir policy (drop everything past the cap) would keep ONLY
    decile 0 (chi2 ~ 576 at these sizes); uniform sampling stays far below
    the 1% critical value for df=9.  Seeded, so the statistic is exact."""
    import numpy as np
    from repro.runtime.service import _LearnReservoir

    cap, total = 64, 640
    res = _LearnReservoir(cap, seed=0)
    for i in range(total):
        res.offer(np.full((1,), i))
    kept = [int(b[0]) for b in res._buf]
    assert len(kept) == cap
    assert res.seen == total and res.discarded == total - cap
    counts = np.bincount([k * 10 // total for k in kept], minlength=10)
    expected = cap / 10
    chi2 = float(((counts - expected) ** 2 / expected).sum())
    assert chi2 < 21.67, (chi2, counts.tolist())  # 1% critical, df=9
    # sanity: the kept set reaches deep into the stream, not just a prefix
    assert max(kept) >= total * 3 // 4


def test_learn_reservoir_is_deterministic_in_seed():
    """Same seed + same offer stream -> the same kept set (backpressure is
    replayable); a different seed diverges."""
    import numpy as np
    from repro.runtime.service import _LearnReservoir

    def kept(seed):
        r = _LearnReservoir(16, seed=seed)
        for i in range(200):
            r.offer(np.full((1,), i))
        return [int(b[0]) for b in r._buf]

    assert kept(3) == kept(3)
    assert kept(3) != kept(4)


def test_learn_reservoir_cap_zero_means_drop_nothing_block():
    """Regression: cap=0 is the strict no-drop mode — the buffer is
    unbounded, nothing is ever discarded, FIFO order is preserved, and a
    take on an empty buffer blocks (queue.Empty after the timeout), which
    is what makes the service's stop() wait for the learner."""
    import queue as _queue

    import numpy as np
    import pytest as _pytest

    from repro.runtime.service import _LearnReservoir

    r = _LearnReservoir(0, seed=0)
    for i in range(300):
        dropped = r.offer(np.full((1,), i))
        assert not dropped
    assert r.discarded == 0 and r.qsize() == 300
    assert [int(r.take(0.01)[0]) for _ in range(300)] == list(range(300))
    with _pytest.raises(_queue.Empty):
        r.take(0.01)
    with _pytest.raises(ValueError):
        _LearnReservoir(-1)


@pytest.mark.slow
def test_service_reservoir_backpressure_end_to_end():
    """A throttled learner behind a hot stream: the service counts
    discards, learn_seen covers every flushed batch, and what the learner
    fit is a sample of the WHOLE stream (stats stay consistent)."""
    out = _run("""
        import numpy as np, jax
        from repro.core.conjugates import make_task
        from repro.core.dictionary import init_dictionary
        from repro.core.distributed import DistConfig, DistributedSparseCoder
        from repro.data.synthetic import sparse_stream
        from repro.runtime import dist
        from repro.runtime.service import DictionaryService, ServiceConfig

        res, reg = make_task("sparse_svd", gamma=0.25, delta=0.05)
        mesh = dist.make_mesh((1, 2), (dist.DATA_AXIS, dist.MODEL_AXIS))
        M, K = 16, 12
        W0 = init_dictionary(jax.random.PRNGKey(0), M, K)
        coder = DistributedSparseCoder(
            mesh, res, reg, DistConfig(mode="exact", iters=30))
        X = sparse_stream(160, m=M, k_true=K, seed=3)

        # cap=2 squeezes the reservoir hard: the learner (one fit per
        # flushed batch, serialized with coding on the shared exec lock)
        # cannot keep up with 20 batches
        svc_cfg = ServiceConfig(micro_batch=8, mu_w=0.05,
                                learn_queue_cap=2, learn_seed=7)
        with DictionaryService(coder, W0, svc_cfg) as svc:
            results = [f.result(timeout=300) for f in svc.submit_many(X)]
            stats = svc.stats()

        assert len(results) == 160
        assert stats["coded"] == 160
        # every flushed batch was OFFERED to the reservoir...
        assert stats["learn_seen"] == 160 // 8
        # ...learner progress + discards account for all of them
        assert stats["fit_steps"] + stats["learn_dropped"] <= stats["learn_seen"]
        assert stats["fit_steps"] >= 1
        assert stats["fit_failures"] == 0, stats["fit_first_error"]
        print("OK dropped=", stats["learn_dropped"])
    """)
    assert "OK" in out
