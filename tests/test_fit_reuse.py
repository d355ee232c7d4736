"""A fit reuses the codes its batch's solve computed (core/distributed.py).

`solve(W, x, t0)` remembers its duals; `fit_batch` on the same W and x
objects at the same t0 takes them and runs only the atom update, and
otherwise solves the batch first.  Both must give the W the old fused
program (solve and update in one program) gave.  The engine cases run in
subprocesses on 1 and 4 forced CPU devices; the service cases run here, on
this process's one CPU device, one micro-batch at a time, so no outcome
depends on thread timing."""

import json
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import REPO, subprocess_env

MODES = ("exact_fista", "exact", "ring")

_ENGINE = """
    import gc, json, weakref
    import jax, jax.numpy as jnp
    from repro.core.conjugates import make_task
    from repro.core.distributed import DistConfig, DistributedSparseCoder, _f32_matmuls
    from repro.runtime import dist
    from repro.runtime.dist import shard_map
    from jax.sharding import PartitionSpec as P

    N = {n}
    res, reg = make_task("sparse_svd", gamma=0.25, delta=0.05)
    mesh = dist.make_mesh((1, N), (dist.DATA_AXIS, dist.MODEL_AXIS))
    W0 = jax.random.normal(jax.random.PRNGKey(1), (16, 32))
    W0 = W0 / jnp.linalg.norm(W0, axis=0)
    x0 = jax.random.normal(jax.random.PRNGKey(2), (8, 16))
    MU = 0.1

    def fused(coder):
        # the old fit program: the solve and the update in one program
        body = lambda W, x, mu, t0: coder._fit_body(W, *coder._solve_body(W, x, t0), mu)
        return jax.jit(shard_map(
            _f32_matmuls(body), mesh=mesh,
            in_specs=(coder._w_spec, coder._x_spec, P(), P()),
            out_specs=coder._w_spec, check_vma=False))

    def rel(a, b):
        return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))

    def counts(coder):
        return [coder.fits_reused, coder.fits_resolved]

    out = {{}}
    for mode, cfg in [(m, DistConfig(mode=m, iters=30)) for m in {modes}] + [
            ("graph_tv", DistConfig(mode="graph_tv", iters=30,
                                    topology_schedule="alternating:ring_metropolis,full"))]:
        coder = DistributedSparseCoder(mesh, res, reg, cfg)
        W, x = coder.shard(W0, x0)
        old = fused(coder)
        ref = {{t0: old(W, x, jnp.float32(MU), jnp.int32(t0)) for t0 in (0, 1)}}
        r = out[mode] = {{}}

        coder.solve(W, x)
        hit = coder.fit_batch(W, x, MU)
        fresh = DistributedSparseCoder(mesh, res, reg, cfg)
        miss = fresh.fit_batch(W, x, MU)
        r["hit"] = dict(counts=counts(coder), vs_fresh=rel(hit, miss), vs_fused=rel(hit, ref[0]),
                        fresh_counts=counts(fresh), fresh_vs_fused=rel(miss, ref[0]))

        for case, (W_fit, x_fit, t0) in dict(
                new_x=(W, jnp.array(x, copy=True), 0),
                new_W=(coder.snapshot(W0), x, 0),
                new_t0=(W, x, 1)).items():
            before = counts(coder)
            coder.solve(W, x)
            W_new = coder.fit_batch(W_fit, x_fit, MU, t0)
            r[case] = dict(counts=[a - b for a, b in zip(counts(coder), before)],
                           vs_fused=rel(W_new, ref[t0]))

        W_tmp = coder.snapshot(W0)
        coder.solve(W_tmp, x)
        gone = weakref.ref(W_tmp)
        del W_tmp
        gc.collect()
        r["freed_W_alive"] = gone() is not None
    print("RESULT", json.dumps(out))
"""


@pytest.fixture(scope="module", params=[1, 4], ids=["1dev", "4dev"])
def engine(request):
    n = request.param
    code = textwrap.dedent(_ENGINE.format(n=n, modes=repr(MODES)))
    proc = subprocess.run([sys.executable, "-c", code], env=subprocess_env(n), cwd=str(REPO),
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = next(l for l in proc.stdout.splitlines() if l.startswith("RESULT "))
    return json.loads(line[len("RESULT "):])


@pytest.mark.parametrize("mode", MODES)
def test_fit_reuses_the_solve_and_matches_the_fused_program(engine, mode):
    """solve then fit_batch on the same W and x reuses the duals and gives
    the W a fresh coder's fit (a re-solve) and the old fused program give;
    another x object, another W or another t0 re-solves, with the same W as
    the fused program; the memo keeps no freed W alive."""
    r = engine[mode]
    assert r["hit"]["counts"] == [1, 0]
    assert r["hit"]["fresh_counts"] == [0, 1]
    assert r["hit"]["vs_fresh"] <= 1e-6
    assert r["hit"]["vs_fused"] <= 1e-6
    assert r["hit"]["fresh_vs_fused"] <= 1e-6
    for case in ("new_x", "new_W", "new_t0"):
        assert r[case]["counts"] == [0, 1], case
        assert r[case]["vs_fused"] <= 1e-6, case
    assert not r["freed_W_alive"]


def test_time_varying_fit_resolves_at_another_window(engine):
    """A time-varying coder's fit at another schedule offset than the solve
    re-solves, and at each offset gives the fused program's W."""
    r = engine["graph_tv"]
    assert r["hit"]["counts"] == [1, 0]
    assert r["hit"]["vs_fused"] <= 1e-6
    assert r["new_t0"]["counts"] == [0, 1]
    assert r["new_t0"]["vs_fused"] <= 1e-6


# -- the service, one micro-batch at a time -----------------------------------

M, K, B = 16, 32, 8


def _service(publish_every: int):
    from repro.core.conjugates import make_task
    from repro.core.distributed import DistConfig, DistributedSparseCoder
    from repro.runtime import dist
    from repro.runtime.service import DictionaryService, ServiceConfig

    res, reg = make_task("sparse_svd", gamma=0.25, delta=0.05)
    mesh = dist.make_mesh((1, 1), (dist.DATA_AXIS, dist.MODEL_AXIS))
    coder = DistributedSparseCoder(mesh, res, reg, DistConfig(mode="exact_fista", iters=30))
    W0 = jax.random.normal(jax.random.PRNGKey(3), (M, K))
    W0 = W0 / jnp.linalg.norm(W0, axis=0)
    svc = DictionaryService(coder, W0, ServiceConfig(
        micro_batch=B, max_wait_s=5.0, mu_w=0.1, publish_every=publish_every))
    return coder, W0, svc


def _feed(svc, batches):
    """Submit each batch whole, wait for its codes and then for its fit;
    returns the serving version of each batch."""
    versions = []
    for n, xb in enumerate(batches):
        for f in [svc.submit(x) for x in xb]:
            f.result(timeout=120)
        versions.append(svc.load()["serving_version"])
        deadline = time.monotonic() + 120
        while svc.stats()["fit_steps"] < n + 1:
            assert time.monotonic() < deadline, "the learner did not fit the batch"
            time.sleep(0.005)
    return versions


def _batches(n):
    return [np.asarray(jax.random.normal(jax.random.PRNGKey(10 + i), (B, M)), np.float32)
            for i in range(n)]


def test_service_fits_reuse_the_served_codes():
    """With publish_every=1 each batch is coded against the dictionary of
    every fit before it, and its fit reuses those codes."""
    _, _, svc = _service(publish_every=1)
    with svc:
        versions = _feed(svc, _batches(4))
        stats = svc.stats()
    assert versions == [0, 1, 2, 3]
    assert stats["fit_steps"] == 4 and stats["fit_failures"] == 0
    assert stats["counters"]["fits_reused"] == stats["fit_steps"]
    assert stats["counters"]["fits_resolved"] == 0


def test_service_publish_every_two_resolves_and_matches_reference():
    """With publish_every=2 a batch coded against the lagging snapshot is
    fitted against the live copy, so its fit re-solves; the dictionary
    still follows a plain loop of solve and update."""
    coder, W0, svc = _service(publish_every=2)
    batches = _batches(4)
    with svc:
        _feed(svc, batches)
        stats = svc.stats()
        W_pub = svc.dictionary()
    assert stats["fit_steps"] == 4 and stats["published"] == 2
    # fits 1 and 3 follow a publish, so snapshot and live copy agree
    assert stats["counters"]["fits_reused"] == 2
    assert stats["counters"]["fits_resolved"] == 2
    W = coder.snapshot(W0)
    for xb in batches:
        nu, y = coder.solve(W, jnp.asarray(xb))
        W = W + 0.1 * nu.T @ y / B
        W = W / jnp.maximum(jnp.linalg.norm(W, axis=0, keepdims=True), 1.0)
    np.testing.assert_allclose(W_pub, np.asarray(W), rtol=1e-5, atol=1e-6)
