"""Reference <-> distributed engine parity.

The shard_map production engine (core/distributed.py, ring mode) and the
paper-faithful reference engine (core/inference.py::diffusion_infer under
the constant-weight ring combiner) must compute the SAME iterates: same
adaptive step size on every model rank (the pmax'd safe mu), same per-agent
(nu, y) to tight tolerance on a forced 1x4 host mesh."""

import subprocess
import sys
import textwrap

import pytest

from conftest import REPO, subprocess_env


def _run(code: str, n_devices: int = 4, timeout: int = 900):
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        env=subprocess_env(n_devices), cwd=str(REPO),
        capture_output=True, text=True, timeout=timeout,
    )
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr[-4000:]}"
    return proc.stdout


def test_kernel_interpret_auto_detects_backend():
    """Interpret mode is decided in one place, from the backend: the
    interpreter only on CPU, compiled kernels elsewhere; the engine exposes
    no option for it and the kernel op defaults to that decision."""
    import inspect

    import jax

    from repro.core.distributed import DistConfig
    from repro.kernels import interpret_mode
    from repro.kernels.dict_dual_step.ops import dict_dual_step

    assert interpret_mode() is (jax.default_backend() == "cpu")
    assert not hasattr(DistConfig(), "kernel_interpret")
    sig = inspect.signature(dict_dual_step)
    assert sig.parameters["interpret"].default is None


@pytest.mark.slow
def test_ring_parity_and_identical_mu():
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        # The engine's pmax'd power iteration and the reference's vmapped one
        # sum in different orders (different fusions), so the two f32 step
        # sizes agree to a few ulps, not bit for bit.
        MU_RTOL = 4 * np.finfo(np.float32).eps
        from repro.core.conjugates import make_task
        from repro.core.distributed import DistributedSparseCoder, DistConfig, make_debug_mesh
        from repro.core.dictionary import blocks_from_full
        from repro.core.inference import DiffusionConfig, diffusion_infer, safe_diffusion_mu
        from repro.core import topology as topo

        res, reg = make_task("sparse_svd", gamma=0.05, delta=0.1)
        N = 4
        mesh = make_debug_mesh(model=N, data=1)   # the forced 1x4 host mesh
        M, K, B = 16, 32, 4
        W = jax.random.normal(jax.random.PRNGKey(1), (M, K))
        W = W / jnp.linalg.norm(W, axis=0)
        x = jax.random.normal(jax.random.PRNGKey(2), (B, M))
        W_blocks = blocks_from_full(W, N)

        # Metropolis weights on a cycle = the constant-weight [1/3,1/3,1/3]
        # ring combiner the ppermute path realizes.
        A = topo.make_topology("ring_metropolis", N)
        np.testing.assert_allclose(A, topo.ring_weights(N, 1.0/3.0), atol=1e-12)

        coder = DistributedSparseCoder(
            mesh, res, reg, DistConfig(mode="ring", iters=300, mu=-1.0, beta=1.0/3.0))
        Ws, xs = coder.shard(W, x)

        # 1) every model rank reports the IDENTICAL adaptive mu, and it equals
        #    the reference max-over-blocks bound.
        mus = np.asarray(coder.adaptive_mu(Ws))
        assert mus.shape == (N,)
        assert float(np.ptp(mus)) == 0.0, mus
        mu_ref = float(safe_diffusion_mu(res, reg, W_blocks))
        assert abs(float(mus[0]) - mu_ref) < MU_RTOL * mu_ref, (mus[0], mu_ref)

        # 2) per-agent (nu, y) parity with the reference diffusion engine.
        nu_ref, y_ref, _ = diffusion_infer(
            res, reg, W_blocks, x, jnp.asarray(A, jnp.float32),
            jnp.ones((N,), jnp.float32), DiffusionConfig(iters=300),
            mu=jnp.asarray(mu_ref, x.dtype))
        nu_d, y_d = coder.solve_per_agent(Ws, xs)
        nu_err = float(jnp.max(jnp.abs(jnp.asarray(nu_d) - nu_ref)))
        y_err = float(jnp.max(jnp.abs(jnp.asarray(y_d) - y_ref)))
        print("nu_err", nu_err, "y_err", y_err)
        assert nu_err < 1e-4, nu_err
        assert y_err < 1e-4, y_err

        # 3) the default solve()'s concatenated y matches the reference's
        #    per-agent blocks laid side by side.
        _, y_flat = coder.solve(Ws, xs)
        y_ref_flat = jnp.moveaxis(y_ref, 0, 1).reshape(B, K)
        assert float(jnp.max(jnp.abs(jnp.asarray(y_flat) - y_ref_flat))) < 1e-4
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_graph_mode_parity_with_reference_engine():
    """mode="graph" under the erdos and ring_metropolis Metropolis combiners
    (the paper's Sec.-IV-B regime) matches diffusion_infer run with the
    IDENTICAL A to 1e-4 on the 1x4 debug mesh — the ppermute schedule
    compiled from A computes the same iterates as the dense reference
    combine."""
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        # The engine's pmax'd power iteration and the reference's vmapped one
        # sum in different orders (different fusions), so the two f32 step
        # sizes agree to a few ulps, not bit for bit.
        MU_RTOL = 4 * np.finfo(np.float32).eps
        from repro.core.conjugates import make_task
        from repro.core.distributed import DistributedSparseCoder, DistConfig, make_debug_mesh
        from repro.core.dictionary import blocks_from_full
        from repro.core.inference import DiffusionConfig, diffusion_infer, safe_diffusion_mu
        from repro.core import topology as topo

        res, reg = make_task("sparse_svd", gamma=0.05, delta=0.1)
        N = 4
        mesh = make_debug_mesh(model=N, data=1)
        M, K, B = 16, 32, 4
        W = jax.random.normal(jax.random.PRNGKey(1), (M, K))
        W = W / jnp.linalg.norm(W, axis=0)
        x = jax.random.normal(jax.random.PRNGKey(2), (B, M))
        W_blocks = blocks_from_full(W, N)
        mu_ref = float(safe_diffusion_mu(res, reg, W_blocks))

        for topology in ["erdos", "ring_metropolis"]:
            coder = DistributedSparseCoder(
                mesh, res, reg, DistConfig(mode="graph", iters=300, mu=-1.0,
                                           topology=topology, topology_seed=7))
            A = coder.combiner()
            assert topo.is_doubly_stochastic(A), topology
            Ws, xs = coder.shard(W, x)

            # graph mode uses the same pmax'd safe step as the ring family.
            mus = np.asarray(coder.adaptive_mu(Ws))
            assert float(np.ptp(mus)) == 0.0, (topology, mus)
            assert abs(float(mus[0]) - mu_ref) < MU_RTOL * mu_ref

            nu_ref, y_ref, _ = diffusion_infer(
                res, reg, W_blocks, x, jnp.asarray(A, jnp.float32),
                jnp.ones((N,), jnp.float32), DiffusionConfig(iters=300),
                mu=jnp.asarray(mu_ref, x.dtype))
            nu_d, y_d = coder.solve_per_agent(Ws, xs)
            nu_err = float(jnp.max(jnp.abs(jnp.asarray(nu_d) - nu_ref)))
            y_err = float(jnp.max(jnp.abs(jnp.asarray(y_d) - y_ref)))
            print(topology, "nu_err", nu_err, "y_err", y_err)
            assert nu_err < 1e-4, (topology, nu_err)
            assert y_err < 1e-4, (topology, y_err)
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_graph_tv_parity_with_reference_engine():
    """mode="graph_tv" under an alternating ring/torus schedule (and an
    erdos_resampled one) matches diffusion_infer run with the IDENTICAL
    time-varying callable A_t to 1e-4 on the 1x4 debug mesh: the lax.switch
    over per-step ppermute schedules computes the same iterates as the dense
    per-iteration combine.  Also asserts the schedule determinism contract
    at the engine level: two constructions (and two grown() coders) with the
    same topology_seed run the identical combiner sequence."""
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        # The engine's pmax'd power iteration and the reference's vmapped one
        # sum in different orders (different fusions), so the two f32 step
        # sizes agree to a few ulps, not bit for bit.
        MU_RTOL = 4 * np.finfo(np.float32).eps
        from repro.core.conjugates import make_task
        from repro.core.distributed import DistributedSparseCoder, DistConfig, make_debug_mesh
        from repro.core.dictionary import blocks_from_full
        from repro.core.inference import DiffusionConfig, diffusion_infer, safe_diffusion_mu
        from repro.core import topology as topo

        res, reg = make_task("sparse_svd", gamma=0.05, delta=0.1)
        N = 4
        mesh = make_debug_mesh(model=N, data=1)
        M, K, B = 16, 32, 4
        W = jax.random.normal(jax.random.PRNGKey(1), (M, K))
        W = W / jnp.linalg.norm(W, axis=0)
        x = jax.random.normal(jax.random.PRNGKey(2), (B, M))
        W_blocks = blocks_from_full(W, N)
        mu_ref = float(safe_diffusion_mu(res, reg, W_blocks))

        for spec, period in [("alternating:ring_metropolis,torus", 2),
                             ("erdos_resampled", 3)]:
            cfg = DistConfig(mode="graph_tv", iters=300, mu=-1.0,
                             topology_schedule=spec, schedule_period=period,
                             topology_seed=7)
            coder = DistributedSparseCoder(mesh, res, reg, cfg)
            sched = coder.topology_schedule
            assert sched.period == period, (spec, sched.period)
            for A_t in sched.combiners:  # every step doubly stochastic
                assert topo.is_doubly_stochastic(A_t), spec

            # determinism: a second engine with the same seed runs the
            # IDENTICAL network sequence
            coder2 = DistributedSparseCoder(mesh, res, reg, cfg)
            for a, b in zip(coder.combiner_sequence(), coder2.combiner_sequence()):
                np.testing.assert_array_equal(a, b)

            Ws, xs = coder.shard(W, x)

            # graph_tv uses the same pmax'd globally-safe step as the
            # static ring/graph families.
            mus = np.asarray(coder.adaptive_mu(Ws))
            assert float(np.ptp(mus)) == 0.0, (spec, mus)
            assert abs(float(mus[0]) - mu_ref) < MU_RTOL * mu_ref

            # parity under the IDENTICAL time-varying callable A_t.
            nu_ref, y_ref, _ = diffusion_infer(
                res, reg, W_blocks, x, sched.as_callable(),
                jnp.ones((N,), jnp.float32), DiffusionConfig(iters=300),
                mu=jnp.asarray(mu_ref, x.dtype))
            nu_d, y_d = coder.solve_per_agent(Ws, xs)
            nu_err = float(jnp.max(jnp.abs(jnp.asarray(nu_d) - nu_ref)))
            y_err = float(jnp.max(jnp.abs(jnp.asarray(y_d) - y_ref)))
            print(spec, "nu_err", nu_err, "y_err", y_err)
            assert nu_err < 1e-4, (spec, nu_err)
            assert y_err < 1e-4, (spec, y_err)

            # schedule-offset parity: solving at t0=1 equals the reference
            # running the shifted sequence A_{1}, A_{2}, ...
            fn = sched.as_callable()
            nu_ref1, _, _ = diffusion_infer(
                res, reg, W_blocks, x, (lambda t: fn(t + 1)),
                jnp.ones((N,), jnp.float32), DiffusionConfig(iters=300),
                mu=jnp.asarray(mu_ref, x.dtype))
            nu_d1, _ = coder.solve_per_agent(Ws, xs, t0=1)
            err1 = float(jnp.max(jnp.abs(jnp.asarray(nu_d1) - nu_ref1)))
            print(spec, "t0=1 err", err1)
            assert err1 < 1e-4, (spec, err1)

        # grown() determinism + neighborhood preservation at the engine
        # level: two grown coders agree, and erdos adjacencies keep the old
        # block (the grow-preserving sampler, not a wholesale resample).
        cfg = DistConfig(mode="graph_tv", iters=50, topology_schedule="erdos_resampled",
                         schedule_period=2, topology_seed=9)
        base = DistributedSparseCoder(mesh, res, reg, cfg)
        Wb = jax.device_put(W, jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(None, "model")))
        g1, _ = base.grown(Wb, 2, jax.random.PRNGKey(0))
        g2, _ = base.grown(Wb, 2, jax.random.PRNGKey(1))  # key only seeds new atoms
        for a, b in zip(g1.combiner_sequence(), g2.combiner_sequence()):
            np.testing.assert_array_equal(a, b)
        for old, new in zip(base.topology_schedule.adjacencies,
                            g1.topology_schedule.adjacencies):
            np.testing.assert_array_equal(new[:N, :N], old)

        # static erdos growth is grow-preserving too
        scfg = DistConfig(mode="graph", iters=50, topology="erdos", topology_seed=3)
        sbase = DistributedSparseCoder(mesh, res, reg, scfg)
        sg, _ = sbase.grown(Wb, 2, jax.random.PRNGKey(0))
        np.testing.assert_array_equal(sg._adj[:N, :N], sbase._adj)
        sg2, _ = sbase.grown(Wb, 2, jax.random.PRNGKey(5))
        np.testing.assert_array_equal(sg._adj, sg2._adj)
        # and it shares the schedule path's seed stream: a static erdos
        # coder and its "fixed:erdos" time-varying wrapper grow to the
        # IDENTICAL network (same seed, step 0, same target size).
        fs = topo.make_topology_schedule(
            "fixed:erdos", N, seed=3).grown(N + 2)
        np.testing.assert_array_equal(sg._adj, fs.adjacencies[0])
        print("OK")
    """, n_devices=8)
    assert "OK" in out


@pytest.mark.slow
def test_hier_parity_with_reference_engine():
    """mode="hier" on a (2, 1, 4) debug mesh — two pods of four agents —
    matches diffusion_infer run under the dense Kronecker combiner
    A_pod (x) A_model to 1e-4: the intra-pod + inter-pod ppermute schedules
    composed inside one shard_map compute the same iterates as the dense
    (8, 8) reference combine over the pod-major flattened agent axis.
    Covers pod_gossip_every=2 (reference = the time-varying sequence
    alternating A_pod (x) A_model with I (x) A_model) including a t0
    phase offset, the pmax-over-BOTH-axes adaptive mu, hier growth
    determinism, and hier_q8 staying in a quantization-sized neighborhood.
    """
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        # The engine's pmax'd power iteration and the reference's vmapped one
        # sum in different orders (different fusions), so the two f32 step
        # sizes agree to a few ulps, not bit for bit.
        MU_RTOL = 4 * np.finfo(np.float32).eps
        from repro.core.conjugates import make_task
        from repro.core.distributed import DistributedSparseCoder, DistConfig, make_debug_mesh
        from repro.core.dictionary import blocks_from_full
        from repro.core.inference import DiffusionConfig, diffusion_infer, safe_diffusion_mu
        from repro.core import topology as topo

        res, reg = make_task("sparse_svd", gamma=0.05, delta=0.1)
        PODS, N = 2, 4
        mesh = make_debug_mesh(model=N, data=1, pods=PODS)  # the (2,1,4) mesh
        M, K, B = 16, 32, 4
        W = jax.random.normal(jax.random.PRNGKey(1), (M, K))
        W = W / jnp.linalg.norm(W, axis=0)
        x = jax.random.normal(jax.random.PRNGKey(2), (B, M))
        # the flat reference network: PODS*N agents, pod-major atom blocks
        W_blocks = blocks_from_full(W, PODS * N)
        mu_ref = float(safe_diffusion_mu(res, reg, W_blocks))

        # -- pod hop every iteration: static Kronecker combiner ------------
        cfg = DistConfig(mode="hier", iters=300, mu=-1.0, topology="torus",
                         pod_topology="ring_metropolis", topology_seed=7)
        coder = DistributedSparseCoder(mesh, res, reg, cfg)
        ht = coder.hier_topology
        A = coder.combiner()
        assert A.shape == (PODS * N, PODS * N)
        np.testing.assert_allclose(A, np.kron(ht.A_pod, ht.A_model))
        assert topo.is_doubly_stochastic(A)

        Ws, xs = coder.shard(W, x)
        # adaptive mu pmax'd over BOTH axes: all 8 agents identical, equal
        # to the reference max-over-8-blocks bound.
        mus = np.asarray(coder.adaptive_mu(Ws))
        assert mus.shape == (PODS * N,)
        assert float(np.ptp(mus)) == 0.0, mus
        assert abs(float(mus[0]) - mu_ref) < MU_RTOL * mu_ref, (mus[0], mu_ref)

        nu_ref, y_ref, _ = diffusion_infer(
            res, reg, W_blocks, x, jnp.asarray(A, jnp.float32),
            jnp.ones((PODS * N,), jnp.float32), DiffusionConfig(iters=300),
            mu=jnp.asarray(mu_ref, x.dtype))
        nu_d, y_d = coder.solve_per_agent(Ws, xs)
        nu_err = float(jnp.max(jnp.abs(jnp.asarray(nu_d) - nu_ref)))
        y_err = float(jnp.max(jnp.abs(jnp.asarray(y_d) - y_ref)))
        print("hier nu_err", nu_err, "y_err", y_err)
        assert nu_err < 1e-4, nu_err
        assert y_err < 1e-4, y_err

        # -- pod_gossip_every=2: reference = alternating dense sequence ----
        cfg2 = DistConfig(mode="hier", iters=300, mu=-1.0, topology="torus",
                          pod_topology="ring_metropolis", topology_seed=7,
                          pod_gossip_every=2)
        coder2 = DistributedSparseCoder(mesh, res, reg, cfg2)
        seq = coder2.combiner_sequence()
        assert len(seq) == 2
        np.testing.assert_allclose(seq[0], np.kron(ht.A_pod, ht.A_model))
        np.testing.assert_allclose(seq[1], np.kron(np.eye(PODS), ht.A_model))
        fn = coder2.hier_topology.as_callable()
        nu_ref2, _, _ = diffusion_infer(
            res, reg, W_blocks, x, fn,
            jnp.ones((PODS * N,), jnp.float32), DiffusionConfig(iters=300),
            mu=jnp.asarray(mu_ref, x.dtype))
        nu_d2, _ = coder2.solve_per_agent(Ws, xs)
        err2 = float(jnp.max(jnp.abs(jnp.asarray(nu_d2) - nu_ref2)))
        print("hier k=2 nu_err", err2)
        assert err2 < 1e-4, err2

        # schedule-offset parity: t0=1 starts on a no-hop iteration
        nu_ref3, _, _ = diffusion_infer(
            res, reg, W_blocks, x, (lambda t: fn(t + 1)),
            jnp.ones((PODS * N,), jnp.float32), DiffusionConfig(iters=300),
            mu=jnp.asarray(mu_ref, x.dtype))
        nu_d3, _ = coder2.solve_per_agent(Ws, xs, t0=1)
        err3 = float(jnp.max(jnp.abs(jnp.asarray(nu_d3) - nu_ref3)))
        print("hier k=2 t0=1 nu_err", err3)
        assert err3 < 1e-4, err3

        # -- hier_q8: int8 on the pod hop only — stays in a quantization-
        #    sized neighborhood of the full-precision iterates
        cfgq = DistConfig(mode="hier_q8", iters=300, mu=-1.0, topology="torus",
                          pod_topology="ring_metropolis", topology_seed=7)
        coderq = DistributedSparseCoder(mesh, res, reg, cfgq)
        nu_q, _ = coderq.solve_per_agent(Ws, xs)
        q_dev = float(jnp.max(jnp.abs(jnp.asarray(nu_q) - nu_ref)))
        print("hier_q8 deviation", q_dev)
        assert np.isfinite(np.asarray(nu_q)).all()
        assert q_dev < 1e-2, q_dev

        # -- growth: model axis only, deterministic, shard-preserving ------
        g1, W2 = coder.grown(Ws, 1, jax.random.PRNGKey(0))
        g2, _ = coder.grown(Ws, 1, jax.random.PRNGKey(9))  # key only seeds atoms
        np.testing.assert_array_equal(g1.hier_topology.A_pod, ht.A_pod)
        for a, b in zip(g1.combiner_sequence(), g2.combiner_sequence()):
            np.testing.assert_array_equal(a, b)
        # pod-major interleave keeps every old (pod, model) shard in place
        kb = K // (PODS * N)
        W2h = np.asarray(jax.device_get(W2))
        Wh = np.asarray(W)
        np.testing.assert_array_equal(W2h[:, :N * kb], Wh[:, :N * kb])
        np.testing.assert_array_equal(
            W2h[:, (N + 1) * kb:(2 * N + 1) * kb], Wh[:, N * kb:])
        print("OK")
    """, n_devices=12)
    assert "OK" in out


# Every registry mode, pinned here so pytest can parametrize without
# importing jax at collection time; test_mu_modes_cover_registry asserts
# this tuple tracks MODE_REGISTRY.
_ALL_MODES = (
    "chain", "exact", "exact_fista", "graph", "graph_async", "graph_q8",
    "graph_tv", "graph_tv_q8", "hier", "hier_q8", "push", "push_q8",
    "ring", "ring_async", "ring_q8",
)


def test_mu_modes_cover_registry():
    from repro.core.distributed import MODES

    assert tuple(sorted(MODES)) == _ALL_MODES


@pytest.mark.slow
@pytest.mark.parametrize("mode", _ALL_MODES)
def test_adaptive_mu_identical_across_ranks(mode):
    """The mu regression, per registry mode: exact modes psum a shared
    bound, ring/graph modes pmax the per-shard bounds, hier/chain modes
    pmax over ALL agent axes of the multi-level network — every rank
    reports the identical adaptive step size.  (The static counterpart is
    tools/analyze's step-size-replication rule, which proves this on the
    jaxpr for any mesh; this test confirms it numerically on a real 4-way
    mesh for the mode under test.)"""
    flat = mode not in ("hier", "hier_q8", "chain")
    if mode in ("push", "push_q8"):
        # the directed row-stochastic-only combiner: the mu pmax must hold
        # even when the gossip itself is asymmetric ratio consensus
        setup = """
        mesh = make_debug_mesh(model=4, data=1)
        cfg = DistConfig(mode=MODE, iters=10, mu=-1.0, topology="distar")
        spec = jax.sharding.PartitionSpec(None, "model")
        """
    elif flat:
        setup = """
        mesh = make_debug_mesh(model=4, data=1)
        cfg = DistConfig(mode=MODE, iters=10, mu=-1.0)
        spec = jax.sharding.PartitionSpec(None, "model")
        """
    elif mode == "chain":
        # two-level Kronecker chain (pod x model) with a q8 outer hop:
        # the mu reduction must span both levels regardless of wire format
        setup = """
        mesh = make_debug_mesh(model=2, data=1, pods=2)
        cfg = DistConfig(mode=MODE, iters=10, mu=-1.0, topology_seed=7,
                         levels="ring_metropolis,ring_metropolis:2:q8")
        spec = jax.sharding.PartitionSpec(None, ("pod", "model"))
        """
    else:
        setup = """
        mesh = make_debug_mesh(model=2, data=1, pods=2)
        cfg = DistConfig(mode=MODE, iters=10, mu=-1.0,
                         pod_topology="ring_metropolis", pod_gossip_every=2)
        spec = jax.sharding.PartitionSpec(None, ("pod", "model"))
        """
    out = _run(f"""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.conjugates import make_task
        from repro.core.distributed import DistributedSparseCoder, DistConfig, make_debug_mesh

        MODE = {mode!r}
        res, reg = make_task("nmf", gamma=0.05, delta=0.1)
        W = jnp.abs(jax.random.normal(jax.random.PRNGKey(3), (24, 32)))
        W = W / jnp.linalg.norm(W, axis=0)
{textwrap.indent(textwrap.dedent(setup), "        ")}
        coder = DistributedSparseCoder(mesh, res, reg, cfg)
        Ws = jax.device_put(W, jax.sharding.NamedSharding(mesh, spec))
        mus = np.asarray(coder.adaptive_mu(Ws))
        print(MODE, mus)
        assert mus.shape == (4,), mus.shape
        assert float(np.ptp(mus)) == 0.0, (MODE, mus)
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_chain_3level_parity_with_reference_engine():
    """mode="chain" with the acceptance 3-level chain (chip x pod x rack,
    strides 1/2/4) on the (2, 2, 1, 2) debug mesh — eight agents, axes
    ("pod2", "pod", "data", "model") — matches diffusion_infer run under
    the dense stride-gated Kronecker-sequence callable
    (KroneckerChain.as_callable) to 1e-4.  The q8-on-both-outer-hops
    variant stays in a quantization-sized neighborhood, and the
    stale-outermost variant matches an explicit one-step-delayed dense
    reference (off-diagonal outer contributions computed from the inner
    combine of the PREVIOUS outer firing, zeros before the first) to
    1e-4."""
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        # The engine's pmax'd power iteration and the reference's vmapped one
        # sum in different orders (different fusions), so the two f32 step
        # sizes agree to a few ulps, not bit for bit.
        MU_RTOL = 4 * np.finfo(np.float32).eps
        from repro.core.conjugates import make_task
        from repro.core.distributed import DistributedSparseCoder, DistConfig, make_debug_mesh
        from repro.core.dictionary import blocks_from_full
        from repro.core.inference import (
            DiffusionConfig, agent_grad, diffusion_infer, safe_diffusion_mu)
        from repro.core import topology as topo

        res, reg = make_task("sparse_svd", gamma=0.05, delta=0.1)
        mesh = make_debug_mesh(model=2, data=1, pods=2, outer=(2,))
        NTOT = 8
        M, K, B, ITERS = 16, 32, 4, 300
        W = jax.random.normal(jax.random.PRNGKey(1), (M, K))
        W = W / jnp.linalg.norm(W, axis=0)
        x = jax.random.normal(jax.random.PRNGKey(2), (B, M))
        # flat reference network: 8 agents, outermost-major atom blocks
        W_blocks = blocks_from_full(W, NTOT)
        mu_ref = float(safe_diffusion_mu(res, reg, W_blocks))
        ones = jnp.ones((NTOT,), jnp.float32)

        # -- fp32 chain, strides 1/2/4: dense Kronecker-sequence parity ----
        cfg = DistConfig(mode="chain", iters=ITERS, mu=-1.0, topology_seed=7,
                         levels="ring_metropolis,ring_metropolis:2,full:4")
        coder = DistributedSparseCoder(mesh, res, reg, cfg)
        chain = coder.chain
        assert chain.ns == (2, 2, 2) and chain.period == 4
        assert coder.schedule_period == 4 and coder.is_time_varying
        A0 = coder.combiner_sequence()[0]
        np.testing.assert_allclose(
            A0, np.kron(chain.combiners[2],
                        np.kron(chain.combiners[1], chain.combiners[0])))
        assert topo.is_doubly_stochastic(np.asarray(A0))

        Ws, xs = coder.shard(W, x)
        # adaptive mu pmax'd over ALL THREE agent axes: identical everywhere
        mus = np.asarray(coder.adaptive_mu(Ws))
        assert mus.shape == (NTOT,)
        assert float(np.ptp(mus)) == 0.0, mus
        assert abs(float(mus[0]) - mu_ref) < MU_RTOL * mu_ref

        nu_ref, y_ref, _ = diffusion_infer(
            res, reg, W_blocks, x, chain.as_callable(), ones,
            DiffusionConfig(iters=ITERS), mu=jnp.asarray(mu_ref, x.dtype))
        nu_d, y_d = coder.solve_per_agent(Ws, xs)
        nu_err = float(jnp.max(jnp.abs(jnp.asarray(nu_d) - nu_ref)))
        y_err = float(jnp.max(jnp.abs(jnp.asarray(y_d) - y_ref)))
        print("chain fp32 nu_err", nu_err, "y_err", y_err)
        assert nu_err < 1e-4, nu_err
        assert y_err < 1e-4, y_err

        # t0 phase offset: engine at t0=1 == reference on the shifted seq
        fn = chain.as_callable()
        nu_ref1, _, _ = diffusion_infer(
            res, reg, W_blocks, x, (lambda t: fn(t + 1)), ones,
            DiffusionConfig(iters=ITERS), mu=jnp.asarray(mu_ref, x.dtype))
        nu_d1, _ = coder.solve_per_agent(Ws, xs, t0=1)
        err1 = float(jnp.max(jnp.abs(jnp.asarray(nu_d1) - nu_ref1)))
        print("chain fp32 t0=1 nu_err", err1)
        assert err1 < 1e-4, err1

        # -- q8 on both outer hops: quantization-sized neighborhood --------
        cfgq = DistConfig(mode="chain", iters=ITERS, mu=-1.0, topology_seed=7,
                          levels="ring_metropolis,ring_metropolis:2:q8,full:4:q8")
        coderq = DistributedSparseCoder(mesh, res, reg, cfgq)
        nu_q, _ = coderq.solve_per_agent(Ws, xs)
        q_dev = float(jnp.max(jnp.abs(jnp.asarray(nu_q) - nu_ref)))
        print("chain q8 deviation", q_dev)
        assert np.isfinite(np.asarray(nu_q)).all()
        assert q_dev < 1e-2, q_dev

        # -- stale outermost hop: explicit one-step-delayed reference ------
        cfgs = DistConfig(mode="chain", iters=ITERS, mu=-1.0, topology_seed=7,
                          levels="ring_metropolis,ring_metropolis:2,full:4:stale")
        coders = DistributedSparseCoder(mesh, res, reg, cfgs)
        sch = coders.chain
        f_out = sch.combiners[2]
        D = np.diag(np.diag(f_out))          # self weights: current value
        Off = f_out - D                      # neighbor weights: delayed value
        n_in = 4                             # agents under each outer group
        I_in = np.eye(n_in)
        k_out = 4                            # outer stride

        def inner_at(t):
            F0 = sch.combiners[0]
            F1 = sch.combiners[1] if t % 2 == 0 else np.eye(2)
            return np.kron(np.eye(2), np.kron(F1, F0))

        grad_all = jax.vmap(
            lambda W_k, nu_k: agent_grad(
                res, reg, W_k, nu_k, x, jnp.asarray(1.0, x.dtype),
                NTOT, jnp.asarray(float(NTOT), x.dtype)))
        mu = jnp.asarray(mu_ref, x.dtype)
        nu = jnp.zeros((NTOT,) + x.shape, x.dtype)
        u_sent = jnp.zeros_like(nu)          # zeros before the first firing
        for t in range(ITERS):
            g = grad_all(W_blocks, nu)
            psi = nu - mu * g
            u = jnp.tensordot(
                jnp.asarray(inner_at(t).T, x.dtype), psi, axes=1)
            if t % k_out == 0:
                comb = (
                    jnp.tensordot(jnp.asarray(np.kron(D, I_in).T, x.dtype),
                                  u, axes=1)
                    + jnp.tensordot(jnp.asarray(np.kron(Off, I_in).T, x.dtype),
                                    u_sent, axes=1)
                )
                u_sent = u                   # messages shipped THIS firing
            else:
                comb = u
            nu = res.project_dual(comb)
        nu_s, _ = coders.solve_per_agent(Ws, xs)
        s_err = float(jnp.max(jnp.abs(jnp.asarray(nu_s) - nu)))
        print("chain stale-outermost nu_err", s_err)
        assert s_err < 1e-4, s_err
        print("OK")
    """, n_devices=8)
    assert "OK" in out
