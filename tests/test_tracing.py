"""The service's span and counter recorder (runtime/tracing.py), and the
names a profile of the engine's programs carries: the jitted module names
by which a trace's device events are found, and the named scopes of the
solve's and the fit's phases."""

import threading
import time

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

from repro.runtime import tracing


def test_span_aggregates_count_total_self_and_max():
    rec = tracing.Recorder()
    for secs in (0.002, 0.006, 0.004):
        with rec.span("work", rows=3):
            time.sleep(secs)
    agg = rec.snapshot()["spans"]["work"]
    assert agg["count"] == 3
    assert agg["total_ms"] >= 12.0
    assert 6.0 <= agg["max_ms"] <= agg["total_ms"]
    assert agg["self_ms"] == pytest.approx(agg["total_ms"])  # no children


def test_counters_start_at_zero_and_add():
    rec = tracing.Recorder(counters=("compiles",))
    assert rec.snapshot()["counters"] == {"compiles": 0}
    rec.count("compiles")
    rec.count("rows", 5)
    rec.count("rows")
    assert rec.snapshot()["counters"] == {"compiles": 1, "rows": 6}


def test_self_time_of_nested_spans_is_per_thread():
    """Thread a nests a child in its span; thread b, at the same time, does
    not.  a's self time loses the child's time, b's loses nothing."""
    rec = tracing.Recorder()
    both_open = threading.Barrier(2, timeout=10)

    def a():
        with rec.span("a.outer"):
            both_open.wait()
            time.sleep(0.005)
            with rec.span("a.inner"):
                time.sleep(0.02)

    def b():
        with rec.span("b.outer"):
            both_open.wait()
            time.sleep(0.03)

    threads = [threading.Thread(target=f) for f in (a, b)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
        assert not t.is_alive()
    spans = rec.snapshot()["spans"]
    outer, inner, other = spans["a.outer"], spans["a.inner"], spans["b.outer"]
    assert inner["total_ms"] >= 20.0
    assert outer["self_ms"] == pytest.approx(outer["total_ms"] - inner["total_ms"])
    assert 5.0 <= outer["self_ms"] < outer["total_ms"] - 19.0
    assert other["self_ms"] == pytest.approx(other["total_ms"])
    assert other["total_ms"] >= 30.0


def test_a_span_closed_early_is_no_parent():
    """A wait span closed where a lock is taken: the span opened after it
    is not its child, and leaving its block records nothing more."""
    rec = tracing.Recorder()
    lock = threading.Lock()
    with rec.span("wait") as waiting, lock:
        time.sleep(0.002)
        waiting.close()
        with rec.span("held"):
            time.sleep(0.004)
    spans = rec.snapshot()["spans"]
    assert spans["wait"]["count"] == 1 and spans["held"]["count"] == 1
    assert spans["wait"]["self_ms"] == pytest.approx(spans["wait"]["total_ms"])
    assert 2.0 <= spans["wait"]["total_ms"] < 4.0 + spans["held"]["total_ms"]


def test_spans_need_no_profiler():
    rec = tracing.Recorder()
    for i in range(1000):
        with rec.span("cheap", i=i):
            pass
    assert rec.snapshot()["spans"]["cheap"]["count"] == 1000


def test_compiles_counted_on_the_counting_thread_only():
    """A jit compiled on the worker thread inside its block is counted; one
    compiled on the main thread meanwhile is not."""
    rec = tracing.Recorder(counters=("compiles",))
    opened, main_done = threading.Event(), threading.Event()

    def worker():
        with rec.counting_compiles():
            opened.set()
            assert main_done.wait(timeout=30)
            jax.block_until_ready(jax.jit(lambda v: v * 3.0 + 1.0)(np.ones(5, np.float32)))

    t = threading.Thread(target=worker)
    t.start()
    assert opened.wait(timeout=30)
    jax.block_until_ready(jax.jit(lambda v: v - 7.0)(np.ones(6, np.float32)))
    main_done.set()
    t.join(timeout=60)
    assert not t.is_alive()
    assert rec.snapshot()["counters"]["compiles"] == 1
    # outside any block nothing is counted
    jax.block_until_ready(jax.jit(lambda v: v / 5.0)(np.ones(7, np.float32)))
    assert rec.snapshot()["counters"]["compiles"] == 1


def test_compile_seconds_sums_the_blocks_compiles():
    out = {}
    with tracing.compile_seconds(out, "fresh"):
        jax.block_until_ready(jax.jit(lambda v: jnp.sin(v) * 2.0)(np.ones(9, np.float32)))
    f = jax.jit(lambda v: jnp.cos(v))
    jax.block_until_ready(f(np.ones(9, np.float32)))
    with tracing.compile_seconds(out, "cached"):
        jax.block_until_ready(f(np.ones(9, np.float32)))
    assert out["fresh"] > 0.0
    assert out["cached"] == 0.0


# -- the engine's program names ------------------------------------------------


@pytest.fixture(scope="module")
def coder():
    from repro.core.conjugates import make_task
    from repro.core.distributed import DistConfig, DistributedSparseCoder
    from repro.runtime import dist

    res, reg = make_task("sparse_svd", gamma=0.25, delta=0.05)
    mesh = dist.make_mesh((1, 1), (dist.DATA_AXIS, dist.MODEL_AXIS))
    return DistributedSparseCoder(mesh, res, reg, DistConfig(mode="exact_fista", iters=5))


# W (M=8, K=16), a batch x of 4 rows, its duals nu (4, M) and y (4, K)
W, X, NU, Y = jnp.zeros((8, 16)), jnp.zeros((4, 8)), jnp.zeros((4, 8)), jnp.zeros((4, 16))


@pytest.fixture(scope="module")
def lowered(coder):
    return {"solve": coder._solve.lower(W, X, jnp.int32(0)),
            "fit": coder._fit.lower(W, NU, Y, jnp.float32(0.1))}


@pytest.mark.parametrize("program,module,scopes", [
    ("solve", "jit__solve_body", ("step_size", "dual_iterations")),
    ("fit", "jit__fit_body", ("atom_update",)),
])
def test_program_module_names_and_phase_scopes(lowered, program, module, scopes):
    """A trace finds the solve and the fit by these module names; a rename
    would leave every metric that reads their device time with nothing."""
    low = lowered[program]
    assert low.as_text().splitlines()[0].startswith(f"module @{module} ")
    meta = low.as_text(debug_info=True)
    for scope in scopes:
        assert f"/{scope}/" in meta, scope
    if program == "solve":
        assert "atom_update" not in meta
    else:
        assert "dual_iterations" not in meta and "step_size" not in meta


def _primitives(jaxpr) -> set:
    """Names of every primitive in `jaxpr` and the jaxprs nested in it."""
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        for param in eqn.params.values():
            for sub in param if isinstance(param, (tuple, list)) else (param,):
                inner = getattr(sub, "jaxpr", sub)
                if isinstance(inner, jax.extend.core.Jaxpr):
                    names |= _primitives(inner)
    return names


def test_fit_program_iterates_nothing(coder):
    """The fit program is the atom update alone: it takes the duals a solve
    returned, so it holds no loop that could solve the batch again."""
    fit = _primitives(jax.make_jaxpr(coder._fit)(W, NU, Y, jnp.float32(0.1)).jaxpr)
    solve = _primitives(jax.make_jaxpr(coder._solve)(W, X, jnp.int32(0)).jaxpr)
    assert "shard_map" in fit and "dot_general" in fit
    assert "scan" in solve  # the walk does find the solve's iterations
    assert not fit & {"scan", "while"}, sorted(fit)
