"""The reduction from a profiler trace to the per-layer metrics.

`test_reductions_by_hand` checks each reduction on a trace small enough to
count by hand.  `test_recorded_trace` reads a trace recorded on the chip
(tests/record_fixture.py): two solves and one fit of the engine, with the
harness's spans.  `test_service_spans_only_name_the_idle_gaps` reads one
recorded through the service, whose own spans name the idle gaps and move
no reading."""

import pathlib

import pytest

import run
import trace

FIXTURES = pathlib.Path(__file__).resolve().parent / "fixtures"
SERVICE_TRACE = FIXTURES / "trace_1chip_service.xplane.pb"
MS = 1_000_000
# the per-layer metrics read from a trace
TRACE_READERS = ("engine.solve_ms.backlog", "engine.solve_ms.rate", "engine.fit_ms",
                 "engine.solve_roofline", "collectives.exposed_ms", "device.idle.backlog",
                 "device.idle.rate")


def _hand_trace():
    # two devices; times in ms.  Device 0: a solve from 0 to 10 with ops
    # [0,4) [4,6) all-reduce [6,10), all inside a loop [0,10); a fit from
    # 12 to 20 with [12,20).  Device 1: the same solve, its all-reduce
    # overlapping a fusion.
    d0 = {"modules": [("jit__solve_body(1)", 0, 10 * MS), ("jit__fit_body(2)", 12 * MS, 20 * MS)],
          "ops": [("while.5", 0, 10 * MS),
                  ("fusion.1", 0, 4 * MS), ("all-reduce.3", 4 * MS, 6 * MS),
                  ("fusion.2", 6 * MS, 10 * MS), ("fusion.7", 12 * MS, 20 * MS)]}
    d1 = {"modules": [("jit__solve_body(1)", 0, 10 * MS)],
          "ops": [("fusion.1", 0, 5 * MS), ("all-reduce.3", 4 * MS, 6 * MS),
                  ("fusion.2", 6 * MS, 10 * MS)]}
    host = [("bench.window", 0, 25 * MS), ("bench.solve", 0, 10 * MS),
            ("bench.fit", 11 * MS, 21 * MS)]
    return {"devices": {0: d0, 1: d1}, "host": host, "planes": []}


def test_reductions_by_hand():
    tr = _hand_trace()
    win = trace.window(tr)
    assert win == (0, 25 * MS)
    assert trace.busy(tr, win) == {0: 18 * MS, 1: 10 * MS}
    ctx = {"trace": tr, "trace_window": win}
    assert trace.idle_share(ctx) == pytest.approx(100 * (1 - 14 / 25))
    assert trace.mean_module_ms(ctx, trace.SOLVE) == pytest.approx(10.0)
    assert trace.mean_module_ms(ctx, trace.FIT) == pytest.approx(8.0)
    # device 0's all-reduce ran alone for 2 ms; device 1's overlapped 1 ms
    assert trace.exposed_collectives(tr, win, trace.SOLVE) == {0: (2 * MS, 1), 1: (1 * MS, 1)}
    ops = dict(trace.top_ops(tr, win))
    assert ops["fusion.7"] == pytest.approx(8 / 2 / 1e3)
    assert ops["fusion.1"] == pytest.approx((4 + 5) / 2 / 1e3)
    gaps = trace.idle_gaps(tr, win)
    assert gaps[0][1] == pytest.approx(5e-3)  # 20..25 ms, no span in flight
    assert gaps[0][0].startswith("service host work")
    assert gaps[1][0].startswith("bench.fit")  # 10..12 ms, in the fit call


def test_interval_arithmetic():
    assert trace.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert trace.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5), (7, 10)]
    assert trace.measure([(0, 2), (1, 3)]) == 3


@pytest.mark.skipif(not list(FIXTURES.glob("trace_*chip.xplane.pb")), reason="no recorded trace")
def test_recorded_trace():
    for pb in sorted(FIXTURES.glob("trace_*chip.xplane.pb")):
        tr = trace.load(str(pb.parent), pattern=pb.name)
        win = trace.window(tr)
        assert win is not None
        ctx = {"trace": tr, "trace_window": win}
        assert len(trace.module_times(tr, trace.SOLVE, win)) == 2 * len(tr["devices"])
        assert len(trace.module_times(tr, trace.FIT, win)) == len(tr["devices"])
        assert 0 < trace.idle_share(ctx) < 100
        assert sorted({n for n, _, _ in tr["host"]}) == ["bench.fit", "bench.solve", "bench.window"]
        if len(tr["devices"]) > 1:
            assert any(trace.COLLECTIVE.search(n) for v in tr["devices"].values()
                       for n, _, _ in v["ops"])


def test_service_spans_only_name_the_idle_gaps():
    """The service's and engine's spans kept beside the harness's: every
    per-layer reading, the busy time and the top ops are what they were
    when only `bench.*` spans were kept, and the gaps are the same gaps;
    only their names change."""
    new = trace.load(str(FIXTURES), pattern=SERVICE_TRACE.name)
    old = dict(new, host=[h for h in new["host"] if h[0].startswith("bench.")])
    assert {"service.exec.solve", "engine.solve", "engine.fit"} <= {n for n, _, _ in new["host"]}
    win = trace.window(new)
    assert win is not None and win == trace.window(old)
    # the recorder's sizes: M=512, 1024 atoms per chip, 20 iterations, 64-sample batches
    cfg = {"m": 512, "atoms": 1024 * len(new["devices"]), "mesh": [1, len(new["devices"])],
           "iters": 20, "micro_batch": 64, "dtype": "float32"}

    def ctx(tr):
        return {"trace": tr, "trace_window": win, "cfg": cfg, "kind": "TPU v5 lite"}

    readings = {name: run.reader(name)(ctx(new)) for name in TRACE_READERS}
    assert readings == {name: run.reader(name)(ctx(old)) for name in TRACE_READERS}
    assert readings["engine.solve_ms.backlog"] > 0 and readings["engine.fit_ms"] > 0
    assert trace.busy(new, win) == trace.busy(old, win)
    assert trace.top_ops(new, win) == trace.top_ops(old, win)
    gaps, gaps_before = trace.idle_gaps(new, win), trace.idle_gaps(old, win)
    assert [g[1] for g in gaps] == [g[1] for g in gaps_before]
    assert any(("service." in g[0] or "engine." in g[0]) and g[0] != b[0]
               for g, b in zip(gaps, gaps_before))
