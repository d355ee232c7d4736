"""The readers of the service's own spans and counters (`service.*` and
`engine.compiles`, read from stats() at the window's close): values on a
hand-made context, nothing from a program that keeps no such record, and
every one present after a CPU run of the harness."""

import pytest

import run
from helpers import tiny_run

READERS = ("service.queue_wait_p95_ms", "service.collect_ms", "service.exec_wait_ms",
           "service.exec_host_ms.backlog", "service.exec_host_ms.rate", "engine.compiles")


def _span(count, total_ms, self_ms, max_ms):
    return {"count": count, "total_ms": total_ms, "self_ms": self_ms, "max_ms": max_ms}


def _ctx():
    spans = {
        "service.collect": _span(4, 402.0, 402.0, 101.0),
        "service.exec_wait.solve": _span(4, 7800.0, 7800.0, 3900.0),
        "service.exec.solve": _span(4, 8000.0, 100.0, 2010.0),
        "engine.solve": _span(4, 7900.0, 7900.0, 1980.0),
        "service.exec.fit": _span(3, 5850.0, 20.0, 1955.0),
        "engine.fit": _span(3, 5830.0, 5830.0, 1950.0),
    }
    stats = {"spans": spans, "counters": {"compiles": 0},
             "queue_wait_ms": {"p50": 1000.0, "p95": 1950.0, "p99": 2000.0, "max": 2050.0}}
    return {"stats": stats}


def test_readers_on_a_hand_made_context():
    ctx = _ctx()
    got = {name: run.reader(name)(ctx) for name in READERS}
    assert got["service.queue_wait_p95_ms"] == 1950.0
    assert got["service.collect_ms"] == pytest.approx(100.5)
    assert got["service.exec_wait_ms"] == pytest.approx(1950.0)
    assert got["service.exec_host_ms.backlog"] == pytest.approx(120.0 / 7)
    assert got["service.exec_host_ms.rate"] == got["service.exec_host_ms.backlog"]
    assert got["engine.compiles"] == 0.0
    # with learning off there is no fit span: every call is a solve
    del ctx["stats"]["spans"]["service.exec.fit"]
    assert run.reader("service.exec_host_ms.rate")(ctx) == pytest.approx(25.0)


def test_readers_give_nothing_without_the_programs_record():
    """A program whose stats() has no spans, counters or queue waits (the
    service before it kept them) gives no value and raises nothing."""
    ctx = {"stats": {"coded": 512, "batches": 2, "learn_seen": 2, "learn_dropped": 0}}
    assert {name: run.reader(name)(ctx) for name in READERS} == dict.fromkeys(READERS)
    ctx = {"stats": {"spans": {}, "counters": {}}}
    assert {name: run.reader(name)(ctx) for name in READERS} == dict.fromkeys(READERS)


@pytest.mark.parametrize("mix", ["learn.backlog", "code.rate"])
def test_a_cpu_run_reports_every_reader(mix):
    man = run.manifest()
    entries = [m for m in man["per_layer"] if m["name"] in READERS]
    out = tiny_run(mix, entries=entries)
    assert out["correct"]
    metrics = out["metrics"]
    assert set(metrics) == set(READERS)
    assert metrics["engine.compiles"]["value"] == 0.0
    assert metrics["engine.compiles"]["unit"] == "count"
    assert metrics["service.collect_ms"]["value"] >= 0.0
    if mix == "learn.backlog":
        assert metrics["service.exec_wait_ms"]["value"] > 0.0
