"""Spans and counters of the dictionary service, kept in memory.

A span (`Recorder.span(name, **args)`) is a context manager around a stretch
of host work.  It always adds its duration to the recorder's aggregates
(count, total, self and max, in ms); self time is the duration less what
the spans opened inside it on the same thread covered.  It also opens a
`jax.profiler.TraceAnnotation(name, **args)`, so that under a running
profiler the span lands on the thread's host line of the same trace as
the device ops, on the profiler's clock; with no profiler running the
annotation costs next to nothing.

Counters (`Recorder.count`) are plain integers.  `Recorder.snapshot()`
returns both in one consistent dict.  The recorder takes its own lock,
never a caller's, so it adds no lock order to the code that uses it.

Compiles are seen through one process-wide `jax.monitoring` listener that
hands each event to the sinks the calling thread has opened:
`compile_seconds` sums a block's trace, lower and compile seconds, and
`Recorder.counting_compiles` counts a thread's backend compiles as the
counter `compiles`.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Callable, Dict, Iterable, Iterator, List

import jax

COMPILE_EVENTS = "/jax/core/compile/"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"

# Per thread: `spans`, the stack of open spans, and `sinks`, the compile
# event sinks opened on that thread.
_local = threading.local()
_install_lock = threading.Lock()
_installed = False


def _on_event_duration(event: str, duration: float, **_) -> None:
    for sink in getattr(_local, "sinks", ()):
        sink(event, duration)


@contextlib.contextmanager
def _listening(sink: Callable[[str, float], None]) -> Iterator[None]:
    """Hand `sink` every duration event JAX records on this thread inside
    the block (the listener is installed once per process, on first use)."""
    global _installed
    with _install_lock:
        if not _installed:
            jax.monitoring.register_event_duration_secs_listener(_on_event_duration)
            _installed = True
    sinks = _local.__dict__.setdefault("sinks", [])
    sinks.append(sink)
    try:
        yield
    finally:
        sinks.remove(sink)


@contextlib.contextmanager
def compile_seconds(out: Dict[str, float], key: str) -> Iterator[None]:
    """Sets `out[key]` to the seconds JAX spent tracing, lowering and
    compiling on the calling thread inside the block."""
    total = [0.0]

    def sink(event: str, duration: float) -> None:
        if event.startswith(COMPILE_EVENTS):
            total[0] += duration

    try:
        with _listening(sink):
            yield
    finally:
        out[key] = total[0]


class Span:
    """One timed stretch of host work; see `Recorder.span`.  `close()` ends
    it early (for a wait that ends when a lock is taken); leaving the
    `with` block after that does nothing more."""

    __slots__ = ("_rec", "_name", "_annotation", "_t0", "_child_s", "_open")

    def __init__(self, rec: "Recorder", name: str, args: Dict):
        self._rec = rec
        self._name = name
        self._annotation = jax.profiler.TraceAnnotation(name, **args)
        self._child_s = 0.0
        self._open = False

    def __enter__(self) -> "Span":
        self._annotation.__enter__()
        _local.__dict__.setdefault("spans", []).append(self)
        self._open = True
        self._t0 = time.perf_counter()
        return self

    def close(self) -> None:
        if not self._open:
            return
        dur = time.perf_counter() - self._t0
        self._open = False
        stack = _local.spans
        stack.pop()
        if stack:
            stack[-1]._child_s += dur
        self._rec._add(self._name, dur, dur - self._child_s)
        self._annotation.__exit__(None, None, None)

    def __exit__(self, *exc) -> None:
        self.close()


class Recorder:
    """In-memory span aggregates and counters, safe to share between
    threads.  `counters` names counters that read 0 before any count."""

    def __init__(self, counters: Iterable[str] = ()):
        self._lock = threading.Lock()
        self._spans: Dict[str, List[float]] = {}  # name -> [count, total, self, max] (s)
        self._counters: Dict[str, int] = dict.fromkeys(counters, 0)

    def span(self, name: str, **args) -> Span:
        return Span(self, name, args)

    def _add(self, name: str, dur: float, self_s: float) -> None:
        with self._lock:
            agg = self._spans.setdefault(name, [0, 0.0, 0.0, 0.0])
            agg[0] += 1
            agg[1] += dur
            agg[2] += self_s
            agg[3] = max(agg[3], dur)

    def count(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n

    @contextlib.contextmanager
    def counting_compiles(self) -> Iterator[None]:
        """Counts as `compiles` every backend compile made on the calling
        thread inside the block."""

        def sink(event: str, _duration: float) -> None:
            if event == BACKEND_COMPILE:
                self.count("compiles")

        with _listening(sink):
            yield

    def snapshot(self) -> Dict:
        """{"spans": name -> {count, total_ms, self_ms, max_ms},
        "counters": name -> int}, read at one instant."""
        with self._lock:
            spans = {
                name: {"count": int(c), "total_ms": t * 1e3, "self_ms": s * 1e3,
                       "max_ms": m * 1e3}
                for name, (c, t, s, m) in self._spans.items()
            }
            return {"spans": spans, "counters": dict(self._counters)}
