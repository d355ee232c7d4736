"""Spans around the service's calls into the engine, taken from the
benchmark's side: the service is handed this wrapper in place of its
`DistributedSparseCoder` and sees the same object in every other respect.

Each armed call is recorded with its start and end on the host clock
(perf_counter) and the dictionary version it ran against; the end is taken
after the result is ready on the device, which the service waits for next
in any case.  A fit keeps its input batch, which the reference follows and
`learned_per_s` counts the real rows of after the window; a solve keeps
its input only where the caller asks for it (`keep_solve_inputs`, for the
tests), since a window's solve inputs would otherwise stay on the device
to its close and count in its peak memory.  Spans also go into the profiler's
trace as `bench.solve` / `bench.fit`, so a traced run can say what the host
was doing while the device idled.

`gate` is held by the benchmark's client while it submits a sample, or a
request that its kind answers with one future, and attaches the done
callback.  A solve passes through it before it starts, so a solve that
holds a sample waits until that sample's callback is in place, and the
callback runs in the batcher as the future resolves.
"""

from __future__ import annotations

import contextlib
import threading
import time
import weakref
from typing import Callable, Optional

import jax
import numpy as np


class Gate:
    """Keeps a solve from starting while the client is between submitting a
    sample and attaching its callback, and lets a solve that waits go before
    the client's next sample, so a burst of submissions does not hold the
    batcher back for longer than one sample takes."""

    def __init__(self):
        self._cond = threading.Condition()
        self._held = False
        self._waiting = 0

    @contextlib.contextmanager
    def held(self):
        """Around one submission and the attaching of its callbacks."""
        with self._cond:
            while self._waiting:
                self._cond.wait()
            self._held = True
        try:
            yield
        finally:
            with self._cond:
                self._held = False
                self._cond.notify_all()

    def pass_through(self) -> None:
        """Before a solve: wait for the submission in progress, if any."""
        with self._cond:
            self._waiting += 1
            while self._held:
                self._cond.wait()
            self._waiting -= 1
            self._cond.notify_all()


class EngineProbe:
    def __init__(self, coder, digest: Optional[Callable] = None, digest_fits: int = 0,
                 keep_solve_inputs: bool = False):
        self._inner = coder
        self._digest = digest
        self._digest_fits = digest_fits
        self._keep_solve_inputs = keep_solve_inputs
        self._lock = threading.Lock()
        self.gate = Gate()
        self._versions = {}  # id(W) -> (weakref to W, version)
        self._last_snapshot = None
        self.armed = False
        self.solves = []  # dicts: t0, t1, version (and x, the device batch, if kept)
        self.fits = []  # dicts: t0, t1, x (device array)
        self.digests = []  # host W[:, cols] after fit 1, 2, ...

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def _version(self, W) -> int:
        ref = self._versions.get(id(W))
        if ref is None or ref[0]() is not W:
            return -1
        return ref[1]

    def _register(self, W, version: int) -> None:
        self._versions[id(W)] = (weakref.ref(W), version)

    def arm(self) -> None:
        """Start recording; the last published snapshot is version 0."""
        self._register(self._last_snapshot, 0)
        self.armed = True

    def snapshot(self, W):
        out = self._inner.snapshot(W)
        self._last_snapshot = out
        return out

    @property
    def solves_done(self) -> int:
        return len(self.solves)

    def solve(self, W, x, t0: int = 0):
        self.gate.pass_through()
        if not self.armed:
            return self._inner.solve(W, x, t0)
        t_start = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.solve"):
            out = jax.block_until_ready(self._inner.solve(W, x, t0))
        t_end = time.perf_counter()
        with self._lock:
            record = dict(t0=t_start, t1=t_end, version=self._version(W))
            if self._keep_solve_inputs:
                record["x"] = x
            self.solves.append(record)
        return out

    def fit_batch(self, W, x, mu_w: float, t0: int = 0):
        if not self.armed:
            return self._inner.fit_batch(W, x, mu_w, t0)
        t_start = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.fit"):
            out = jax.block_until_ready(self._inner.fit_batch(W, x, mu_w, t0))
        t_end = time.perf_counter()
        with self._lock:
            version = len(self.fits) + 1
            self.fits.append(dict(t0=t_start, t1=t_end, x=x))
            self._register(out, version)
            if self._digest is not None and version <= self._digest_fits:
                self.digests.append(np.asarray(self._digest(out)))
        return out
