"""Online streaming dictionary service — the serving path of the engine.

The paper's headline property is single-pass streaming: each sample is
presented to the network once (Sec. I).  This module turns the multi-device
dual solver (`core/distributed.DistributedSparseCoder`) into a service with
exactly that contract:

  * **micro-batching** — incoming per-sample requests are queued and flushed
    as fixed-size micro-batches (padded, so every coder sees ONE compiled
    shape); each sample is coded once and its `(nu, y)` resolved on a
    per-request Future;
  * **double-buffered dictionary** — readers code against a published
    *snapshot* while `fit_batch` advances the *live* copy.  `fit_batch` is
    functional (returns a new buffer), so the snapshot is immutable by
    construction and publishing is an atomic reference swap: readers never
    wait on a learning epoch or a dictionary swap and never observe a
    half-written dictionary.  (On a shared device mesh the engine programs
    themselves are serialized at micro-batch granularity — two multi-device
    XLA programs must not interleave their collectives — so a coding batch
    waits at most one fit step of compute.);
  * **online learning** — every flushed micro-batch is also fed (once) to
    the learner thread, which runs one distributed dictionary step on the
    live copy and republishes every `publish_every` steps (if the learner
    lags a sustained hot stream, the buffered learn batches are thinned by
    seeded Algorithm-R reservoir sampling at `learn_queue_cap` — discarded
    batches are counted in stats(), snapshot staleness and memory stay
    bounded, coding never stalls on learning, and what the learner DOES
    fit remains a uniform sample of everything submitted during the lag
    window rather than a biased prefix);
  * **elastic growth** — `grow(extra_model, key)` re-shards the live
    dictionary onto a mesh whose `model` axis is larger (the distributed
    counterpart of `DictionaryLearner.expanded()`, paper Sec. IV-C: new
    atoms/agents arrive mid-stream).  Graph-mode coders re-derive their
    doubly-stochastic combiner A (and its ppermute schedule) for the larger
    axis; time-varying coders re-derive the whole combiner SEQUENCE, with
    erdos steps grown neighborhood-preservingly (topology.erdos_renyi_grow);
    hierarchical coders (hier/hier_q8/chain — an N-level Kronecker chain)
    grow on the innermost model level ONLY — every outer-level group gains
    the new agents, all outer combiners are carried verbatim (outer agent
    counts are fixed at mesh construction) and each existing agent keeps
    its atom shard;
    stats() and the growth event report the topology + mixing rate (windowed
    for sequences, effective chain rate for the hierarchical family) +
    schedule spec/period + the hier pod_topology / pod_gossip_every identity
    + the uniform per-level `levels` rows (kind/axis/n/stride/wire/stale).
    Growth is applied by the learner thread at a step boundary; the batcher
    keeps coding against the old (coder, snapshot) pair until the new pair
    is published.
  * **agent drain** — `drain(departing_ranks)` is the inverse event:
    agents leave the network mid-stream and the LIVE dictionary is
    restricted to the survivors' atom shards (bit for bit — no re-init)
    on a mesh whose `model` axis is smaller
    (`DistributedSparseCoder.shrunk`).  Erdos combiners restrict to the
    survivor-induced subgraph (deterministic ring repair only if the
    departures disconnected it); a `LinkFailureSchedule` re-applies its
    seeded dropout over the shrunk base.  The handoff is
    schedule-clock-consistent: the drained coder inherits the stream's
    schedule clock (reduced mod its own period at the next claim), so
    the survivors continue ONE time-varying network rather than
    restarting at A_0.  Same swap mechanics and caveats as growth
    (applied at a learner step boundary, warmup off the serving path
    under the exec lock, stats + a drain event with the new identity).
    The new coder's programs are compiled by their first execution, which
    must hold the exec lock (collectives from two programs must not
    interleave on shared devices) — so an elastic-growth swap pauses coding
    for one compile+warmup window.  Steady-state coding and learning never
    recompile (fixed micro-batch shape).

Consistency model: a sample's code reflects the newest snapshot published
at the time its micro-batch's solve takes the exec lock (the batcher reads
the snapshot under that lock, and the learner publishes a fit before it
releases the lock) — bounded staleness of at most `publish_every` fit
steps plus one in-flight batch, never a torn read.  So with
`publish_every=1` a learned batch is coded and fitted against the same
dictionary, and the fit reuses the batch's codes (the engine's
`fits_reused`) instead of solving it again.
"""

from __future__ import annotations

import collections
import dataclasses
import queue
import threading
import time
from concurrent.futures import Future
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.distributed import DistributedSparseCoder
from repro.runtime import dist, tracing

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class ServiceConfig:
    """Knobs for the streaming service."""

    micro_batch: int = 16  # samples per coding micro-batch (padded to this)
    max_wait_s: float = 0.02  # flush a partial micro-batch after this long
    learn: bool = True  # online dictionary learning on the live copy
    mu_w: float = 0.05  # dictionary step size
    warmup: bool = True  # compile solve/fit before serving (and before a
    # growth swap), so cold-start and growth never stall the serving path
    publish_every: int = 1  # fit steps between snapshot publishes
    queue_capacity: int = 8192  # submit() blocks when this many are pending
    learn_queue_cap: int = 64  # learn batches buffered when the learner
    # lags; past this the buffer becomes a seeded Algorithm-R reservoir:
    # discarded batches are counted in stats() and the batches the learner
    # does fit stay a UNIFORM sample of the lag window.  0 = no sampling:
    # the buffer is unbounded, nothing is ever discarded, and stop()
    # blocks until the learner has consumed everything.
    learn_seed: int = 0  # seed of the reservoir's eviction draws (same
    # seed + same stream -> the same kept set, so backpressure is replayable)
    latency_window: int = 100_000  # per-sample latencies kept for stats


class _Item:
    __slots__ = ("x", "future", "t_submit")

    def __init__(self, x: np.ndarray):
        self.x = x
        self.future: Future = Future()
        self.t_submit = time.perf_counter()


def _resolve(fut: Future, result=None, exc: Optional[BaseException] = None) -> None:
    """Terminal-state a Future without ever raising: a client may have
    cancelled it, and an InvalidStateError must not kill a worker thread."""
    try:
        if exc is not None:
            fut.set_exception(exc)
        else:
            fut.set_result(result)
    except Exception:
        pass  # already cancelled/resolved by the client


class _LearnBatch:
    """One coded micro-batch offered to the learner: its real rows, and
    the padded device batch the solve coded (`x`, None once newer batches
    took its place), which the fit passes on so the engine can reuse the
    solve's codes."""

    __slots__ = ("rows", "x")

    def __init__(self, rows: np.ndarray, x: Optional[Array]):
        self.rows = rows
        self.x = x


# Learn batches that keep their device batch: the newest two, enough for a
# batcher that takes the exec lock twice in a row, so the reservoir's
# device memory stays bounded however far the learner lags.
_DEVICE_BATCHES = 2


class _LearnReservoir:
    """Seeded Algorithm-R reservoir between the batcher and the learner.

    While the learner keeps up (buffer below `cap`) this is a plain FIFO.
    Once `cap` batches are buffered, each further `offer` runs one
    Algorithm-R step over the stream seen since the buffer last saturated:
    the t-th batch of the window is kept with probability cap/t, evicting a
    uniformly-random buffered batch — so the batches the learner eventually
    fits are a UNIFORM sample of everything submitted during the lag
    window, not the oldest prefix (the pre-reservoir policy dropped every
    batch past the cap, biasing online learning toward the start of a hot
    stream).  Whenever the learner catches up enough to take a batch, the
    buffer drops below `cap` and the sampling window restarts at the
    buffer's contents.

    `cap=0` disables sampling: the buffer is unbounded and nothing is ever
    discarded (the service's stop() then blocks until the learner has
    consumed everything — strict no-drop backpressure).

    Eviction draws come from one seeded `np.random.default_rng(seed)`, so
    the kept set is a deterministic function of (seed, offer order): the
    same stream replays to the same learner input.  Single-writer /
    single-reader (batcher offers, learner takes); the internal condition
    variable makes the counters consistent for stats().
    """

    def __init__(self, cap: int, seed: int = 0):
        if cap < 0:
            raise ValueError(f"learn_queue_cap must be >= 0, got {cap}")
        self.cap = int(cap)
        self._rng = np.random.default_rng(seed)
        self._buf: List[np.ndarray] = []
        self._window = 0  # offers since the buffer last saturated
        self.seen = 0  # total batches offered
        self.discarded = 0  # batches that will never reach the learner
        self._cond = threading.Condition(threading.Lock())

    def offer(self, xb: np.ndarray) -> bool:
        """Offer one learn batch; returns True when a batch (the incoming
        one or an evicted buffered one) was discarded."""
        with self._cond:
            self.seen += 1
            if self.cap == 0 or len(self._buf) < self.cap:
                self._buf.append(xb)
                # not saturated: the sampling window is the buffer itself
                self._window = len(self._buf)
                self._cond.notify()
                return False
            # saturated: Algorithm R — keep batch t of the window with
            # probability cap/t, evicting a uniform victim
            self._window += 1
            j = int(self._rng.integers(self._window))
            if j < self.cap:
                self._buf[j] = xb
            self.discarded += 1
            return True

    def take(self, timeout: float) -> np.ndarray:
        """Oldest kept batch (FIFO over the reservoir); raises queue.Empty
        after `timeout` seconds without one."""
        with self._cond:
            if not self._buf:
                self._cond.wait(timeout)
            if not self._buf:
                raise queue.Empty
            return self._buf.pop(0)

    def empty(self) -> bool:
        with self._cond:
            return not self._buf

    def qsize(self) -> int:
        with self._cond:
            return len(self._buf)

    def clear(self) -> int:
        """Discard everything buffered (kill path); returns the count."""
        with self._cond:
            n = len(self._buf)
            self._buf.clear()
            self.discarded += n
            return n


class DictionaryService:
    """Continuously-learning dictionary server over a device mesh.

    Usage:
        coder = DistributedSparseCoder(mesh, res, reg, dist_cfg)
        with DictionaryService(coder, W0, ServiceConfig()) as svc:
            futs = [svc.submit(x_i) for x_i in stream]
            svc.grow(extra_model=2, key=key)         # mid-stream, optional
            svc.drain([1, 3])                        # decommission, optional
            results = [f.result() for f in futs]     # (nu_i, y_i) each
    """

    # The service's concurrency contract, machine-checked by
    # tools/analyze (rules lock-discipline / exec-lock): every mutation of
    # a _GUARDED_BY_LOCK attribute outside __init__ must hold `self._lock`
    # (stats()/readers see consistent snapshots), and every call of an
    # _EXEC_GUARDED_CALLS engine method outside __init__ must hold
    # `self._exec_lock` (multi-device programs with collectives must not
    # interleave).  Extending the service = extending these tuples.
    _GUARDED_BY_LOCK = (
        "submitted", "coded", "batches", "fit_steps", "fit_failures",
        "learn_dropped",
        "fit_first_error", "published", "grow_events", "drain_events",
        "_latencies", "_queue_waits",
        "_sched_t", "_coder", "_live", "_snap", "_comb_info",
        "_snap_version", "_serving_version",
    )
    _EXEC_GUARDED_CALLS = (
        "solve", "fit_batch", "score", "solve_per_agent", "adaptive_mu",
    )

    def __init__(
        self,
        coder: DistributedSparseCoder,
        W0: Array,
        cfg: ServiceConfig = ServiceConfig(),
    ):
        self.cfg = cfg
        self._lock = threading.Lock()  # guards the (coder, snapshot, live) triple
        # Multi-device XLA programs containing collectives deadlock if two of
        # them interleave their rendezvous on the same device set (each
        # device must see the programs in the same order).  All engine
        # executions therefore serialize through this lock, at micro-batch
        # granularity: a coding batch waits at most one fit step, never a
        # full learning epoch or a dictionary swap.
        self._exec_lock = threading.Lock()
        # Makes the running-check + enqueue in submit()/grow() atomic w.r.t.
        # stop()'s failure-drain, so a request racing shutdown is always
        # either processed or failed — never stranded unresolved.
        self._submit_lock = threading.Lock()
        self._coder = coder
        self._live = coder.snapshot(W0)
        self._snap = self._live
        self._m = int(W0.shape[0])
        self._pad = self._pad_target(coder)
        self._queue: "queue.Queue[_Item]" = queue.Queue(maxsize=cfg.queue_capacity)
        self._learn_q = _LearnReservoir(cfg.learn_queue_cap, cfg.learn_seed)
        # The offered learn batches that still hold a device batch (the
        # batcher's alone), oldest first.
        self._device_batches: "collections.deque[_LearnBatch]" = collections.deque()
        self._grow_q: "queue.Queue[Tuple[int, jax.Array, Optional[Tuple], Future]]" = queue.Queue()
        self._drain_q: "queue.Queue[Tuple[Tuple[int, ...], Future]]" = queue.Queue()
        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []
        self._t_start: Optional[float] = None
        # Gossip-topology identity of the current coder (label + mixing
        # rate; for time-varying coders the schedule spec, period, and the
        # WINDOWED mixing rate); re-derived on growth since the combiner —
        # or the whole sequence — is rebuilt for the larger model axis.
        self._comb_info: Dict = coder.combiner_info()
        # Time-varying schedule clock: the combiner-sequence offset the next
        # engine execution starts from.  Each solve/fit consumes cfg.iters
        # iterations of the network sequence, so the stream as a whole runs
        # ONE continuous time-varying network rather than restarting the
        # schedule at A_0 every micro-batch.  Static coders keep it at 0.
        self._sched_t = 0
        # Counters: mutated by the batcher/learner threads, read by stats().
        # EVERY mutation and the stats() read happen under self._lock so a
        # caller always sees a consistent snapshot (e.g. never a published
        # count ahead of its fit_steps).
        self.submitted = 0
        self.coded = 0
        self.batches = 0  # coded micro-batches (engine solve calls)
        self.fit_steps = 0
        self.fit_failures = 0
        self.learn_dropped = 0
        self.fit_first_error: Optional[str] = None
        self.published = 0
        self.grow_events: List[Dict] = []
        self.drain_events: List[Dict] = []
        self._latencies = collections.deque(maxlen=cfg.latency_window)
        # Per sample, submit -> its micro-batch's flush (seconds).
        self._queue_waits = collections.deque(maxlen=cfg.latency_window)
        # Spans of the batcher's and learner's work and the `compiles` the
        # worker threads make (the recorder locks for itself).
        # `fits_reused` and `fits_resolved` count the learner's fits by
        # whether the engine reused the batch's codes.
        self._trace = tracing.Recorder(
            counters=("compiles", "fits_reused", "fits_resolved"))
        # Snapshot versioning for the serving plane (runtime/serving): the
        # version of the currently-published snapshot (0 = the initial one;
        # bumped by every publish — learner republish, install_snapshot,
        # grow/drain swap) and the version the last COMPLETED solve coded
        # against.  A router sheds load from replicas whose _snap_version
        # trails the fleet head; `serving_version` is what lets a caller
        # distinguish "published" from "actually serving" (a batch in
        # flight when a snapshot lands still carries the old version).
        self._snap_version = 0
        self._serving_version = 0
        # Seconds the start-up warmup spent tracing + compiling each engine
        # program (set once by start(); growth/drain warmups not included).
        self.compile_s: Dict[str, float] = {}

    # -- helpers ----------------------------------------------------------

    def _pad_target(self, coder: DistributedSparseCoder) -> int:
        """Micro-batches are padded to a multiple of the data-axes extent so
        the batch dim always shards evenly (x spec is P(data..., None))."""
        sizes = dist.axis_sizes(coder.mesh)
        d = 1
        for nm in coder.cfg.data_axes:
            d *= sizes[nm]
        return max(self.cfg.micro_batch, d) + (-max(self.cfg.micro_batch, d)) % d

    def _pad_rows(self, xb: np.ndarray) -> np.ndarray:
        """Zero-pad a batch to the fixed micro-batch shape (one compiled
        shape per coder; zero rows code to nu=0 and cost nothing)."""
        b = xb.shape[0]
        if b >= self._pad:
            return xb
        return np.concatenate(
            [xb, np.zeros((self._pad - b, xb.shape[1]), xb.dtype)], axis=0
        )

    def _advance_schedule(self, coder) -> int:
        """Claim the next cfg.iters iterations of a time-varying coder's
        combiner sequence; returns the schedule offset t0 this execution
        starts from (always 0 for static coders).

        MUST be called while holding `_exec_lock` (both callers do): claims
        happen at the execution serialization point, so claim order equals
        execution order and the stream really runs one continuous network.
        The returned offset is reduced mod the coder's schedule period (a
        `TopologySchedule` period, or the LCM of level strides for a
        hierarchical coder — only t0 mod P reaches the compiled program,
        and the LCM is exactly the point at which every level's firing
        phase realigns) so the int
        passed to the engine stays small no matter how long the unbounded
        Python-int clock runs (an unreduced clock would eventually overflow
        the int32 cast)."""
        if not getattr(coder, "is_time_varying", False):
            return 0
        with self._lock:
            t0 = self._sched_t
            self._sched_t += coder.cfg.iters
        return t0 % coder.schedule_period

    def _rollback_schedule(self, coder) -> None:
        """Return a claimed-but-never-executed window (a fit that raised
        before running) so the clock reflects only executions that happened.
        Safe because claims only occur under `_exec_lock`, which the caller
        still holds — no concurrent claim can have built on top of ours."""
        if not getattr(coder, "is_time_varying", False):
            return
        with self._lock:
            self._sched_t -= coder.cfg.iters

    def _solve_padded(self, xb: np.ndarray):
        """Code a real batch of b rows against the published snapshot,
        read once the exec lock is held (so no fit can publish a newer one
        before the solve runs).  Returns (nu, y, the padded device batch,
        the snapshot's version)."""
        b = xb.shape[0]
        # the wait span ends where the lock is taken
        with self._trace.span("service.exec_wait.solve") as waiting, self._exec_lock:
            waiting.close()
            with self._trace.span("service.exec.solve", batch=b):
                with self._lock:
                    coder, snap, ver = self._coder, self._snap, self._snap_version
                t0 = self._advance_schedule(coder)
                x = jnp.asarray(self._pad_rows(xb), jnp.float32)
                with self._trace.span("engine.solve"):
                    nu, y = jax.block_until_ready(coder.solve(snap, x, t0))
                nu, y = np.asarray(nu), np.asarray(y)
        return nu[:b], y[:b], x, ver

    def _offer_learn(self, xb: np.ndarray, x: Array) -> bool:
        """Offer a coded batch to the learner (see `_LearnReservoir.offer`);
        a batch older than the newest `_DEVICE_BATCHES` drops its device
        copy, and the learner uploads its rows again if it ever fits it."""
        batch = _LearnBatch(xb, x)
        self._device_batches.append(batch)
        if len(self._device_batches) > _DEVICE_BATCHES:
            self._device_batches.popleft().x = None
        return self._learn_q.offer(batch)

    # -- lifecycle --------------------------------------------------------

    def _warmup(self, coder: DistributedSparseCoder, W: Array) -> Dict[str, float]:
        """Trigger the jit compiles on a zero micro-batch so the first real
        request (and the first post-growth request) pays no compile stall.
        Results are discarded; with mu_w=0 the fit warmup is a no-op step.
        Returns the trace+compile seconds of each program.

        Runs WITHOUT taking `_exec_lock` itself: start() calls it before
        any worker thread exists, and _maybe_grow() calls it while already
        holding the lock (threading.Lock is not reentrant)."""
        secs: Dict[str, float] = {}
        z = jnp.zeros((self._pad, self._m), jnp.float32)
        with tracing.compile_seconds(secs, "solve"):
            jax.block_until_ready(coder.solve(W, z))  # analyze: allow(exec-lock)
        if self.cfg.learn:
            with tracing.compile_seconds(secs, "fit"):
                jax.block_until_ready(coder.fit_batch(W, z, 0.0))  # analyze: allow(exec-lock)
        return secs

    def start(self) -> "DictionaryService":
        if self._threads:
            raise RuntimeError("service already started")
        if self._stop.is_set():
            raise RuntimeError(
                "service cannot be restarted after stop(); create a new "
                "DictionaryService (counters and queues are single-run)"
            )
        if self.cfg.warmup:
            self.compile_s = self._warmup(self._coder, self._snap)
        self._t_start = time.perf_counter()
        self._threads = [
            threading.Thread(target=self._counting_compiles, args=(self._batcher_loop,),
                             name="dict-batcher", daemon=True),
            threading.Thread(target=self._counting_compiles, args=(self._learner_loop,),
                             name="dict-learner", daemon=True),
        ]
        for t in self._threads:
            t.start()
        return self

    def _counting_compiles(self, loop) -> None:
        """A worker thread's body: `loop`, with every compile it makes
        counted (steady-state serving should make none)."""
        with self._trace.counting_compiles():
            loop()

    def stop(self) -> None:
        """Drain the queues (every submitted sample is coded — single-pass
        means no drops, including the tail), then join the workers.  Any
        request that raced the shutdown is failed, never left hanging."""
        self._stop.set()
        for t in self._threads:
            t.join()
        err = RuntimeError("service stopped before this request was processed")
        with self._submit_lock:  # no submit/grow can be mid-enqueue now
            self._threads = []
            while True:
                try:
                    _resolve(self._queue.get_nowait().future, exc=err)
                except queue.Empty:
                    break
            while True:
                try:
                    _resolve(self._grow_q.get_nowait()[3], exc=err)
                except queue.Empty:
                    break
            while True:
                try:
                    _resolve(self._drain_q.get_nowait()[1], exc=err)
                except queue.Empty:
                    break

    def kill(self) -> None:
        """Hard-stop for fault drills: fail everything still queued instead
        of draining it (stop() codes the whole backlog first — a crashed
        replica must not).  Pending Futures resolve exceptionally, which is
        the signal a serving-plane router (runtime/serving.Router) uses to
        re-route those requests to the surviving replicas.  Idempotent, and
        stop() after kill() is a no-op sweep."""
        err = RuntimeError("replica killed")
        with self._submit_lock:  # no submit/grow can be mid-enqueue now
            self._stop.set()
            while True:
                try:
                    _resolve(self._queue.get_nowait().future, exc=err)
                except queue.Empty:
                    break
        # batcher first (it may still offer one last learn batch), then
        # purge the reservoir so the learner's drain check sees it empty
        for t in self._threads[:1]:
            t.join()
        self._learn_q.clear()
        for t in self._threads[1:]:
            t.join()
        with self._submit_lock:
            self._threads = []
            while True:
                try:
                    _resolve(self._queue.get_nowait().future, exc=err)
                except queue.Empty:
                    break
            while True:
                try:
                    _resolve(self._grow_q.get_nowait()[3], exc=err)
                except queue.Empty:
                    break
            while True:
                try:
                    _resolve(self._drain_q.get_nowait()[1], exc=err)
                except queue.Empty:
                    break

    def __enter__(self) -> "DictionaryService":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- client API -------------------------------------------------------

    def submit(self, x: np.ndarray) -> Future:
        """Enqueue one sample (M,); the Future resolves to (nu (M,), y (K,))."""
        x = np.asarray(x, np.float32)
        if x.shape != (self._m,):
            raise ValueError(f"expected sample shape ({self._m},), got {x.shape}")
        item = _Item(x)
        with self._submit_lock:
            if self._stop.is_set() or not self._threads:
                raise RuntimeError(
                    "service is not running (submit() before start() or after "
                    "stop() would enqueue a sample no worker will ever code)"
                )
            self._queue.put(item)
        with self._lock:
            self.submitted += 1
        return item.future

    def submit_many(self, X: np.ndarray) -> List[Future]:
        return [self.submit(x) for x in X]

    def grow(self, extra_model: int, key: jax.Array, devices=None) -> Future:
        """Request elastic growth of the model axis by `extra_model` agents.
        Applied by the learner thread at the next step boundary; the Future
        resolves to an info dict once the new (coder, snapshot) is live.

        `devices` is the flat device pool the GROWN mesh is built from
        (current devices + the arrivals).  It defaults to all of
        jax.devices() — correct for a single-tenant service, but a replica
        in a fleet (runtime/serving.ReplicaSet) must pass its own enlarged
        subset or the grown mesh would annex devices owned by its peers."""
        fut: Future = Future()
        with self._submit_lock:
            if self._stop.is_set() or not self._threads:
                raise RuntimeError("service is not running; cannot grow")
            self._grow_q.put((int(extra_model), key, devices, fut))
        return fut

    def drain(self, departing_ranks: Sequence[int]) -> Future:
        """Request decommission of `departing_ranks` model agents (the
        inverse of grow()).  Applied by the learner thread at the next step
        boundary; the Future resolves to an info dict once the shrunk
        (coder, snapshot) pair is live.  Surviving agents keep their atom
        shards bit for bit, and the stream's schedule clock carries over
        (the survivors continue ONE time-varying network)."""
        departing = tuple(sorted(set(int(r) for r in departing_ranks)))
        if not departing:
            raise ValueError("departing_ranks is empty: nothing to drain")
        fut: Future = Future()
        with self._submit_lock:
            if self._stop.is_set() or not self._threads:
                raise RuntimeError("service is not running; cannot drain")
            self._drain_q.put((departing, fut))
        return fut

    def dictionary(self) -> np.ndarray:
        """Host copy of the currently *published* dictionary snapshot."""
        with self._lock:
            snap = self._snap
        return np.asarray(jax.device_get(snap))

    @property
    def sample_dim(self) -> int:
        """Row dimension M a submitted sample must have."""
        return self._m

    def running(self) -> bool:
        """True while the worker threads are up and shutdown hasn't begun
        (the window in which submit()/grow()/drain() are accepted)."""
        return bool(self._threads) and not self._stop.is_set()

    def install_snapshot(self, W: np.ndarray) -> int:
        """Externally publish a dictionary (the fan-out path of
        runtime/serving.ReplicaSet.publish): shard `W` onto this coder's
        mesh and atomically swap it in as BOTH the live copy and the
        published snapshot, exactly like a grow/drain swap.  Returns the
        new snapshot version.  In-flight micro-batches finish against the
        old snapshot (and report its version as serving_version); the next
        flushed batch codes against `W` — readers never pause.
        """
        W = np.asarray(W, np.float32)
        with self._submit_lock:
            if self._stop.is_set() or not self._threads:
                raise RuntimeError("service is not running; cannot install a snapshot")
        with self._lock:
            coder, live = self._coder, self._live
        want = tuple(int(s) for s in live.shape)
        if tuple(W.shape) != want:
            raise ValueError(
                f"snapshot shape {W.shape} does not match the live dictionary "
                f"{want} (grow/drain the replica first, then publish)"
            )
        # Device placement outside _lock (it is a transfer, not a mutation);
        # the swap below re-checks the coder so a concurrent grow/drain that
        # changed the mesh underneath us fails loudly instead of installing
        # a stale-sharded buffer.
        W_dev = coder.snapshot(W)
        with self._lock:
            if self._coder is not coder:
                raise RuntimeError(
                    "coder changed (grow/drain) during install_snapshot; retry "
                    "against the new geometry"
                )
            self._live = W_dev
            self._snap = W_dev
            self.published += 1
            self._snap_version += 1
            return self._snap_version

    def load(self) -> Dict:
        """Cheap routing signal for the serving plane: queue depth plus the
        snapshot/serving versions, in one consistent read (no latency
        percentiles — stats() is for humans, load() is for the router's
        per-batch scoring loop)."""
        with self._lock:
            return {
                "queue_depth": self._queue.qsize(),
                "snapshot_version": self._snap_version,
                "serving_version": self._serving_version,
                "coded": self.coded,
            }

    def stats(self) -> Dict:
        """One consistent snapshot of the service counters: throughput,
        latency percentiles, learner progress, growth events, and the gossip
        identity (topology label, mixing rate — windowed for time-varying
        schedules, the effective two-level rate for hierarchical coders —
        plus schedule spec/period, the active-schedule index the next engine
        execution starts from, and the hier pod_topology /
        pod_gossip_every)."""
        elapsed = (time.perf_counter() - self._t_start) if self._t_start else 0.0
        trace = self._trace.snapshot()
        with self._lock:  # one consistent snapshot of every counter
            lat = np.asarray(self._latencies, np.float64)
            waits = np.asarray(self._queue_waits, np.float64)
            out = {
                "submitted": self.submitted,
                "coded": self.coded,
                "batches": self.batches,
                "compile_s": dict(self.compile_s),
                "fit_steps": self.fit_steps,
                "fit_failures": self.fit_failures,
                "fit_first_error": self.fit_first_error,
                "learn_dropped": self.learn_dropped,
                "learn_seen": self._learn_q.seen,
                "published": self.published,
                # Versioning for the serving plane: the published snapshot's
                # version vs the version the last COMPLETED solve actually
                # coded against (a batch in flight when a publish lands
                # still carries the old version).
                "snapshot_version": self._snap_version,
                "serving_version": self._serving_version,
                "grow_events": [dict(ev) for ev in self.grow_events],
                "drain_events": [dict(ev) for ev in self.drain_events],
                "topology": self._comb_info["topology"],
                "mixing_rate": self._comb_info["mixing_rate"],
                # Time-varying schedule identity: the spec (None when
                # static), its period, and the index of the combiner the
                # NEXT engine execution starts from.
                "schedule": self._comb_info.get("schedule"),
                "schedule_period": self._comb_info.get("schedule_period", 1),
                "active_schedule": (
                    self._sched_t % self._comb_info.get("schedule_period", 1)
                ),
                # Hierarchical (two-level shim) gossip identity: the
                # inter-pod combiner kind and its sparse-gossip stride
                # (None / 1 for every flat mode and for mode="chain").
                "pod_topology": self._comb_info.get("pod_topology"),
                "pod_gossip_every": self._comb_info.get("pod_gossip_every", 1),
                # Uniform per-level metadata rows, innermost-first: one per
                # chain level for the hierarchical family, a single row for
                # every flat mode (kind/axis/n/gossip_every/wire/stale).
                "levels": self._comb_info.get("levels"),
                "elapsed_s": elapsed,
                "samples_per_s": (self.coded / elapsed) if elapsed > 0 else 0.0,
            }
        # Spans of the worker threads (service.collect, service.exec_wait.*,
        # service.exec.*, engine.*, service.resolve) and the compiles they
        # made after start().
        out["spans"] = trace["spans"]
        out["counters"] = trace["counters"]
        for key, secs in (("latency_ms", lat), ("queue_wait_ms", waits)):
            if secs.size:
                out[key] = {
                    "p50": float(np.percentile(secs, 50) * 1e3),
                    "p95": float(np.percentile(secs, 95) * 1e3),
                    "p99": float(np.percentile(secs, 99) * 1e3),
                    "max": float(secs.max() * 1e3),
                }
        return out

    # -- worker loops -----------------------------------------------------

    def _collect(self) -> List[_Item]:
        """Block for the first item, then fill up to micro_batch until the
        max_wait deadline passes (classic size-or-deadline batcher)."""
        items: List[_Item] = []
        try:
            items.append(self._queue.get(timeout=0.01))
        except queue.Empty:
            return items
        with self._trace.span("service.collect"):
            deadline = time.perf_counter() + self.cfg.max_wait_s
            while len(items) < self.cfg.micro_batch:
                left = deadline - time.perf_counter()
                if left <= 0:
                    break
                try:
                    items.append(self._queue.get(timeout=left))
                except queue.Empty:
                    break
        return items

    def _batcher_loop(self) -> None:
        while True:
            items = self._collect()
            if not items:
                if self._stop.is_set() and self._queue.empty():
                    return
                continue
            t_flush = time.perf_counter()
            xb = np.stack([it.x for it in items])
            try:
                nu, y, x, ver = self._solve_padded(xb)
            except Exception as e:  # resolve futures so clients never hang
                for it in items:
                    _resolve(it.future, exc=e)
                continue
            with self._trace.span("service.resolve"):
                dropped = False
                if self.cfg.learn:
                    # learner lagging past the cap: the reservoir evicts a
                    # uniform victim (and counts it) rather than stalling
                    # coding or letting staleness/memory grow without bound
                    dropped = self._offer_learn(xb, x)
                # Account BEFORE resolving futures: a client woken by the
                # last result may immediately read stats() and must see this
                # batch counted (and must not observe _latencies mid-append).
                t_done = time.perf_counter()
                with self._lock:
                    for it in items:
                        self._latencies.append(t_done - it.t_submit)
                        self._queue_waits.append(t_flush - it.t_submit)
                    self.coded += len(items)
                    self.batches += 1
                    self._serving_version = ver
                    if dropped:
                        self.learn_dropped += 1
                for i, it in enumerate(items):
                    _resolve(it.future, (nu[i], y[i]))

    def _learner_loop(self) -> None:
        while True:
            self._maybe_grow()
            self._maybe_drain()
            try:
                batch = self._learn_q.take(timeout=0.02)
            except queue.Empty:
                # Exit only once the batcher has EXITED (not merely an empty
                # queue — it may be mid-solve, about to enqueue the final
                # learn batch) and everything it produced is consumed.
                batcher = self._threads[0] if self._threads else None
                if (
                    self._stop.is_set()
                    and (batcher is None or not batcher.is_alive())
                    and self._learn_q.empty()
                ):
                    return
                continue
            b = batch.rows.shape[0]
            x = batch.x
            try:
                # the wait span ends where the lock is taken
                with self._trace.span("service.exec_wait.fit") as waiting, self._exec_lock:
                    waiting.close()
                    with self._trace.span("service.exec.fit", fit=self.fit_steps + 1):
                        with self._lock:
                            coder, live = self._coder, self._live
                        t0 = self._advance_schedule(coder)
                        try:
                            if x is None:
                                x = jnp.asarray(self._pad_rows(batch.rows), jnp.float32)
                            # Zero pad rows code to nu=0 so they add nothing
                            # to the gradient sum; rescale mu_w so the
                            # minibatch mean is over REAL samples.
                            mu_w_eff = self.cfg.mu_w * (x.shape[0] / b)
                            reused = coder.fits_reused
                            with self._trace.span("engine.fit"):
                                live2 = coder.fit_batch(live, x, mu_w_eff, t0)
                                jax.block_until_ready(live2)
                        except Exception:
                            # the claimed window never ran: hand it back so
                            # the schedule clock only counts real executions
                            self._rollback_schedule(coder)
                            raise
                        # Published before the exec lock is released, so
                        # the next solve codes against this fit's W.
                        self._publish_fit(coder, live2)
                        self._trace.count("fits_reused" if coder.fits_reused > reused
                                          else "fits_resolved")
            except Exception as e:
                # A failed fit step must never take down serving, but it
                # must not be invisible either: count it and keep the first
                # error for stats().
                with self._lock:
                    self.fit_failures += 1
                    if self.fit_first_error is None:
                        self.fit_first_error = repr(e)

    def _publish_fit(self, coder, live2: Array) -> None:
        """Count a fit step and make its W the live copy, and the published
        snapshot every `publish_every` steps."""
        with self._lock:
            self.fit_steps += 1
            # only publish if no growth swapped the coder underneath us
            if self._coder is coder:
                self._live = live2
                if self.fit_steps % self.cfg.publish_every == 0:
                    self._snap = live2
                    self.published += 1
                    self._snap_version += 1

    def _maybe_grow(self) -> None:
        try:
            extra, key, devices, fut = self._grow_q.get_nowait()
        except queue.Empty:
            return
        try:
            with self._lock:
                coder, live = self._coder, self._live
            k_old = int(live.shape[1])
            new_coder, W2 = coder.grown(live, extra, key, devices=devices)
            if self.cfg.warmup:
                # compile the new coder OFF the serving path: readers keep
                # coding on the old (coder, snapshot) pair until the swap.
                # The warmup executes on devices shared with in-flight
                # old-coder programs, so it takes the exec lock too.
                with self._exec_lock:
                    self._warmup(new_coder, W2)
            # The grown coder re-derived its combiner for the larger model
            # axis (DistributedSparseCoder.__init__ rebuilds A from the new
            # mesh), so the topology identity changes with the swap.
            new_info = new_coder.combiner_info()
            with self._lock:
                self._coder, self._live, self._snap = new_coder, W2, W2
                self._comb_info = new_info
                self.published += 1
                self._snap_version += 1
                info = {
                    "at_coded": self.coded,
                    "k_old": k_old,
                    "k_new": int(W2.shape[1]),
                    "model_old": dist.axis_sizes(coder.mesh)[coder.cfg.model_axis],
                    "model_new": dist.axis_sizes(new_coder.mesh)[new_coder.cfg.model_axis],
                    "topology": new_info["topology"],
                    "mixing_rate": new_info["mixing_rate"],
                    "schedule": new_info.get("schedule"),
                    "schedule_period": new_info.get("schedule_period", 1),
                    "pod_topology": new_info.get("pod_topology"),
                    "pod_gossip_every": new_info.get("pod_gossip_every", 1),
                    "levels": new_info.get("levels"),
                }
                self.grow_events.append(info)
            _resolve(fut, info)
        except Exception as e:
            _resolve(fut, exc=e)

    def _maybe_drain(self) -> None:
        try:
            departing, fut = self._drain_q.get_nowait()
        except queue.Empty:
            return
        try:
            with self._lock:
                coder, live = self._coder, self._live
            k_old = int(live.shape[1])
            new_coder, W2 = coder.shrunk(live, departing)
            if self.cfg.warmup:
                # compile the shrunk coder OFF the serving path: readers keep
                # coding on the old (coder, snapshot) pair until the swap.
                # The warmup executes on devices shared with in-flight
                # old-coder programs, so it takes the exec lock too.
                with self._exec_lock:
                    self._warmup(new_coder, W2)
            # The shrunk coder restricted (or re-derived) its combiner for
            # the survivor network, so the topology identity changes with
            # the swap.  The schedule clock is NOT reset: _advance_schedule
            # reduces it mod the new coder's period at the next claim, so
            # the survivors continue one continuous time-varying network.
            new_info = new_coder.combiner_info()
            with self._lock:
                self._coder, self._live, self._snap = new_coder, W2, W2
                self._comb_info = new_info
                self.published += 1
                self._snap_version += 1
                info = {
                    "at_coded": self.coded,
                    "departed": list(departing),
                    "k_old": k_old,
                    "k_new": int(W2.shape[1]),
                    "model_old": dist.axis_sizes(coder.mesh)[coder.cfg.model_axis],
                    "model_new": dist.axis_sizes(new_coder.mesh)[new_coder.cfg.model_axis],
                    "sched_t": self._sched_t,
                    "topology": new_info["topology"],
                    "mixing_rate": new_info["mixing_rate"],
                    "schedule": new_info.get("schedule"),
                    "schedule_period": new_info.get("schedule_period", 1),
                    "pod_topology": new_info.get("pod_topology"),
                    "pod_gossip_every": new_info.get("pod_gossip_every", 1),
                    "levels": new_info.get("levels"),
                }
                self.drain_events.append(info)
            _resolve(fut, info)
        except Exception as e:
            _resolve(fut, exc=e)
