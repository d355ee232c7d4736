"""Unified mesh/collectives runtime — the repo's single communication seam.

The paper's protocol (arXiv:1402.1515) maps the network of N agents onto
the `model` axis of a device mesh and realizes gossip as collectives over
that axis.  Every mesh, every `shard_map` entry, and every gossip exchange
in the repo is constructed HERE, so (a) the jax mesh/shard_map API is
called in one place (runtime/compat.py, which this module fronts), and (b)
new topologies, combiners, or backends plug in at one seam instead of per
solver.

Mode -> collective mapping (core/distributed.py consumes these):

  exact, exact_fista   gossip_psum        one all-reduce of the local
                                          back-projection per iteration
                                          (fully-connected A = 11^T/N)
  ring, ring_async     ring_shift         ppermute to both ring neighbors
                                          (constant-weight ring combiner)
  ring_q8              ring_shift over    int8 messages + per-row scales,
                       (quantize_q8 ..)   error feedback kept by the caller
  graph, graph_async   graph_combine /    ANY doubly-stochastic combiner A
                       graph_shift        (core/topology.make_topology)
                                          compiled to a static ppermute
                                          schedule: one shift per distinct
                                          edge-offset of the graph, with a
                                          per-rank weight table baked in
  graph_q8             graph_combine_     same schedule over the int8 wire
                       quantized          format (quantize_q8 scales ride
                                          along each shift)
  push                 push_graph_        push-sum (ratio consensus): a
                       combine            scalar weight channel rides every
                                          shift next to psi and the dual
                                          update divides by it — only needs
                                          A ROW stochastic, so DIRECTED
                                          combiners (make_topology's
                                          "dicycle"/"distar") are admissible
  push_q8              push_graph_        the same ratio consensus over the
                       combine_quantized  int8 payload format (the scalar
                                          weight channel stays fp32)
  graph_tv             graph_combine_     TIME-VARYING combiner sequence
                       switch over        (core/topology.TopologySchedule):
                       (graph_schedule_   every A_t pre-compiled to its own
                       sequence ...)      ppermute schedule, the active one
                                          selected per iteration by the
                                          traced index via lax.switch — the
                                          whole run stays ONE compiled
                                          program
  graph_tv_q8          graph_combine_     the same switch over the int8
                       quantized_switch   wire format
  chain                chain_combine over HIERARCHICAL N-level gossip
                       (chain_schedule    (core/topology.KroneckerChain):
                       of a Kronecker-    one `GraphSchedule` per level,
                       Chain)             applied INNERMOST-FIRST inside
                                          one shard_map body, realizing the
                                          Kronecker chain A_{L-1} (x) ...
                                          (x) A_0.  Each level's hop is
                                          gated on its own stride by the
                                          traced iteration index (lax.cond
                                          — one compiled program, like the
                                          tv switch), ships fp32 or q8
                                          (+error feedback) per its wire
                                          format, and the OUTERMOST level
                                          may combine one-step-stale
                                          messages (graph_async style) to
                                          hide long-haul latency
  hier                 hier_combine       two-level special case of the
                                          chain (`HierSchedule.as_chain`):
                                          intra-pod schedule over
                                          MODEL_AXIS, inter-pod over
                                          POD_AXIS, pod hop gated on
                                          gossip_every
  hier_q8              hier_combine_      the same composition with the q8
                       quantized          wire format on the INTER-POD hop
                                          only (that is the bandwidth-
                                          constrained link; the intra-pod
                                          hop stays full precision)

A torus combiner additionally gets `torus_schedule`: exactly four neighbor
permutations (row +/-1, column +/-1) that map onto 2-D ICI links instead of
the up-to-(N-1) flat offsets the generic decomposition would use.

Mesh factories:

  debug_mesh        (data, model) or (pod, data, model) over however many
                    devices the platform exposes — tests force N CPU
                    devices via XLA_FLAGS and call this.
  production_mesh   (16, 16) v5e pod or (2, 16, 16) two pods.
  make_mesh         arbitrary (shape, axes) — serving CLIs, elastic
                    rescale targets.
  abstract_mesh     shape-only mesh for sharding-rule logic with NO device
                    requirement (divisibility guards on production sizes).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime.compat import (  # re-exported: THE way to get these
    abstract_mesh,
    axis_sizes,
    make_mesh,
    shard_map,
)

__all__ = [
    "shard_map",
    "make_mesh",
    "abstract_mesh",
    "axis_sizes",
    "as_mesh",
    "debug_mesh",
    "production_mesh",
    "gossip_psum",
    "ring_perms",
    "ring_shift",
    "all_to_all_tiled",
    "all_gather_tiled",
    "psum_scatter_tiled",
    "quantize_q8",
    "dequantize_q8",
    "GraphSchedule",
    "graph_schedule",
    "torus_schedule",
    "graph_schedule_sequence",
    "graph_shift",
    "graph_accumulate",
    "graph_combine",
    "graph_combine_quantized",
    "graph_combine_switch",
    "graph_combine_quantized_switch",
    "push_graph_combine",
    "push_graph_combine_quantized",
    "LevelPlan",
    "ChainSchedule",
    "chain_schedule",
    "wire_bytes_per_level",
    "chain_state_init",
    "chain_combine",
    "HierSchedule",
    "hier_schedule",
    "hier_combine",
    "hier_combine_quantized",
]

Array = jax.Array

# Canonical axis roles (DESIGN §2): `model` is the agent/TP/gossip axis,
# `data` the intra-pod DP/FSDP axis, `pod` the cross-pod pure-DP axis.
MODEL_AXIS = "model"
DATA_AXIS = "data"
POD_AXIS = "pod"


# ---------------------------------------------------------------------------
# Mesh factories
# ---------------------------------------------------------------------------


def debug_mesh(model: int, data: int = 1, pods: int = 0, outer: tuple = ()):
    """CPU/debug mesh with the production axis names over the first
    `prod(outer)*pods*data*model` visible devices (tests force multi-device
    via XLA_FLAGS=--xla_force_host_platform_device_count=N).

    `outer` adds agent levels ABOVE the pod level for N-level chain runs,
    outermost first; their axes are named "pod2", "pod3", ... innermost-out
    to match `DistConfig.level_axis` — e.g. ``debug_mesh(model=2, pods=2,
    outer=(2,))`` is the (2, 2, 1, 2) mesh ("pod2", "pod", "data",
    "model")."""
    if outer and not pods:
        raise ValueError("outer levels require pods >= 1 (the pod level "
                         "sits between model and the outer levels)")
    if pods:
        n_out = len(outer)
        names = tuple(
            f"{POD_AXIS}{n_out + 1 - i}" for i in range(n_out)
        ) + (POD_AXIS, DATA_AXIS, MODEL_AXIS)
        return make_mesh((*outer, pods, data, model), names)
    return make_mesh((data, model), (DATA_AXIS, MODEL_AXIS))


def production_mesh(*, multi_pod: bool = False):
    """One v5e pod (data=16, model=16) = 256 chips, or two pods with a
    leading pure-DP `pod` axis = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = (POD_AXIS, DATA_AXIS, MODEL_AXIS) if multi_pod else (DATA_AXIS, MODEL_AXIS)
    return make_mesh(shape, axes)


def as_mesh(mesh_or_shape, axes: Sequence[str] = (DATA_AXIS, MODEL_AXIS)):
    """Accept a ready Mesh or an int shape tuple (elastic-rescale callers
    pass the target shape; everything else passes a Mesh through)."""
    if hasattr(mesh_or_shape, "axis_names"):
        return mesh_or_shape
    return make_mesh(tuple(mesh_or_shape), tuple(axes))


# ---------------------------------------------------------------------------
# Gossip collectives (used inside shard_map bodies)
# ---------------------------------------------------------------------------


def gossip_psum(x, axis_name: str):
    """Exact-mode gossip: fully-connected combine = one all-reduce."""
    return jax.lax.psum(x, axis_name)


def ring_perms(n: int) -> Tuple[List[Tuple[int, int]], List[Tuple[int, int]]]:
    """(forward, backward) ppermute permutations of an n-ring; static, so
    they must be built from the mesh axis SIZE, not from traced values."""
    fwd = [(i, (i + 1) % n) for i in range(n)]
    bwd = [(i, (i - 1) % n) for i in range(n)]
    return fwd, bwd


def ring_shift(x, axis_name: str, n: int):
    """Send `x` (array or pytree) to both ring neighbors over `axis_name`
    (size n); returns (from_left, from_right).  This is the diffusion
    combine's data movement: each agent receives psi from its two ring
    neighbors (doubly-stochastic [beta, 1-2beta, beta] combiner)."""
    fwd, bwd = ring_perms(n)
    left = jax.tree.map(lambda v: jax.lax.ppermute(v, axis_name, fwd), x)
    right = jax.tree.map(lambda v: jax.lax.ppermute(v, axis_name, bwd), x)
    return left, right


# ---------------------------------------------------------------------------
# Graph gossip: any doubly-stochastic combiner A compiled to a static
# ppermute schedule (the production realization of core/topology combiners)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class GraphSchedule:
    """Static data-movement plan for nu_k = sum_l A[l, k] psi_l over a mesh
    axis of size `n`.

    `steps` holds one entry per collective round: a ppermute permutation
    (src, dst) pairs covering every rank, and the per-DESTINATION weight
    table w with w[dst] = A[src, dst] for that round's (src -> dst) edge.
    `diag` is the self-weight A[k, k].  Everything is plain Python data,
    fixed at trace time — permutations can never depend on traced values.
    """

    n: int
    diag: Tuple[float, ...]
    steps: Tuple[Tuple[Tuple[Tuple[int, int], ...], Tuple[float, ...]], ...]

    def reconstruct(self) -> np.ndarray:
        """Dense A this schedule realizes (host-side; tests/benchmarks)."""
        a = np.diag(np.asarray(self.diag, np.float64))
        for perm, w in self.steps:
            for src, dst in perm:
                a[src, dst] += w[dst]
        return a

    @property
    def messages_per_iter(self) -> int:
        """ppermute rounds per combine = per-device messages per iteration."""
        return len(self.steps)


def _check_combiner(A: np.ndarray, row_stochastic: bool = False) -> np.ndarray:
    from repro.core.topology import (  # numpy-only leaves
        is_doubly_stochastic,
        is_row_stochastic,
    )

    A = np.asarray(A, np.float64)
    if A.ndim != 2 or A.shape[0] != A.shape[1]:
        raise ValueError(f"combiner must be square, got shape {A.shape}")
    if row_stochastic:
        if not is_row_stochastic(A):
            raise ValueError(
                "push-sum combiner A must be row stochastic (nonnegative, "
                "rows summing to 1 — mass conservation under the combine "
                "convention nu_k = sum_l A[l, k] psi_l) — see "
                "core/topology.make_topology's directed kinds"
            )
    elif not is_doubly_stochastic(A):
        raise ValueError(
            "combiner A must be doubly stochastic (nonnegative, rows and "
            "columns summing to 1) — see core/topology.make_topology"
        )
    return A


def graph_schedule(
    A: np.ndarray, tol: float = 0.0, *, row_stochastic: bool = False
) -> GraphSchedule:
    """Compile a doubly-stochastic combiner into a ppermute schedule.

    Decomposes A by flat edge-offset: round d (1 <= d < n) shifts psi by d
    along the axis and each destination k scales the received value by
    A[(k - d) % n, k].  Offsets with an all-zero weight table are dropped, so
    a sparse graph costs exactly its number of distinct edge-offsets per
    iteration (ring combiners reduce to the familiar two shifts).

    `row_stochastic=True` relaxes the admission check to row stochasticity
    only — the push-sum (ratio-consensus) contract, which is what lets the
    push modes run DIRECTED combiners whose columns do not sum to one.
    The offset decomposition itself is combiner-agnostic.
    """
    A = _check_combiner(A, row_stochastic=row_stochastic)
    n = A.shape[0]
    steps = []
    for d in range(1, n):
        w = np.array([A[(k - d) % n, k] for k in range(n)])
        if np.any(np.abs(w) > tol):
            perm = tuple((i, (i + d) % n) for i in range(n))
            steps.append((perm, tuple(float(v) for v in w)))
    return GraphSchedule(
        n=n, diag=tuple(float(A[k, k]) for k in range(n)), steps=tuple(steps)
    )


def torus_schedule(rows: int, cols: int, A: np.ndarray) -> GraphSchedule:
    """Compile a torus combiner into four neighbor permutations.

    The generic offset decomposition of a (rows x cols) torus costs up to
    three flat offsets per axis; this schedule instead uses exactly one
    permutation per grid direction (row +/-1, column +/-1), each of which is
    a nearest-neighbor exchange on a 2-D ICI mesh.  Degenerate axes (rows or
    cols <= 2, where the +1 and -1 neighbors coincide) are deduplicated so
    each graph edge is shipped and weighted once.
    """
    A = _check_combiner(A)
    n = rows * cols
    if A.shape[0] != n:
        raise ValueError(f"combiner is {A.shape[0]}x{A.shape[0]}, torus has {n} ranks")

    def idx(r: int, c: int) -> int:
        return (r % rows) * cols + (c % cols)

    directions = (
        lambda r, c: (r - 1, c),  # receive from the row above
        lambda r, c: (r + 1, c),
        lambda r, c: (r, c - 1),  # receive from the left column
        lambda r, c: (r, c + 1),
    )
    steps = []
    seen: set = set()  # (src, dst) edges already carried by an earlier round
    for nbr in directions:
        perm, w = [], [0.0] * n
        for r in range(rows):
            for c in range(cols):
                dst = idx(r, c)
                src = idx(*nbr(r, c))
                perm.append((src, dst))
                if src != dst and (src, dst) not in seen:
                    seen.add((src, dst))
                    w[dst] = float(A[src, dst])
        if any(v != 0.0 for v in w):
            steps.append((tuple(perm), tuple(w)))
    return GraphSchedule(
        n=n, diag=tuple(float(A[k, k]) for k in range(n)), steps=tuple(steps)
    )


def _rank_weight(weights: Tuple[float, ...], axis_name: str) -> Array:
    """This rank's entry of a static per-rank weight table (replicated
    constant indexed by axis_index — stays inside the shard_map body)."""
    return jnp.asarray(weights, jnp.float32)[jax.lax.axis_index(axis_name)]


def graph_shift(x, axis_name: str, sched: GraphSchedule) -> Tuple:
    """Data movement only: run every ppermute round of the schedule on `x`
    (array or pytree); returns one received message per round.  Callers that
    combine with STALE messages (graph_async) keep these as scan carry."""
    return tuple(
        jax.tree.map(lambda v: jax.lax.ppermute(v, axis_name, list(perm)), x)
        for perm, _ in sched.steps
    )


def graph_accumulate(x_self, received: Sequence, axis_name: str, sched: GraphSchedule):
    """Weighted combine diag[k] * x_self + sum_rounds w[k] * received[round]
    — the arithmetic half of graph_combine, split out so the async mode can
    feed it one-step-stale messages."""
    d = _rank_weight(sched.diag, axis_name)
    out = jax.tree.map(lambda v: d.astype(v.dtype) * v, x_self)
    for (_, weights), r in zip(sched.steps, received):
        w = _rank_weight(weights, axis_name)
        out = jax.tree.map(lambda o, v: o + w.astype(v.dtype) * v, out, r)
    return out


def graph_combine(x, axis_name: str, sched: GraphSchedule):
    """Synchronous graph gossip: nu_k = sum_l A[l, k] psi_l realized as
    `len(sched.steps)` ppermutes + weighted accumulate."""
    return graph_accumulate(x, graph_shift(x, axis_name, sched), axis_name, sched)


def graph_schedule_sequence(
    As: Sequence[np.ndarray], kinds: Optional[Sequence[str]] = None
) -> Tuple[GraphSchedule, ...]:
    """Compile a time-varying combiner sequence (one (n, n) doubly-stochastic
    A per step, e.g. `core/topology.TopologySchedule.combiners`) into a tuple
    of static ppermute schedules.

    `kinds` (same length, entries from core/topology.GRAPH_KINDS) routes
    torus steps through `torus_schedule` so an alternating ring/torus
    sequence keeps the 4-link 2-D ICI data movement on its torus iterations;
    everything else takes the generic edge-offset decomposition.
    """
    from repro.core.topology import torus_dims  # numpy-only leaf

    out = []
    for i, A in enumerate(As):
        kind = kinds[i] if kinds is not None else None
        if kind == "torus":
            rows, cols = torus_dims(np.asarray(A).shape[0])
            out.append(torus_schedule(rows, cols, A))
        else:
            out.append(graph_schedule(A))
    return tuple(out)


def graph_combine_switch(
    x, axis_name: str, scheds: Sequence[GraphSchedule], t
) -> Array:
    """Time-varying synchronous gossip: apply combiner A_{t mod P} where
    `scheds` holds the P pre-compiled schedules of one period and `t` is the
    (traced) iteration index.

    Every branch is traced once at compile time with its own static ppermute
    permutations; `lax.switch` picks the active one at run time, so the whole
    time-varying run is ONE compiled program.  `t` must be replicated across
    the axis (it always is: it comes from the scan counter), otherwise ranks
    would disagree about which collective to issue.

    The period selector uses `lax.rem` (valid because t >= 0 always: it is a
    scan counter seeded at t0 >= 0) so the switch index stays a single
    readable `rem` equation in the jaxpr — tools/analyze reads the period
    off it when attributing wire bytes to branches.
    """
    if len(scheds) == 1:
        return graph_combine(x, axis_name, scheds[0])
    branches = [
        (lambda v, s=s: graph_combine(v, axis_name, s)) for s in scheds
    ]
    return jax.lax.switch(
        jax.lax.rem(t, jnp.int32(len(scheds))), branches, x
    )


def graph_combine_quantized_switch(
    x_self: Array,
    q: Array,
    s: Array,
    axis_name: str,
    scheds: Sequence[GraphSchedule],
    t,
) -> Array:
    """`graph_combine_switch` over the int8 wire format: the caller
    quantizes its outgoing message once as (q, s) = quantize_q8(...), and the
    active schedule (index t mod P, via lax.switch) ships (int8 payload,
    scales) on each of its rounds.  Error feedback stays with the caller,
    exactly as in graph_combine_quantized / ring_q8.  Selector uses
    `lax.rem` for the same jaxpr-readability reason as
    graph_combine_switch (t >= 0 always)."""
    if len(scheds) == 1:
        return graph_combine_quantized(x_self, q, s, axis_name, scheds[0])
    branches = [
        (lambda op, sch=sch: graph_combine_quantized(
            op[0], op[1], op[2], axis_name, sch))
        for sch in scheds
    ]
    return jax.lax.switch(
        jax.lax.rem(t, jnp.int32(len(scheds))), branches, (x_self, q, s)
    )


def graph_combine_quantized(
    x_self: Array, q: Array, s: Array, axis_name: str, sched: GraphSchedule
) -> Array:
    """graph_combine over the int8 wire format: the caller quantizes its
    outgoing message ONCE (q, s) = quantize_q8(...); each schedule round
    ships (int8 payload, scales) and dequantizes on receipt.  The self term
    uses the full-precision x_self (error feedback stays with the caller,
    exactly as in the ring_q8 mode)."""
    out = _rank_weight(sched.diag, axis_name).astype(x_self.dtype) * x_self
    for perm, weights in sched.steps:
        ql = jax.lax.ppermute(q, axis_name, list(perm))
        sl = jax.lax.ppermute(s, axis_name, list(perm))
        w = _rank_weight(weights, axis_name)
        out = out + w.astype(x_self.dtype) * dequantize_q8(ql, sl, x_self.dtype)
    return out


# ---------------------------------------------------------------------------
# Push-sum (ratio-consensus) gossip: a second scalar weight channel rides
# the wire next to psi, and the caller divides by it — which relaxes the
# combiner requirement from doubly stochastic to ROW stochastic (mass
# conservation only), unlocking directed combiners (Daneshmand et al.,
# time-varying digraphs; Kempe-Dobra-Gehrke push-sum)
# ---------------------------------------------------------------------------


def push_graph_combine(
    x: Array, w: Array, axis_name: str, sched: GraphSchedule
) -> Tuple[Array, Array]:
    """One push-sum gossip round: ship (w * x, w) through the schedule.

    `w` is this rank's scalar push-sum weight (initialized to 1.0 at the
    start of a solve).  Returns (v_new, w_new) = (A^T (w x), A^T w); the
    caller's dual estimate is the RATIO v_new / w_new, which is what
    corrects the mass drift a merely-row-stochastic A introduces.  When A
    is doubly stochastic, column sums are 1 so w stays identically 1 and
    the ratio reduces EXACTLY to the plain diffusion combine — the parity
    invariant the push tests pin.

    Both channels ride the SAME ppermute rounds (one pytree through
    `graph_combine`), so the weight channel can never desynchronize from
    the payload — tools/analyze's push-weight-pairing rule proves this
    pairing on the compiled jaxpr.
    """
    v = w.astype(x.dtype) * x
    return graph_combine((v, w), axis_name, sched)


def push_graph_combine_quantized(
    v_self: Array, q: Array, s: Array, w: Array, axis_name: str,
    sched: GraphSchedule,
) -> Tuple[Array, Array]:
    """`push_graph_combine` over the int8 wire format.

    The caller forms v = w * psi, quantizes it ONCE with error feedback
    ((q, s) = quantize_q8(v + err)), and passes the full-precision v as
    `v_self` for the self term — exactly the graph_combine_quantized
    contract, applied in the v = w * psi coordinates where push-sum's
    linearity lives.  The scalar weight channel ships full precision (it
    is 4 bytes; quantizing the DIVISOR would amplify the payload's
    quantization error).  Returns (v_new, w_new).
    """
    out = _rank_weight(sched.diag, axis_name).astype(v_self.dtype) * v_self
    w_out = _rank_weight(sched.diag, axis_name).astype(w.dtype) * w
    for perm, weights in sched.steps:
        ql = jax.lax.ppermute(q, axis_name, list(perm))
        sl = jax.lax.ppermute(s, axis_name, list(perm))
        wl = jax.lax.ppermute(w, axis_name, list(perm))
        wt = _rank_weight(weights, axis_name)
        out = out + wt.astype(v_self.dtype) * dequantize_q8(ql, sl, v_self.dtype)
        w_out = w_out + wt.astype(w.dtype) * wl
    return out, w_out


# ---------------------------------------------------------------------------
# Hierarchical N-level gossip: the Kronecker chain A_{L-1} (x) ... (x) A_0
# realized as one GraphSchedule per level, applied innermost-first inside a
# single shard_map body (core/topology.KroneckerChain)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LevelPlan:
    """One compiled level of a `ChainSchedule` — the runtime half of a
    `core/topology.LevelSpec`.

    Fields:
      axis          mesh axis name this level's ppermutes run over
      sched         the level's compiled `GraphSchedule`
      gossip_every  fire the hop only at iterations t % gossip_every == 0
      quantized     ship this level's messages in the int8 wire format
                    (q8 + per-row scales, error feedback kept in the chain
                    state)
      stale         combine with the messages shipped at the PREVIOUS
                    firing iteration (graph_async style; outermost level
                    only — validated by the topology layer)
    """

    axis: str
    sched: GraphSchedule
    gossip_every: int = 1
    quantized: bool = False
    stale: bool = False

    @property
    def messages_per_iter(self) -> float:
        """ppermute rounds per iteration on this level, AVERAGED over the
        gossip stride (the hop only fires every gossip_every-th step)."""
        return self.sched.messages_per_iter / self.gossip_every


@dataclasses.dataclass(frozen=True)
class ChainSchedule:
    """Static N-level data-movement plan for the Kronecker-chain combine
    nu = (A_{L-1} (x) ... (x) A_0)^T psi.

    `levels` is INNERMOST-FIRST (level 0 = model level): because the
    Kronecker combine factorizes, running each level's schedule over its
    own mesh axis back-to-back inside one shard_map body realizes the full
    composition; each level is independently gated on its own stride.
    """

    levels: Tuple[LevelPlan, ...]

    @property
    def period(self) -> int:
        """LCM of the per-level gossip strides — iterations before the
        gating pattern repeats."""
        return math.lcm(*(lvl.gossip_every for lvl in self.levels))

    def reconstruct(self) -> np.ndarray:
        """Dense all-hops-firing combiner this schedule realizes
        (host-side; tests/benchmarks)."""
        acc = self.levels[0].sched.reconstruct()
        for lvl in self.levels[1:]:
            acc = np.kron(lvl.sched.reconstruct(), acc)
        return acc

    @property
    def messages_per_iter_per_level(self) -> Tuple[float, ...]:
        """Per-level ppermute rounds per iteration, stride-averaged —
        innermost-first (the per-level wire-byte accounting the gossip
        benchmarks report)."""
        return tuple(lvl.messages_per_iter for lvl in self.levels)


def chain_schedule(chain, axes: Sequence[str]) -> ChainSchedule:
    """Compile a `core/topology.KroneckerChain` into a `ChainSchedule`.

    `axes` names the mesh axis of each level, innermost-first (same order
    as `chain.specs`).  Each factor is compiled independently
    (`graph_schedule`; a level whose kind is "torus" takes the 4-link 2-D
    ICI `torus_schedule` instead), and the level's stride / wire format /
    staleness ride into the `LevelPlan`.
    """
    from repro.core.topology import torus_dims  # numpy-only leaf

    axes = tuple(axes)
    if len(axes) != len(chain.specs):
        raise ValueError(
            f"chain has {len(chain.specs)} levels but got {len(axes)} axis "
            f"names"
        )
    levels = []
    for spec, A, axis in zip(chain.specs, chain.combiners, axes):
        if spec.kind == "torus":
            rows, cols = torus_dims(np.asarray(A).shape[0])
            sched = torus_schedule(rows, cols, A)
        else:
            sched = graph_schedule(A)
        levels.append(LevelPlan(
            axis=axis, sched=sched, gossip_every=spec.gossip_every,
            quantized=(spec.wire == "q8"), stale=spec.stale,
        ))
    return ChainSchedule(levels=tuple(levels))


def wire_bytes_per_level(
    cs: ChainSchedule, b_loc: int, m: int
) -> Tuple[float, ...]:
    """Stride-averaged wire bytes per iteration on each level of `cs`,
    innermost-first, for a (b_loc, m) per-device code block.

    One fp32 message is `4 * b_loc * m` bytes; one q8 message is
    `b_loc * (m + 4)` (int8 payload plus one fp32 scale per row).  Each
    level ships `messages_per_iter` messages (already divided by its
    gossip stride).  This is the SINGLE source of truth for per-level
    byte accounting: `DistributedSparseCoder.wire_bytes_per_iter`, the
    gossip benchmarks, and the tools/analyze jaxpr byte cross-check all
    call it rather than re-deriving the formula."""
    out = []
    for lvl in cs.levels:
        msg = b_loc * (m + 4) if lvl.quantized else 4 * b_loc * m
        out.append(lvl.messages_per_iter * msg)
    return tuple(out)


def chain_state_init(x: Array, cs: ChainSchedule) -> Tuple:
    """Initial per-level carry state for `chain_combine`: one (err, recv)
    pair per level.  `err` is the q8 error-feedback accumulator
    (zeros_like(x) for quantized levels, () otherwise); `recv` holds the
    messages shipped at the previous firing iteration for stale levels
    (one zeros_like(x) per schedule round — the first stale combine sees
    zero neighbor contributions, exactly like graph_async's first step;
    () for synchronous levels)."""
    state = []
    for lvl in cs.levels:
        err = jnp.zeros_like(x) if lvl.quantized else ()
        recv = (tuple(jnp.zeros_like(x) for _ in lvl.sched.steps)
                if lvl.stale else ())
        state.append((err, recv))
    return tuple(state)


def _level_apply(v: Array, lvl: LevelPlan, t, err, recv_prev):
    """One level's gated hop: ship v's messages (fp32 or q8+error-feedback
    per the level's wire format), combine with this round's messages — or
    the PREVIOUS firing round's for a stale level — and return
    (combined, new_err, new_recv).  Skipped iterations (t % gossip_every
    != 0) pass everything through unchanged via lax.cond; both branches
    share one pytree structure, so the gated run stays one program.  The
    gate uses `lax.rem` (t >= 0 always — scan counter) so the stride is a
    single readable `rem` equation in the jaxpr for tools/analyze."""

    def fire(op):
        u, e, r_prev = op
        if lvl.quantized:
            q, s = quantize_q8(u + e)
            e_next = (u + e) - dequantize_q8(q, s)
            recv = tuple(
                dequantize_q8(
                    jax.lax.ppermute(q, lvl.axis, list(perm)),
                    jax.lax.ppermute(s, lvl.axis, list(perm)),
                    u.dtype,
                )
                for perm, _ in lvl.sched.steps
            )
        else:
            e_next = e
            recv = graph_shift(u, lvl.axis, lvl.sched)
        out = graph_accumulate(u, r_prev if lvl.stale else recv,
                               lvl.axis, lvl.sched)
        return out, e_next, (recv if lvl.stale else ())

    if lvl.gossip_every == 1:
        return fire((v, err, recv_prev))
    return jax.lax.cond(
        jnp.equal(jax.lax.rem(t, jnp.int32(lvl.gossip_every)), 0),
        fire, lambda op: op, (v, err, recv_prev),
    )


def chain_combine(x: Array, cs: ChainSchedule, t, state: Tuple):
    """N-level synchronous/stale gossip: apply every level of the chain
    innermost-first, each hop gated on its own stride by the (traced)
    iteration index `t`.

    `state` is the per-level (err, recv) carry from `chain_state_init` /
    the previous call; returns (combined, new_state).  Quantized levels
    update their error-feedback accumulator only on firing iterations;
    stale levels combine with the messages shipped at the PREVIOUS firing
    iteration and stash this round's sends in the state (`t` must be
    replicated across all agent axes; it comes from the scan counter, so
    it always is)."""
    out = x
    new_state = []
    for lvl, (err, recv_prev) in zip(cs.levels, state):
        out, err_next, recv_next = _level_apply(out, lvl, t, err, recv_prev)
        new_state.append((err_next, recv_next))
    return out, tuple(new_state)


# ---------------------------------------------------------------------------
# Hierarchical (two-level) gossip: the Kronecker combiner A_pod (x) A_model —
# the stable two-level surface of the hier/hier_q8 modes, implemented as a
# two-level ChainSchedule (core/topology.HierarchicalTopology)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class HierSchedule:
    """Static two-level data-movement plan for nu = (A_pod (x) A_model)^T psi.

    `model` is the intra-pod ppermute schedule (over the model axis, within
    each pod) and `pod` the inter-pod schedule (over the pod axis); because
    the Kronecker combine factorizes — (A (x) B)^T psi = apply B^T over the
    model axis, then A^T over the pod axis — running the two schedules
    back-to-back inside one shard_map body realizes the full composition.
    `gossip_every` = k fires the pod schedule only at iterations t with
    t % k == 0 (the sparse-communication trick for slow inter-pod links).
    """

    model: GraphSchedule
    pod: GraphSchedule
    gossip_every: int = 1

    def reconstruct(self) -> np.ndarray:
        """Dense A_pod (x) A_model this schedule realizes on a pod-hop
        iteration (host-side; tests/benchmarks)."""
        return np.kron(self.pod.reconstruct(), self.model.reconstruct())

    @property
    def model_messages_per_iter(self) -> int:
        """Intra-pod ppermute rounds per iteration (every iteration)."""
        return self.model.messages_per_iter

    @property
    def pod_messages_per_iter(self) -> float:
        """Inter-pod ppermute rounds per iteration, AVERAGED over the
        gossip_every period (the hop only fires every k-th iteration)."""
        return self.pod.messages_per_iter / self.gossip_every

    def as_chain(self, model_axis: str, pod_axis: str, *,
                 quantized_pod: bool = False,
                 stale_pod: bool = False) -> ChainSchedule:
        """The equivalent two-level `ChainSchedule` (model level innermost,
        pod level carrying this schedule's gossip stride).  `hier_combine`
        and `hier_combine_quantized` run THROUGH this chain — the two-level
        path and the N-level path are one implementation."""
        return ChainSchedule(levels=(
            LevelPlan(axis=model_axis, sched=self.model),
            LevelPlan(axis=pod_axis, sched=self.pod,
                      gossip_every=self.gossip_every,
                      quantized=quantized_pod, stale=stale_pod),
        ))


def hier_schedule(
    A_pod: np.ndarray,
    A_model: np.ndarray,
    *,
    pod_kind: Optional[str] = None,
    model_kind: Optional[str] = None,
    gossip_every: int = 1,
) -> HierSchedule:
    """Compile a two-level combiner pair into a `HierSchedule`.

    Each factor is compiled independently (`graph_schedule`; a factor whose
    kind is "torus" takes the 4-link 2-D ICI `torus_schedule` instead), so
    an intra-pod torus keeps nearest-neighbor data movement while the
    inter-pod factor pays only its own edge-offsets on the long-haul link.
    """
    from repro.core.topology import torus_dims  # numpy-only leaf

    if gossip_every < 1:
        raise ValueError(f"gossip_every must be >= 1, got {gossip_every}")

    def compile_one(A: np.ndarray, kind: Optional[str]) -> GraphSchedule:
        if kind == "torus":
            rows, cols = torus_dims(np.asarray(A).shape[0])
            return torus_schedule(rows, cols, A)
        return graph_schedule(A)

    return HierSchedule(
        model=compile_one(A_model, model_kind),
        pod=compile_one(A_pod, pod_kind),
        gossip_every=int(gossip_every),
    )


def hier_combine(x, model_axis: str, pod_axis: str, hs: HierSchedule, t=0):
    """Two-level synchronous gossip: nu = (A_pod (x) A_model)^T psi, as the
    intra-pod combine over `model_axis` followed by the inter-pod combine
    over `pod_axis` in the same program.

    With gossip_every > 1 the pod hop is gated on the (traced) iteration
    index `t` via lax.cond — both branches are traced once with their own
    static ppermutes, so the whole gated run stays ONE compiled program
    (`t` must be replicated across both axes; it comes from the scan
    counter, so it always is).  Thin wrapper over `chain_combine` on the
    equivalent two-level chain (no per-call state: fp32 levels carry
    none)."""
    cs = hs.as_chain(model_axis, pod_axis)
    out, _ = chain_combine(x, cs, t, chain_state_init(x, cs))
    return out


def hier_combine_quantized(
    x: Array, err: Array, model_axis: str, pod_axis: str, hs: HierSchedule, t=0
) -> Tuple[Array, Array]:
    """`hier_combine` with the int8 wire format on the INTER-POD hop only.

    The intra-pod combine ships full-precision messages (local ICI links
    are cheap); the combined intra-pod value is then quantized ONCE with
    error feedback `err` and shipped as (int8 payload, scales) on each
    inter-pod round — that hop is the bandwidth-constrained link the q8
    format exists for.  Returns (combined, new_err); on iterations where
    the pod hop does not fire (t % gossip_every != 0) nothing is quantized
    and `err` rides through unchanged.  Thin wrapper over `chain_combine`
    on the equivalent two-level chain with a quantized pod level."""
    cs = hs.as_chain(model_axis, pod_axis, quantized_pod=True)
    out, new_state = chain_combine(x, cs, t, (((), ()), (err, ())))
    return out, new_state[1][0]


def all_to_all_tiled(x: Array, axis_name: str) -> Array:
    """Tiled all_to_all over the leading dim (expert-parallel dispatch)."""
    return jax.lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0, tiled=True)


def all_gather_tiled(x: Array, axis_name: str, axis: int = 0) -> Array:
    """Tiled all_gather along `axis` (the FSDP weight gather)."""
    return jax.lax.all_gather(x, axis_name, axis=axis, tiled=True)


def psum_scatter_tiled(x: Array, axis_name: str, axis: int = 0) -> Array:
    """Tiled reduce-scatter along `axis` (transpose of all_gather_tiled)."""
    return jax.lax.psum_scatter(x, axis_name, scatter_dimension=axis, tiled=True)


# ---------------------------------------------------------------------------
# int8 wire format (ring_q8 gossip, q8 MoE collectives)
# ---------------------------------------------------------------------------


def quantize_q8(
    x: Array, axis: int = -1, scale_dtype: Optional[jnp.dtype] = None
) -> Tuple[Array, Array]:
    """Symmetric per-slice int8 quantization along `axis`; returns
    (q int8, scale).  `scale_dtype` defaults to x.dtype; the MoE wire path
    passes float16 to halve the scale payload."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0 + 1e-30
    if scale_dtype is not None:
        scale = scale.astype(scale_dtype)
    q = jnp.clip(jnp.round(x / scale.astype(x.dtype)), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_q8(q: Array, scale: Array, dtype: Optional[jnp.dtype] = None) -> Array:
    """Inverse of `quantize_q8`: q (int8) * scale, in `dtype` (defaults to
    the scale's dtype) — applied on receipt of every q8 wire message."""
    out_dtype = dtype if dtype is not None else scale.dtype
    return q.astype(out_dtype) * scale.astype(out_dtype)
