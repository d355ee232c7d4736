"""Production multi-device engine for model-distributed dictionary learning.

This is the TPU-native realization of the paper's protocol (DESIGN.md §2):

  * the "network of agents" becomes the `model` axis of a device mesh —
    device r on that axis *is* agent r and owns the atom shard W_r;
  * the sample batch is sharded along the `data` (and `pod`) axes — the
    dual problems are independent per sample, so batching is exact;
  * the gossip combine  nu_k = sum_l a_{lk} psi_l  becomes `lax.ppermute`
    exchanges with ring neighbors (constant-weight ring combiner, doubly
    stochastic), or a single `lax.psum` in the exact/fully-connected mode;
  * the dictionary update (paper Eq. 51) stays fully local in the atom
    dimension — its only cross-device traffic is the minibatch-mean over
    the data axis, the standard DP gradient reduction.

Modes (gossip schedules):
  exact       one psum of the (B_loc, M) back-projection per iteration;
              identical iterates to the centralized projected gradient
              (fully-connected A = 11^T/N applied every step).
  exact_fista exact + Nesterov momentum on the strongly-convex dual
              (beyond-paper; geometric sqrt(kappa) rate).
  ring        faithful diffusion: ppermute psi to the two ring neighbors,
              combine with [beta, 1-2beta, beta] weights.
  ring_q8     ring with int8-quantized messages + error feedback
              (beyond-paper; 4x collective-byte reduction).
  ring_async  ring with one-step-stale neighbor messages — the combine at
              iteration i uses psi_{i-1} from the neighbors, which lets the
              ppermute of psi_i overlap with computing psi_{i+1}
              (beyond-paper; straggler/latency hiding).
  graph       faithful diffusion under ANY doubly-stochastic combiner from
              core/topology.make_topology (DistConfig.topology picks the
              kind: "ring_metropolis", "torus", "erdos", ... — the paper's
              Sec. IV-B connected-random-graph regime).  The combiner is
              compiled once into a static per-neighbor ppermute schedule
              (runtime/dist.graph_schedule; torus combiners get the 4-link
              2-D ICI schedule from torus_schedule).
  graph_q8    graph with int8-quantized messages + error feedback over the
              same wire format as ring_q8.
  graph_async graph with one-step-stale neighbor messages (the received
              per-round messages ride the scan carry).
  graph_tv    diffusion under a TIME-VARYING combiner sequence A_0, A_1, ...
              (core/topology.TopologySchedule, selected by
              DistConfig.topology_schedule) — the regime of Daneshmand et
              al. (arXiv:1612.07335 / arXiv:1808.05933) where the network
              changes every iteration.  Each A_t is pre-compiled to its own
              ppermute schedule; inside the scanned gossip loop the active
              schedule is picked by the traced iteration index via
              lax.switch, so the whole time-varying run stays ONE compiled
              program.  solve/fit accept a schedule offset t0 so a serving
              stream can keep advancing the network across micro-batches.
  graph_tv_q8 graph_tv over the int8 wire format (one quantization per
              iteration + error feedback, same as ring_q8/graph_q8).
              Both graph_tv modes accept DistConfig.failure_p > 0: the
              schedule is then wrapped in `topology.link_failure_schedule`
              — a seeded per-step Bernoulli link-dropout realization with
              Metropolis renormalization, compiled through the SAME
              lax.switch machinery (a failure trace is still one program).
  push        push-sum (ratio-consensus) diffusion: each agent carries a
              scalar weight w (w0 = 1) next to nu; per iteration the pair
              (w*psi, w) ships through the combiner schedule and the dual
              update divides by the combined weight.  Mass conservation
              then only needs A ROW stochastic, so DistConfig.topology may
              also name a DIRECTED kind ("dicycle", "distar") — the
              digraph regime of Daneshmand et al.  With a doubly-
              stochastic A, w stays identically 1 and the iterates equal
              mode="graph" exactly.
  push_q8     push with the int8 wire format on the payload channel (in
              the v = w*psi coordinates, error feedback as in graph_q8);
              the scalar weight channel stays fp32.
  chain       HIERARCHICAL (N-level, graph-of-graphs) diffusion for
              multi-hop meshes: the network of agents is the device grid
              of every level axis (outermost-major) and the combiner is
              the Kronecker chain A_{L-1} (x) ... (x) A_0 described by
              DistConfig.levels — a list of `core/topology.LevelSpec`s,
              INNERMOST (model) level first, each carrying its own
              combiner kind, gossip stride, wire format (fp32 / q8 with
              error feedback), and optionally one-step staleness on the
              OUTERMOST hop (graph_async style, hiding long-haul
              latency).  Every level compiles to its own ppermute
              schedule and they run back-to-back inside one shard_map
              body (runtime/dist.chain_combine), each hop gated on its
              own stride by the traced iteration index (lax.cond — one
              compiled program); the dictionary is atom-sharded over ALL
              level axes (outermost-major) and the globally safe adaptive
              mu is pmax'd over all of them.
  hier        the two-level special case of `chain`, kept as the stable
              multi-pod surface: DistConfig.topology picks the dense
              INTRA-POD kind over the model axis, DistConfig.pod_topology
              the sparse INTER-POD kind over the pod axis, and
              DistConfig.pod_gossip_every > 1 fires the inter-pod hop
              only every k-th iteration.  Runs THROUGH the chain solver
              on the equivalent two-level `DistConfig.chain_levels()`.
  hier_q8     hier with the int8 wire format on the INTER-POD hop only
              (the bandwidth-constrained link); intra-pod messages stay
              full precision.  Error feedback as in ring_q8, updated only
              on iterations where the pod hop fires.

Every mode returns per-device (nu, y) with nu converged to the same global
optimum the reference engine (core/inference.py) computes.  Mode
capabilities (which modes quantize, vary in time, span multiple axes, or
combine stale messages) live in ONE place — `MODE_REGISTRY` — consumed by
`DistConfig.__post_init__` validation, the solver dispatch, and
`combiner_info()`, so adding a mode means adding one registry row.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import threading
import weakref
from typing import Deque, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import topology as topo
from repro.core.conjugates import Regularizer, Residual
from repro.core.dictionary import init_dictionary
from repro.core.inference import power_sigma2
from repro.runtime import dist
from repro.runtime.dist import shard_map

Array = jax.Array

@dataclasses.dataclass(frozen=True)
class ModeCaps:
    """One row of the mode registry: the capability flags of a gossip mode.

    `family` names the solver branch ("exact" | "ring" | "graph" | "tv" |
    "chain"); the flags say whether the mode quantizes its wire messages,
    runs a time-varying combiner sequence, spans multiple agent axes
    (hierarchical), or combines one-step-stale messages.  Validation,
    dispatch, and reporting all read THESE flags instead of
    pattern-matching mode strings."""

    family: str
    quantized: bool = False
    time_varying: bool = False
    hierarchical: bool = False
    stale: bool = False


# THE mode table: every mode the engine accepts, with its capabilities.
# Adding a gossip mode = adding one row here (plus, for a new family, one
# solver branch keyed on caps.family).
MODE_REGISTRY = {
    "exact": ModeCaps(family="exact"),
    "exact_fista": ModeCaps(family="exact"),
    "ring": ModeCaps(family="ring"),
    "ring_q8": ModeCaps(family="ring", quantized=True),
    "ring_async": ModeCaps(family="ring", stale=True),
    "graph": ModeCaps(family="graph"),
    "graph_q8": ModeCaps(family="graph", quantized=True),
    "graph_async": ModeCaps(family="graph", stale=True),
    "graph_tv": ModeCaps(family="tv", time_varying=True),
    "graph_tv_q8": ModeCaps(family="tv", quantized=True, time_varying=True),
    "push": ModeCaps(family="push"),
    "push_q8": ModeCaps(family="push", quantized=True),
    "hier": ModeCaps(family="chain", hierarchical=True),
    "hier_q8": ModeCaps(family="chain", quantized=True, hierarchical=True),
    "chain": ModeCaps(family="chain", hierarchical=True),
}

# Derived mode groups (kept as public names — tests, benchmarks, and docs
# enumerate them).  HIER_MODES is the two-level deprecation shim; the
# N-level "chain" mode shares its family but takes DistConfig.levels.
RING_MODES = tuple(m for m, c in MODE_REGISTRY.items() if c.family == "ring")
GRAPH_MODES = tuple(m for m, c in MODE_REGISTRY.items() if c.family == "graph")
TV_MODES = tuple(m for m, c in MODE_REGISTRY.items() if c.family == "tv")
PUSH_MODES = tuple(m for m, c in MODE_REGISTRY.items() if c.family == "push")
HIER_MODES = ("hier", "hier_q8")
CHAIN_MODES = tuple(m for m, c in MODE_REGISTRY.items() if c.family == "chain")
MODES = tuple(MODE_REGISTRY)


@dataclasses.dataclass(frozen=True)
class DistConfig:
    """Configuration for the multi-device dual solver.

    Field reference (shapes are per the engine's layout: the dictionary W is
    (M, K) atom-sharded over `model_axis`, the batch x is (B, M) sharded over
    `data_axes`):

      mode             gossip schedule, one of MODES (see the module
                       docstring for the collective each maps to).
      iters            dual diffusion/gradient iterations per solve
                       (paper Eq. 31: more iterations = tighter consensus).
      mu               dual step size; <= 0 selects the curvature-adaptive
                       globally-safe step (pmax'd over the model axis, the
                       distributed `safe_diffusion_mu`).
      beta             ring combiner weight [beta, 1-2*beta, beta]
                       (doubly stochastic iff beta in [0, 1/2]).
      topology         static graph-mode combiner kind — any
                       `core/topology.make_topology` kind
                       ("ring_metropolis" | "torus" | "erdos" | ...).
      topology_p       erdos edge probability (static and time-varying).
      topology_seed    seed of every seeded topology draw: the static erdos
                       graph, and the whole time-varying sequence (same seed
                       => identical combiner sequence, also across grown()).
      topology_schedule  time-varying modes only: the
                       `core/topology.make_topology_schedule` spec —
                       "fixed:<kind>", "alternating:<k1>,<k2>,...", or
                       "erdos_resampled".  "" / "fixed" degenerate to the
                       static `topology` kind wrapped in a period-1 schedule.
                       None with a time-varying mode is rejected at
                       construction (there is no sequence to run).
      schedule_period  period of the "erdos_resampled" spec (number of
                       distinct graphs before the sequence repeats).
      failure_p        time-varying modes only: per-step, per-edge link
                       dropout probability in [0, 1).  > 0 wraps the
                       schedule in `core/topology.link_failure_schedule`
                       (seeded Bernoulli realizations, Metropolis-
                       renormalized per step so every realized A_t stays
                       doubly stochastic).  Correctness under failures is
                       gated on the realization's WINDOWED mixing rate.
      failure_seed     seed of the per-step failure draws (independent of
                       topology_seed: the same network can replay
                       different failure traces).
      failure_steps    number of distinct failure realizations before the
                       trace repeats (the realized schedule period).
                       0 = the base schedule's own period; raise it so a
                       short-period base network does not replay the same
                       dropped links forever.
      pod_topology     hier modes only: the INTER-POD combiner kind over
                       the pod axis (any `make_topology` kind; typically a
                       sparse one — the pod links are the slow long-haul
                       hop).  REQUIRED for the hier modes: "" is rejected
                       at construction.  `topology` picks the dense
                       intra-pod kind, so the two-level combiner is
                       A_pod(pod_topology) (x) A_model(topology).
      pod_gossip_every hier modes: fire the inter-pod hop only every k-th
                       diffusion iteration (1 = every iteration).  The
                       per-iteration combiner sequence has period k
                       (A_pod (x) A_model alternating with I (x) A_model),
                       which is how the reference parity models it.
      levels           mode="chain" only: the N-level Kronecker-chain spec,
                       a sequence of `core/topology.LevelSpec`s INNERMOST
                       (model) level first — each level carries its own
                       combiner kind, gossip stride, wire format, optional
                       staleness (outermost level only), and optionally an
                       explicit mesh axis name (default: level 0 ->
                       model_axis, level 1 -> pod_axis, level i >= 2 ->
                       "<pod_axis><i>").  A spec STRING is also accepted
                       and parsed with `core/topology.parse_level_specs`
                       (e.g. "torus,ring_metropolis:2:q8,ring:4:q8").  The
                       hier modes ignore this field and shim their
                       (topology, pod_topology, pod_gossip_every) trio
                       onto a two-level chain — see `chain_levels()`.
      informed         "all" (every agent sees x) or "one" (only agent 0 —
                       global pod-major rank 0 in the hier modes — is
                       informed, the paper's |N_I| = 1 regime).
      model_axis       mesh axis name the agents/atom shards live on.
      data_axes        mesh axes the sample batch is sharded over.
      pod_axis         mesh axis name of the inter-pod hop (hier modes).
      use_kernel       fuse the local hot loop with the Pallas
                       dict_dual_step kernel (interpret mode follows the
                       backend: `repro.kernels.interpret_mode`).
    """

    mode: str = "exact_fista"  # see MODES
    iters: int = 100
    mu: float = -1.0  # <= 0 -> curvature-adaptive (safe) step
    beta: float = 1.0 / 3.0  # ring combiner weight, admissible range [0, 1/2]
    # graph-mode combiner: any core/topology.make_topology kind.
    topology: str = "ring_metropolis"  # ring_metropolis | torus | erdos | ...
    topology_p: float = 0.5  # erdos edge probability
    topology_seed: int = 0  # erdos graph / schedule sequence seed
    # time-varying modes: core/topology.make_topology_schedule spec + period.
    topology_schedule: str = "alternating:ring_metropolis,torus"
    schedule_period: int = 2  # erdos_resampled period
    # link-failure injection (time-varying modes): per-edge drop probability,
    # failure-stream seed, and realized-trace period (0 = base period).
    failure_p: float = 0.0
    failure_seed: int = 0
    failure_steps: int = 0
    # hier modes: inter-pod combiner kind (required) + sparse-gossip stride.
    pod_topology: str = ""  # e.g. "ring_metropolis"; "" = not configured
    pod_gossip_every: int = 1  # inter-pod hop every k iterations
    # chain mode: N-level spec list (LevelSpecs or a parse_level_specs string)
    levels: Tuple[topo.LevelSpec, ...] = ()
    informed: str = "all"  # "all" | "one" (only model-rank 0 sees x)
    model_axis: str = dist.MODEL_AXIS
    data_axes: Tuple[str, ...] = (dist.DATA_AXIS,)
    pod_axis: str = dist.POD_AXIS  # inter-pod gossip axis (hier modes)
    use_kernel: bool = False  # fuse local hot loop with the Pallas kernel

    def __post_init__(self):
        """Construction-time validation of cross-field requirements.

        Misconfigurations that would otherwise only surface deep inside
        schedule compilation (or, worse, inside a traced shard_map body)
        fail HERE with an actionable message (each requirement read off
        the mode's `MODE_REGISTRY` capability row, not a mode-string
        pattern): a time-varying mode needs a schedule spec, the hier shim
        modes need an inter-pod combiner kind, mode="chain" needs a level
        list, and the inter-pod gossip stride must be a positive count.
        `levels` given as a spec string is parsed here
        (`topology.parse_level_specs`); as a sequence it is normalized to
        a tuple.
        """
        if isinstance(self.levels, str):
            # "" means "not configured" (the CLI default), not a 1-level
            # chain with an empty kind.
            object.__setattr__(
                self, "levels",
                topo.parse_level_specs(self.levels) if self.levels else (),
            )
        else:
            object.__setattr__(self, "levels", tuple(self.levels))
        caps = MODE_REGISTRY.get(self.mode)
        if caps is not None and caps.time_varying \
                and self.topology_schedule is None:
            raise ValueError(
                f"mode={self.mode!r} needs a combiner sequence but "
                f"topology_schedule is None; pass a "
                f"make_topology_schedule spec ('fixed:<kind>', "
                f"'alternating:<k1>,<k2>,...', or 'erdos_resampled') — or "
                f"'' to degenerate to the static `topology` kind"
            )
        if self.mode in HIER_MODES and not self.pod_topology:
            raise ValueError(
                f"mode={self.mode!r} composes an inter-pod combiner with "
                f"the intra-pod one but pod_topology is not set; pass a "
                f"core/topology.make_topology kind (e.g. "
                f"pod_topology='ring_metropolis') for the pod axis"
            )
        if self.mode == "chain" and not self.levels:
            raise ValueError(
                "mode='chain' runs an N-level Kronecker chain but levels is "
                "empty; pass levels=[LevelSpec(...), ...] (innermost/model "
                "level first) or a parse_level_specs string like "
                "'torus,ring_metropolis:2:q8,ring:4:q8'"
            )
        if self.levels and self.mode != "chain":
            raise ValueError(
                f"levels is only consumed by mode='chain' (got "
                f"mode={self.mode!r}); the hier modes configure their "
                f"two-level chain via topology/pod_topology/"
                f"pod_gossip_every instead"
            )
        if self.pod_gossip_every < 1:
            raise ValueError(
                f"pod_gossip_every must be >= 1 (the inter-pod hop fires "
                f"every k-th iteration), got {self.pod_gossip_every}"
            )
        if not 0.0 <= self.failure_p < 1.0:
            raise ValueError(
                f"failure_p must be in [0, 1) (a per-edge dropout "
                f"probability; 1 would sever every link), got "
                f"{self.failure_p}"
            )
        if self.failure_p > 0 and (caps is None or not caps.time_varying):
            raise ValueError(
                f"failure_p > 0 injects a per-step failure REALIZATION "
                f"sequence, which only the time-varying family can run as "
                f"one program (got mode={self.mode!r}); use mode='graph_tv'"
                f"/'graph_tv_q8', e.g. with topology_schedule="
                f"'fixed:<kind>' to degrade a static network"
            )
        if self.failure_steps < 0:
            raise ValueError(
                f"failure_steps must be >= 0 (0 = the base schedule's own "
                f"period), got {self.failure_steps}"
            )

    def chain_levels(self) -> Tuple[topo.LevelSpec, ...]:
        """The effective Kronecker-chain level list, innermost-first.

        mode="chain" returns `levels` verbatim; the hier modes return the
        two-level DEPRECATION SHIM — model level from `topology`, pod
        level from `pod_topology` with the `pod_gossip_every` stride and
        the q8 wire for hier_q8 — so the legacy trio and a hand-built
        two-level `levels` config compile to bit-identical schedules.
        Flat modes return ()."""
        caps = MODE_REGISTRY.get(self.mode)
        if caps is None or not caps.hierarchical:
            return ()
        if self.mode == "chain":
            return self.levels
        return (
            topo.LevelSpec(kind=self.topology, axis=self.model_axis),
            topo.LevelSpec(
                kind=self.pod_topology,
                gossip_every=self.pod_gossip_every,
                wire="q8" if MODE_REGISTRY[self.mode].quantized else "fp32",
                axis=self.pod_axis,
            ),
        )

    def level_axis(self, i: int) -> str:
        """Mesh axis name of chain level i: the level's explicit `axis`
        when set, else the default naming — level 0 gossips over
        `model_axis`, level 1 over `pod_axis`, level i >= 2 over
        "<pod_axis><i>" (e.g. "pod2")."""
        specs = self.chain_levels()
        if specs and specs[i].axis:
            return specs[i].axis
        if i == 0:
            return self.model_axis
        if i == 1:
            return self.pod_axis
        return f"{self.pod_axis}{i}"


# ---------------------------------------------------------------------------
# int8 quantization with error feedback (ring_q8) — wire format shared with
# the runtime layer (runtime/dist.py)
# ---------------------------------------------------------------------------

_quantize_q8 = dist.quantize_q8
_dequantize_q8 = dist.dequantize_q8


# The engine's matmul precision, decided once: every program body is traced
# with f32 matmuls at HIGHEST.  A TPU computes an f32 matmul at DEFAULT
# precision as one bf16 pass (~3 significant digits), which would make the
# engine disagree with its f32 reference far beyond reduction-order noise
# and bias the step-size estimate; HIGHEST keeps the paper's f32 semantics.
# Lower-precision storage and matmuls are a separate, measured change.
MATMUL_PRECISION = "highest"


def _f32_matmuls(body):
    """`body` traced with the engine's matmul precision."""

    @functools.wraps(body)
    def traced(*args):
        with jax.default_matmul_precision(MATMUL_PRECISION):
            return body(*args)

    return traced


# ---------------------------------------------------------------------------
# The shard_map dual solver
# ---------------------------------------------------------------------------


def _local_code_and_back(
    res: Residual,
    reg: Regularizer,
    W_loc: Array,  # (M, K_loc)
    nu: Array,  # (B, M)
    cfg: DistConfig,
) -> Tuple[Array, Array]:
    """Per-agent hot loop: y = ystar(W^T nu), back = y W^T.  Optionally via
    the fused Pallas kernel (kernels/dict_dual_step)."""
    if cfg.use_kernel:
        from repro.kernels.dict_dual_step import ops as kops

        return kops.dict_dual_step(
            W_loc,
            nu,
            gamma=reg.gamma,
            delta=reg.delta,
            nonneg=reg.nonneg,
        )
    y = reg.ystar(nu @ W_loc)  # (B, K_loc)
    return y, y @ W_loc.T


def _safe_mu_local(res: Residual, reg: Regularizer, W_loc: Array, axis) -> Array:
    """Per-shard curvature bound -> globally-safe diffusion step (pmax'd).

    Every agent bounds its own local Lipschitz constant L_k <= c_f/N +
    sigma_max(W_k)^2/delta, then the max is reduced over the gossip
    axis/axes so ALL agents step with the one mu that is safe for the worst
    shard — the distributed equivalent of `safe_diffusion_mu` in
    core/inference.py (which maxes over blocks).  Without the reduction
    each device would use a step safe only for its own shard and the gossip
    iterates can diverge.  `axis` is the model axis name, or a (pod, model)
    tuple for the hierarchical modes whose agents span BOTH axes — the max
    (and the agent count N in the bound) then reduces over the whole
    two-level network.
    """
    c_f = res.grad_fstar(jnp.ones((1,), W_loc.dtype))[0]
    n_agents = jax.lax.psum(1, axis)
    sig2_max = jax.lax.pmax(power_sigma2(W_loc), axis)
    return 0.9 / (c_f / n_agents + sig2_max / reg.delta)


def _safe_mu_exact(res: Residual, reg: Regularizer, W_loc: Array, axis: str) -> Array:
    """1/L for the summed dual: L <= c_f + sigma_max(W)^2/delta; we bound
    sigma_max(W)^2 <= sum_k sigma_max(W_k)^2 (Frobenius-style, loose but safe
    and collective-cheap: one scalar psum)."""
    c_f = res.grad_fstar(jnp.ones((1,), W_loc.dtype))[0]
    sig2_sum = jax.lax.psum(power_sigma2(W_loc), axis)
    return 1.0 / (c_f + sig2_sum / reg.delta)


@dataclasses.dataclass(frozen=True)
class OutSpecInfo:
    """Replication contract of ONE shard_map output, machine-checkable.

    `spec` mirrors the PartitionSpec handed to shard_map (entries are
    None, an axis name, or a tuple of axis names).  Every mesh axis NOT
    mentioned in `spec` is declared replicated: the compiled program
    places the same bytes on every device along that axis, so the
    per-device body must provably produce a value that does not vary
    along it (tools/analyze rule: out-spec-replication).  The engine runs
    its shard_maps with check_vma=False, so XLA does NOT verify this —
    without the static proof, a forgotten psum/pmax silently ships
    device-dependent garbage as if it were replicated.

    `consensus=True` exempts the AGENT axes only: the output is an
    approximate-consensus estimate that intentionally differs per agent
    (nu/y leave the solve un-replicated along the agent axes — each
    agent holds its own estimate; that is the documented check_vma=False
    rationale, not a bug).  Non-agent axes are still checked.
    """

    name: str
    spec: Tuple
    consensus: bool = False


@dataclasses.dataclass(frozen=True)
class _Solved:
    """One solve's duals, with weak references to its W and x: the memo
    never keeps a dictionary or a batch alive."""

    W: weakref.ref
    x: weakref.ref
    t0: int
    nu: Array
    y: Array

    def matches(self, W, x, t0) -> bool:
        return self.W() is W and self.x() is x and self.t0 == t0


class DistributedSparseCoder:
    """Dual-domain sparse coder over an atom-sharded dictionary on a mesh.

    Usage:
        coder = DistributedSparseCoder(mesh, res, reg, cfg)
        nu, y = coder.solve(W, x)        # global arrays, jit-sharded
        W2    = coder.fit_batch(W, x, mu_w)  # one dictionary step
    """

    def __init__(
        self,
        mesh: Mesh,
        res: Residual,
        reg: Regularizer,
        cfg: DistConfig,
        grown_from: Optional["DistributedSparseCoder"] = None,
        shrunk_from: Optional[
            Tuple["DistributedSparseCoder", Tuple[int, ...]]
        ] = None,
    ):
        """Build the coder's combiner state and compile its mesh programs.

        `grown_from` is the elastic-growth hook (`grown()` passes the old
        coder): erdos-backed topologies — the static "erdos" kind, every
        erdos step of a time-varying schedule, and the erdos intra-pod
        factor of a hierarchical coder — are then GROWN from the old
        adjacency via `topology.erdos_renyi_grow` (existing agents keep
        their neighborhoods; only new-agent edges are sampled) instead of
        resampled wholesale.  Hierarchical coders additionally carry their
        inter-pod combiner verbatim (growth is model-axis only).

        `shrunk_from` is the drain hook (`shrunk()` passes (old_coder,
        survivors)): erdos-backed topologies are then RESTRICTED to the
        survivor-induced subgraph via `topology.shrink_adjacency`
        (survivors keep every edge among themselves, deterministic ring
        repair if departures disconnected the graph); structured kinds
        re-derive at the smaller size.  Mutually exclusive with
        `grown_from`.
        """
        if grown_from is not None and shrunk_from is not None:
            raise ValueError("grown_from and shrunk_from are mutually "
                             "exclusive construction hooks")
        if cfg.mode not in MODES:
            raise KeyError(f"unknown mode {cfg.mode!r}; options: {MODES}")
        if not 0.0 <= cfg.beta <= 0.5:
            # beta > 1/2 makes the self-weight 1-2*beta negative: A is no
            # longer doubly stochastic and the gossip iterates can diverge.
            raise ValueError(
                f"DistConfig.beta={cfg.beta} outside the admissible range "
                f"[0, 1/2]: the ring combiner [beta, 1-2*beta, beta] needs "
                f"beta <= 1/2 to keep all weights nonnegative"
            )
        self.mesh = mesh
        self.res = res
        self.reg = reg
        self.cfg = cfg
        ax = cfg.model_axis
        da = tuple(cfg.data_axes)
        # Graph modes: build the doubly-stochastic combiner(s) for this
        # mesh's model-axis size and compile each to a static ppermute
        # schedule.  A grown() coder re-runs this on the larger axis, so the
        # topology (or the whole time-varying sequence) is re-derived — not
        # padded — after elastic growth, with erdos neighborhoods preserved.
        self._A: Optional[np.ndarray] = None
        self._adj: Optional[np.ndarray] = None  # static erdos adjacency
        self._gsched: Optional[dist.GraphSchedule] = None
        self._tsched: Optional[topo.TopologySchedule] = None
        self._gscheds: Optional[Tuple[dist.GraphSchedule, ...]] = None
        self._htopo: Optional[topo.HierarchicalTopology] = None
        self._hsched: Optional[dist.HierSchedule] = None
        self._chain: Optional[topo.KroneckerChain] = None
        self._csched: Optional[dist.ChainSchedule] = None
        self._level_axes: Tuple[str, ...] = ()
        caps = MODE_REGISTRY[cfg.mode]
        n_model = dist.axis_sizes(mesh)[ax]
        if cfg.mode in GRAPH_MODES or caps.family == "push":
            if cfg.topology == "erdos":
                if grown_from is not None and grown_from._adj is not None:
                    # seed stream (seed, step=0, n_new): IDENTICAL to the one
                    # TopologySchedule.grown uses for its step 0, so a static
                    # erdos coder and its "fixed:erdos" schedule wrapper stay
                    # the same network through elastic growth too.
                    self._adj = topo.erdos_renyi_grow(
                        grown_from._adj, n_model, p=cfg.topology_p,
                        seed=topo.derive_seed(cfg.topology_seed, 0, n_model),
                    )
                elif shrunk_from is not None and shrunk_from[0]._adj is not None:
                    # Survivors keep every edge among themselves (ring repair
                    # only if the departures disconnected the graph).
                    self._adj = topo.shrink_adjacency(
                        shrunk_from[0]._adj, shrunk_from[1]
                    )
                else:
                    self._adj = topo.erdos_renyi_adjacency(
                        n_model, p=cfg.topology_p, seed=cfg.topology_seed
                    )
                self._A = topo.metropolis_weights(self._adj)
            else:
                self._A = topo.make_topology(
                    cfg.topology, n_model, p=cfg.topology_p,
                    seed=cfg.topology_seed, beta=cfg.beta,
                )
            if caps.family == "push":
                # Push-sum rides directed, row-stochastic-only combiners: the
                # weight channel absorbs the non-uniform column sums, so only
                # row stochasticity is required of A here.
                self._gsched = dist.graph_schedule(self._A, row_stochastic=True)
            elif cfg.topology == "torus":
                rows, cols = topo.torus_dims(n_model)
                self._gsched = dist.torus_schedule(rows, cols, self._A)
            else:
                self._gsched = dist.graph_schedule(self._A)
        elif cfg.mode in TV_MODES:
            if grown_from is not None and grown_from._tsched is not None:
                # A LinkFailureSchedule re-applies its dropout to the grown
                # base here, so failure_p survives elastic growth too.
                self._tsched = grown_from._tsched.grown(n_model)
            elif shrunk_from is not None and shrunk_from[0]._tsched is not None:
                self._tsched = shrunk_from[0]._tsched.shrunk(shrunk_from[1])
            else:
                spec = cfg.topology_schedule or "fixed"
                if spec == "fixed":
                    spec = f"fixed:{cfg.topology}"
                self._tsched = topo.make_topology_schedule(
                    spec, n_model, p=cfg.topology_p, seed=cfg.topology_seed,
                    beta=cfg.beta, period=cfg.schedule_period,
                )
                if cfg.failure_p > 0:
                    self._tsched = topo.link_failure_schedule(
                        self._tsched, cfg.failure_p,
                        failure_seed=cfg.failure_seed,
                        steps=cfg.failure_steps or None,
                    )
            self._gscheds = dist.graph_schedule_sequence(
                self._tsched.combiners, self._tsched.kinds
            )
        elif caps.hierarchical:
            sizes = dist.axis_sizes(mesh)
            level_specs = cfg.chain_levels()
            self._level_axes = tuple(
                cfg.level_axis(i) for i in range(len(level_specs))
            )
            for axis in self._level_axes:
                if axis not in sizes:
                    raise ValueError(
                        f"mode={cfg.mode!r} gossips over a {axis!r} axis "
                        f"the mesh does not have (axes: "
                        f"{tuple(mesh.axis_names)}); build a mesh with one "
                        f"axis per chain level, e.g. dist.debug_mesh("
                        f"model=N, data=D, pods=P) or dist.make_mesh(...)"
                    )
            level_ns = tuple(sizes[axis] for axis in self._level_axes)
            if grown_from is not None and grown_from._chain is not None:
                # growth is model-axis only: every outer factor is carried
                # verbatim, the innermost one re-derived (erdos grown
                # neighborhood-preservingly) at the larger size.
                self._chain = grown_from._chain.grown(n_model)
            elif shrunk_from is not None and shrunk_from[0]._chain is not None:
                # drain is model-axis only too: outer factors verbatim, the
                # innermost restricted to the survivor subgraph.
                self._chain = shrunk_from[0]._chain.shrunk(shrunk_from[1])
            else:
                self._chain = topo.make_kronecker_chain(
                    level_specs, level_ns,
                    p=cfg.topology_p, seed=cfg.topology_seed, beta=cfg.beta,
                )
            self._csched = dist.chain_schedule(self._chain, self._level_axes)
            if cfg.mode in HIER_MODES:
                # The legacy two-level surface, rebuilt FROM the chain
                # factors/schedules so the shim is bit-identical to a
                # hand-built two-level chain by construction.
                self._htopo = topo.HierarchicalTopology(
                    pod_kind=cfg.pod_topology, model_kind=cfg.topology,
                    n_pods=level_ns[1], n_model=level_ns[0],
                    A_pod=self._chain.combiners[1],
                    A_model=self._chain.combiners[0],
                    gossip_every=cfg.pod_gossip_every,
                    p=cfg.topology_p, seed=cfg.topology_seed, beta=cfg.beta,
                    model_adjacency=self._chain.adjacencies[0],
                )
                self._hsched = dist.HierSchedule(
                    model=self._csched.levels[0].sched,
                    pod=self._csched.levels[1].sched,
                    gossip_every=cfg.pod_gossip_every,
                )
        # The agent axes the dictionary (and the per-agent outputs) shard
        # over: the level axes OUTERMOST-FIRST for the hierarchical family
        # — device (i, ..., j) of the (outer, ..., model) grid IS the flat
        # outermost-major agent of the Kronecker chain (pod-major in the
        # two-level case) — and just (model,) for every flat mode.
        self._agent_axes: Tuple[str, ...] = (
            tuple(reversed(self._level_axes)) if caps.hierarchical else (ax,)
        )
        agent_spec = (
            self._agent_axes if len(self._agent_axes) > 1 else self._agent_axes[0]
        )
        self._w_spec = P(None, agent_spec)
        self._x_spec = P(da, None)
        # Every entry takes the schedule offset t0 (a replicated int32
        # scalar) as its last argument: the time-varying modes start their
        # combiner sequence at iteration t0, everything else ignores it.
        # t0 is traced, not static, so varying it never recompiles.
        t_spec = P()
        # nu/y leave the solve un-replicated along `model` (each agent its own
        # estimate), hence check_vma=False on the shard_map.
        self._solve = jax.jit(
            shard_map(
                _f32_matmuls(self._solve_body),
                mesh=mesh,
                in_specs=(self._w_spec, self._x_spec, t_spec),
                out_specs=(P(da, None), P(da, agent_spec)),
                check_vma=False,
            )
        )
        # The fit takes the duals a solve returned, with the solve's out
        # specs, so each device gets back its own (nu, y) buffers.
        self._fit = jax.jit(
            shard_map(
                _f32_matmuls(self._fit_body),
                mesh=mesh,
                in_specs=(self._w_spec, P(da, None), P(da, agent_spec), P()),
                out_specs=self._w_spec,
                check_vma=False,
            )
        )
        # The last solves' duals, keyed by the identity of (W, x) and by
        # t0, so a fit on the same batch against the same W reuses them.
        self._solved: Deque[_Solved] = collections.deque(maxlen=2)
        self._solved_lock = threading.Lock()
        self.fits_reused = 0  # fits that took a remembered solve's duals
        self.fits_resolved = 0  # fits that solved their batch first
        self._score = jax.jit(
            shard_map(
                _f32_matmuls(self._score_body),
                mesh=mesh,
                in_specs=(self._w_spec, self._x_spec, t_spec),
                out_specs=P(da),
                check_vma=False,
            )
        )
        # Diagnostic/parity hooks: per-agent stacked outputs (N leading axis,
        # the reference engine's layout) and the per-rank adaptive step size.
        self._solve_stacked = jax.jit(
            shard_map(
                _f32_matmuls(lambda W_loc, x_loc, t0: tuple(
                    v[None] for v in self._solve_body(W_loc, x_loc, t0)
                )),
                mesh=mesh,
                in_specs=(self._w_spec, self._x_spec, t_spec),
                out_specs=(P(agent_spec, *da, None), P(agent_spec, *da, None)),
                check_vma=False,
            )
        )
        self._init_w = jax.jit(
            init_dictionary, static_argnames=("m", "k", "nonneg"),
            out_shardings=NamedSharding(mesh, self._w_spec),
        )
        self._mu = jax.jit(
            shard_map(
                _f32_matmuls(self._mu_body),
                mesh=mesh,
                in_specs=(self._w_spec,),
                out_specs=P(agent_spec),
                check_vma=False,
            )
        )
        # The replication contract of every public program, one OutSpecInfo
        # per output, mirroring the out_specs above.  tools/analyze's
        # layer-3 verifier (rules_replication) traces each body and PROVES
        # every axis a spec omits non-varying — with check_vma=False these
        # declarations are otherwise unchecked.  nu and the novelty score
        # are per-agent consensus estimates (consensus=True: agent axes
        # exempt by design); W after fit and the step size mu must be
        # bit-identical wherever their specs say "replicated".
        self.out_spec_meta: Dict[str, Tuple[OutSpecInfo, ...]] = {
            "solve": (
                OutSpecInfo("nu", (da, None), consensus=True),
                OutSpecInfo("y", (da, agent_spec)),
            ),
            "fit": (OutSpecInfo("W", (None, agent_spec)),),
            "score": (OutSpecInfo("novelty", (da,), consensus=True),),
            "mu": (OutSpecInfo("mu", (agent_spec,)),),
        }

    # -- solver body (runs per device) -------------------------------------

    def _iter_setup(self, W_loc: Array, x_loc: Array):
        """Shared per-rank constants: total agent count, this agent's flat
        rank, and the informed-agent weighting (theta, |N_I|) of paper
        Eq. 29.  For the hierarchical family the network spans EVERY level
        axis: the count reduces over all of them and the flat rank is
        outermost-major (fold of rank * axis_size + axis_index over the
        agent axes, pod-major in the two-level case), matching the
        Kronecker chain's agent ordering."""
        res, reg, cfg = self.res, self.reg, self.cfg
        ax = cfg.model_axis
        n_model = jax.lax.psum(1, self._agent_axes)
        if len(self._agent_axes) > 1:
            sizes = dist.axis_sizes(self.mesh)
            rank = jnp.asarray(0, jnp.int32)
            for axis in self._agent_axes:  # outermost-first
                rank = rank * sizes[axis] + jax.lax.axis_index(axis)
        else:
            rank = jax.lax.axis_index(ax)
        if cfg.informed == "all":
            theta = jnp.ones((), x_loc.dtype)
            n_inf = jnp.asarray(n_model, x_loc.dtype)
        else:  # "one": only model-rank 0 is informed
            theta = (rank == 0).astype(x_loc.dtype)
            n_inf = jnp.ones((), x_loc.dtype)
        return n_model, rank, theta, n_inf

    def _solve_body(
        self, W_loc: Array, x_loc: Array, t0: Array
    ) -> Tuple[Array, Array]:
        """Per-device dual solve: cfg.iters gossip iterations from nu = 0.
        `t0` (replicated int32 scalar) is the combiner-schedule origin of
        the time-varying modes; every other mode ignores it.  The phases
        carry named scopes, so a profile groups the program's ops by them."""
        with jax.named_scope("step_size"):
            mu = self._mu_for(W_loc)
        with jax.named_scope("dual_iterations"):
            nu = self._dual_iterations(W_loc, x_loc, t0, mu)
        if self.cfg.use_kernel:
            y, _ = _local_code_and_back(self.res, self.reg, W_loc, nu, self.cfg)
        else:
            # The codes' product stays out of the threshold's fusion: with
            # the threshold in its epilogue XLA tiles it otherwise on a v5e
            # mesh, codes near the threshold change in their last bits, and
            # the atom update amplifies that (the learned W drifts from one
            # fitted with the same product computed on its own).
            y = self.reg.ystar(jax.lax.optimization_barrier(nu @ W_loc))
        return nu, y

    def _dual_iterations(
        self, W_loc: Array, x_loc: Array, t0: Array, mu: Array
    ) -> Array:
        """The solve's cfg.iters iterations of `cfg.mode` from nu = 0 at step
        size `mu`; returns this device's dual estimate."""
        res, reg, cfg = self.res, self.reg, self.cfg
        ax = cfg.model_axis
        n_model, rank, theta, n_inf = self._iter_setup(W_loc, x_loc)
        nu0 = jnp.zeros_like(x_loc)

        if cfg.mode in ("exact", "exact_fista"):

            def total_grad(nu):
                y, back = _local_code_and_back(res, reg, W_loc, nu, cfg)
                return res.grad_fstar(nu) - x_loc + dist.gossip_psum(back, ax)

            if cfg.mode == "exact":

                def step(nu, _):
                    nu = res.project_dual(nu - mu * total_grad(nu))
                    return nu, None

                nu, _ = jax.lax.scan(step, nu0, None, length=cfg.iters)
            else:  # exact_fista: strongly-convex Nesterov momentum
                # kappa from the same curvature estimate: m >= c_f.
                c_f = res.grad_fstar(jnp.ones((1,), W_loc.dtype))[0]
                L = 1.0 / mu
                beta = (jnp.sqrt(L) - jnp.sqrt(c_f)) / (jnp.sqrt(L) + jnp.sqrt(c_f))

                def step(carry, _):
                    nu, nu_prev = carry
                    z = nu + beta * (nu - nu_prev)
                    z = res.project_dual(z - mu * total_grad(z))
                    return (z, nu), None

                (nu, _), _ = jax.lax.scan(step, (nu0, nu0), None, length=cfg.iters)

        elif cfg.mode in RING_MODES:  # per-agent estimates + neighbor gossip
            beta = jnp.asarray(cfg.beta, x_loc.dtype)
            # ring exchanges need the static axis size (perms can't trace).
            nm = dist.axis_sizes(self.mesh)[ax]
            local_grad = self._local_grad_fn(W_loc, x_loc, theta, n_inf, n_model)

            def combine(psi, psi_left, psi_right):
                out = (1.0 - 2.0 * beta) * psi + beta * psi_left + beta * psi_right
                return res.project_dual(out)

            if cfg.mode == "ring":

                def step(nu, _):
                    psi = nu - mu * local_grad(nu)
                    left, right = dist.ring_shift(psi, ax, nm)
                    return combine(psi, left, right), None

                nu, _ = jax.lax.scan(step, nu0, None, length=cfg.iters)

            elif cfg.mode == "ring_q8":

                def step(carry, _):
                    nu, err = carry
                    psi = nu - mu * local_grad(nu)
                    # error-feedback quantization of the *message* only; the
                    # local copy of psi stays full precision.
                    q, s = _quantize_q8(psi + err)
                    err = (psi + err) - _dequantize_q8(q, s)
                    (ql, sl), (qr, sr) = dist.ring_shift((q, s), ax, nm)
                    nu = combine(
                        psi, _dequantize_q8(ql, sl), _dequantize_q8(qr, sr)
                    )
                    return (nu, err), None

                (nu, _), _ = jax.lax.scan(
                    step, (nu0, jnp.zeros_like(nu0)), None, length=cfg.iters
                )

            else:  # ring_async: combine with one-step-stale neighbor psi
                def step(carry, _):
                    nu, left_prev, right_prev = carry
                    psi = nu - mu * local_grad(nu)
                    nu_next = combine(psi, left_prev, right_prev)
                    # These sends overlap with the *next* local_grad compute.
                    left, right = dist.ring_shift(psi, ax, nm)
                    return (nu_next, left, right), None

                (nu, _, _), _ = jax.lax.scan(
                    step, (nu0, nu0, nu0), None, length=cfg.iters
                )

        elif cfg.mode in TV_MODES:  # time-varying combiner sequence
            scheds = self._gscheds
            local_grad = self._local_grad_fn(W_loc, x_loc, theta, n_inf, n_model)
            t_start = jnp.asarray(t0, jnp.int32)

            if cfg.mode == "graph_tv":

                def step(carry, _):
                    nu, t = carry
                    psi = nu - mu * local_grad(nu)
                    # the traced iteration index picks A_{t mod P}'s compiled
                    # ppermute schedule inside ONE program (lax.switch)
                    nu = res.project_dual(
                        dist.graph_combine_switch(psi, ax, scheds, t)
                    )
                    return (nu, t + 1), None

                (nu, _), _ = jax.lax.scan(
                    step, (nu0, t_start), None, length=cfg.iters
                )

            else:  # graph_tv_q8: same switch over the int8 wire format

                def step(carry, _):
                    nu, err, t = carry
                    psi = nu - mu * local_grad(nu)
                    # same wire format and error feedback as ring_q8: only
                    # the outgoing message is quantized, once per iteration.
                    q, s = _quantize_q8(psi + err)
                    err = (psi + err) - _dequantize_q8(q, s)
                    nu = res.project_dual(
                        dist.graph_combine_quantized_switch(
                            psi, q, s, ax, scheds, t
                        )
                    )
                    return (nu, err, t + 1), None

                (nu, _, _), _ = jax.lax.scan(
                    step, (nu0, jnp.zeros_like(nu0), t_start), None,
                    length=cfg.iters,
                )

        elif cfg.mode in PUSH_MODES:  # push-sum ratio consensus (directed A)
            sched = self._gsched
            local_grad = self._local_grad_fn(W_loc, x_loc, theta, n_inf, n_model)
            # Ratio consensus (push-sum): a scalar weight w rides the wire
            # next to the weighted dual v = w*psi and the update divides by
            # the combined weight, so ONLY row stochasticity of A is needed
            # (mass is conserved; each rank's bias cancels in the ratio).
            # On a doubly stochastic A the weight channel stays exactly 1
            # and the iteration reduces to plain ATC diffusion.
            w0 = jnp.ones((), x_loc.dtype)

            if cfg.mode == "push":

                def step(carry, _):
                    nu, w = carry
                    psi = nu - mu * local_grad(nu)
                    v, w = dist.push_graph_combine(psi, w, ax, sched)
                    nu = res.project_dual(v / w.astype(v.dtype))
                    return (nu, w), None

                (nu, _), _ = jax.lax.scan(
                    step, (nu0, w0), None, length=cfg.iters
                )

            else:  # push_q8: int8 wire format on the weighted dual channel

                def step(carry, _):
                    nu, w, err = carry
                    psi = nu - mu * local_grad(nu)
                    # error feedback on the WEIGHTED message v = w*psi (the
                    # quantity that actually crosses the wire); the scalar
                    # weight channel stays full precision — it costs 4 bytes
                    # and the ratio is too sensitive to quantize it.
                    v = w.astype(psi.dtype) * psi
                    q, s = _quantize_q8(v + err)
                    err = (v + err) - _dequantize_q8(q, s)
                    v_new, w = dist.push_graph_combine_quantized(
                        v, q, s, w, ax, sched
                    )
                    nu = res.project_dual(v_new / w.astype(v_new.dtype))
                    return (nu, w, err), None

                (nu, _, _), _ = jax.lax.scan(
                    step, (nu0, w0, jnp.zeros_like(nu0)), None,
                    length=cfg.iters,
                )

        elif MODE_REGISTRY[cfg.mode].hierarchical:  # N-level chain gossip
            cs = self._csched
            local_grad = self._local_grad_fn(W_loc, x_loc, theta, n_inf, n_model)
            t_start = jnp.asarray(t0, jnp.int32)
            # ONE branch for the whole family (hier, hier_q8, chain): each
            # level's hop is gated on its own stride by the traced t, q8
            # error feedback and stale-round messages ride the per-level
            # chain state (empty slots for levels that need neither, so the
            # carry pytree is as small as the config demands).
            state0 = dist.chain_state_init(nu0, cs)

            def step(carry, _):
                nu, st, t = carry
                psi = nu - mu * local_grad(nu)
                comb, st = dist.chain_combine(psi, cs, t, st)
                return (res.project_dual(comb), st, t + 1), None

            (nu, _, _), _ = jax.lax.scan(
                step, (nu0, state0, t_start), None, length=cfg.iters
            )

        else:  # graph family: gossip under the compiled combiner schedule
            sched = self._gsched
            local_grad = self._local_grad_fn(W_loc, x_loc, theta, n_inf, n_model)

            if cfg.mode == "graph":

                def step(nu, _):
                    psi = nu - mu * local_grad(nu)
                    nu = res.project_dual(dist.graph_combine(psi, ax, sched))
                    return nu, None

                nu, _ = jax.lax.scan(step, nu0, None, length=cfg.iters)

            elif cfg.mode == "graph_q8":

                def step(carry, _):
                    nu, err = carry
                    psi = nu - mu * local_grad(nu)
                    # same wire format and error feedback as ring_q8: only
                    # the outgoing message is quantized, once per iteration.
                    q, s = _quantize_q8(psi + err)
                    err = (psi + err) - _dequantize_q8(q, s)
                    nu = res.project_dual(
                        dist.graph_combine_quantized(psi, q, s, ax, sched)
                    )
                    return (nu, err), None

                (nu, _), _ = jax.lax.scan(
                    step, (nu0, jnp.zeros_like(nu0)), None, length=cfg.iters
                )

            else:  # graph_async: combine with one-step-stale round messages

                def step(carry, _):
                    nu, recv_prev = carry
                    psi = nu - mu * local_grad(nu)
                    nu_next = res.project_dual(
                        dist.graph_accumulate(psi, recv_prev, ax, sched)
                    )
                    # These sends overlap with the next local_grad compute.
                    recv = dist.graph_shift(psi, ax, sched)
                    return (nu_next, recv), None

                recv0 = tuple(nu0 for _ in sched.steps)
                (nu, _), _ = jax.lax.scan(
                    step, (nu0, recv0), None, length=cfg.iters
                )

        return nu

    def _local_grad_fn(self, W_loc, x_loc, theta, n_inf, n_model):
        """Per-agent dual gradient grad J_k (shared by the ring and graph
        families; mirrors core/inference.agent_grad exactly)."""
        res, reg, cfg = self.res, self.reg, self.cfg

        def local_grad(nu):
            y, back = _local_code_and_back(res, reg, W_loc, nu, cfg)
            return (
                -(theta / n_inf) * x_loc
                + res.grad_fstar(nu) / n_model
                + back
            )

        return local_grad

    def _mu_for(self, W_loc: Array) -> Array:
        """THE step-size rule: shared by the solver bodies and the
        adaptive_mu diagnostic so the two can never diverge."""
        res, reg, cfg = self.res, self.reg, self.cfg
        if cfg.mu > 0:
            return jnp.asarray(cfg.mu, W_loc.dtype)
        if cfg.mode in ("exact", "exact_fista"):
            return _safe_mu_exact(res, reg, W_loc, cfg.model_axis)
        # gossip families: pmax over the agent axes — BOTH pod and model
        # for the hierarchical modes, so every agent of the two-level
        # network steps with the one globally-safe mu.
        return _safe_mu_local(res, reg, W_loc, self._agent_axes)

    def _mu_body(self, W_loc: Array) -> Array:
        """The step size this rank's solve would use (shape (1,) per rank;
        stacked to (N,) by the out_spec).  After the pmax fix all ranks must
        report the identical value for the adaptive ring modes."""
        return self._mu_for(W_loc)[None]

    # -- one dictionary-learning step (atom update from a solve's duals) ----

    def _fit_body(
        self, W_loc: Array, nu: Array, y: Array, mu_w: Array
    ) -> Array:
        """One dictionary step (paper Eq. 51) from a solve's duals (nu, y)
        of the batch: the locally-owned atom update with the
        minibatch-mean gradient reduced over the data axes."""
        reg, cfg = self.reg, self.cfg
        with jax.named_scope("atom_update"):
            # Minibatch-mean gradient nu^T y; reduce over the data axes (DP sync).
            b_loc = jnp.asarray(nu.shape[0], nu.dtype)
            g = nu.T @ y  # (M, K_loc)
            for da in cfg.data_axes:
                g = jax.lax.psum(g, da)
                b_loc = jax.lax.psum(b_loc, da)
            W_new = W_loc + mu_w * g / b_loc
            if reg.nonneg:
                W_new = jnp.maximum(W_new, 0.0)
            norms = jnp.linalg.norm(W_new, axis=0, keepdims=True)
            return W_new / jnp.maximum(norms, 1.0)

    # -- novel-document scoring (exact aggregation = 1 psum) ---------------

    def _score_body(self, W_loc: Array, h_loc: Array, t0: Array) -> Array:
        """Per-device novelty scoring (paper Eq. 63-66): dual value of the
        fit, aggregated exactly with one psum over the agent axes (model,
        plus pod in the hierarchical modes — the atom blocks span both)."""
        res, reg, cfg = self.res, self.reg, self.cfg
        nu, _ = self._solve_body(W_loc, h_loc, t0)
        hstar = reg.hstar(nu @ W_loc)  # (B,)
        hstar_sum = jax.lax.psum(hstar, self._agent_axes)
        val = res.fstar(nu) - jnp.sum(nu * h_loc, axis=-1) + hstar_sum
        return -val  # higher = more novel (dual value of the fit)

    # -- public API ---------------------------------------------------------

    def solve(self, W: Array, x: Array, t0: int = 0) -> Tuple[Array, Array]:
        """Dual inference. W (M, K) atom-sharded; x (B, M) batch-sharded.
        Returns (nu (B, M) — agent-local estimates, y (B, K)).  `t0` is the
        combiner-schedule offset for the time-varying modes (the network at
        iteration i of this solve is A_{t0+i}) and the inter-pod gossip
        phase for hier modes with pod_gossip_every = k > 1 (the pod hop
        fires at iterations i with (t0+i) % k == 0); it is traced, so
        varying it never recompiles.  Static modes ignore it."""
        nu, y = self._solve(W, x, jnp.asarray(t0, jnp.int32))
        with self._solved_lock:
            self._solved.append(_Solved(weakref.ref(W), weakref.ref(x), int(t0), nu, y))
        return nu, y

    def fit_batch(self, W: Array, x: Array, mu_w: float, t0: int = 0) -> Array:
        """One distributed dictionary-learning step (Alg. 1): returns new W.
        `t0` is the time-varying combiner-schedule offset (see solve).

        When one of the last two `solve` calls had this very `W` and `x`
        (the same objects) and the same `t0`, its duals are the batch's
        solution and the step reuses them (`fits_reused`); otherwise it
        solves the batch first (`fits_resolved`).  Both give the same W."""
        with self._solved_lock:
            hit = next((s for s in reversed(self._solved) if s.matches(W, x, t0)), None)
            if hit is None:
                self.fits_resolved += 1
            else:
                self.fits_reused += 1
        if hit is None:
            nu, y = self._solve(W, x, jnp.asarray(t0, jnp.int32))
        else:
            nu, y = hit.nu, hit.y
        return self._fit(W, nu, y, jnp.asarray(mu_w, jnp.float32))

    def score(self, W: Array, h: Array, t0: int = 0) -> Array:
        """Novelty scores for test batch h (paper Eq. 63-66, exact path)."""
        return self._score(W, h, jnp.asarray(t0, jnp.int32))

    def solve_per_agent(
        self, W: Array, x: Array, t0: int = 0
    ) -> Tuple[Array, Array]:
        """Dual inference with per-agent outputs stacked on a leading N axis:
        nu (N, B, M) and y (N, B, Kb) — the reference engine's layout, used
        by the ref<->dist parity tests and debugging."""
        return self._solve_stacked(W, x, jnp.asarray(t0, jnp.int32))

    def adaptive_mu(self, W: Array) -> Array:
        """Per-rank step size the configured mode would use, gathered to
        (N,).  All entries must agree (regression hook for the pmax fix)."""
        return self._mu(W)

    def combiner(self) -> np.ndarray:
        """The doubly-stochastic combination matrix A this coder's mode
        realizes, in the reference engine's layout (A[l, k] = a_{lk}): the
        compiled graph combiner for the graph family, the constant-weight
        ring matrix for the ring family, and 11^T/N for the exact modes.
        For the time-varying modes this is the effective ONE-PERIOD window
        product A_0 A_1 ... A_{P-1} (itself doubly stochastic) — the
        per-step sequence is `combiner_sequence()`.  For the hierarchical
        family it is the dense Kronecker chain on the prod(ns)-agent
        network (the window product over one stride-LCM period when any
        stride is > 1; A_pod (x) A_model in the two-level case).  Used by
        the ref<->dist parity tests, the gossip benchmarks, and service
        stats."""
        if self._chain is not None:
            return self._chain.window_combiner()
        if self._tsched is not None:
            return self._tsched.window_combiner()
        if self._A is not None:
            return np.array(self._A)
        n = dist.axis_sizes(self.mesh)[self.cfg.model_axis]
        if self.cfg.mode in ("exact", "exact_fista"):
            return topo.uniform_weights(n)
        return topo.ring_weights(n, self.cfg.beta)

    def combiner_sequence(self) -> Tuple[np.ndarray, ...]:
        """The per-iteration combiner sequence A_0 .. A_{P-1} (period P = 1
        for every static mode; P = the stride LCM for the hierarchical
        family, whose sequence gates each level's factor on its own stride
        — alternating A_pod (x) A_model with I (x) A_model in the
        two-level case) — the determinism tests compare this across engine
        constructions and grown() restarts."""
        if self._chain is not None:
            return tuple(np.array(a) for a in self._chain.sequence())
        if self._tsched is not None:
            return tuple(np.array(a) for a in self._tsched.combiners)
        return (self.combiner(),)

    def _levels_info(self) -> list:
        """Per-level metadata rows (kind, axis, n, gossip_every, wire,
        stale), innermost-first: one row per chain level for the
        hierarchical family, and the degenerate single-level view of every
        flat mode (wire/stale read off the mode's registry caps) — so
        stats and growth events report a uniform `levels` schema."""
        if self._chain is not None:
            return [
                {
                    "kind": spec.kind,
                    "axis": lvl.axis,
                    "n": int(n),
                    "gossip_every": spec.gossip_every,
                    "wire": spec.wire,
                    "stale": spec.stale,
                }
                for spec, n, lvl in zip(
                    self._chain.specs, self._chain.ns, self._csched.levels
                )
            ]
        caps = MODE_REGISTRY[self.cfg.mode]
        if caps.family == "tv":
            kind = f"tv:{self._tsched.spec}"
        elif caps.family in ("graph", "push"):
            kind = self.cfg.topology
        elif caps.family == "ring":
            kind = "ring"
        else:
            kind = "full"
        return [{
            "kind": kind,
            "axis": self.cfg.model_axis,
            "n": int(dist.axis_sizes(self.mesh)[self.cfg.model_axis]),
            "gossip_every": 1,
            "wire": "q8" if caps.quantized else "fp32",
            "stale": caps.stale,
        }]

    def combiner_info(self) -> dict:
        """Topology label + mixing rate for stats/benchmark reporting.

        mixing_rate is the gossip contraction factor: the second-largest
        singular value of A for static modes, the per-step WINDOWED rate
        sigma_2(window product)^(1/P) for the time-varying modes, and the
        EFFECTIVE chain rate (sigma_2 of the all-hops composition,
        windowed over the stride-LCM period when any stride is > 1) for
        the hierarchical family.  Also carries `schedule` (the spec, None
        when static), `schedule_period` (1 when static; the stride LCM for
        the hierarchical family), the hier identity `pod_topology` /
        `pod_gossip_every` (None / 1 for every flat mode and for
        mode="chain", whose level data lives in `levels`), and `levels` —
        the uniform per-level metadata rows of `_levels_info` (every mode,
        single-entry for flat ones)."""
        caps = MODE_REGISTRY[self.cfg.mode]
        if caps.hierarchical:
            if self.cfg.mode in HIER_MODES:
                # label reads intra+inter: hier:<model kind>+<pod kind>
                label = f"hier:{self.cfg.topology}+{self.cfg.pod_topology}"
                pod_topology = self.cfg.pod_topology
                pod_gossip_every = self.cfg.pod_gossip_every
            else:
                label = "chain:" + "+".join(
                    s.kind for s in self._chain.specs
                )
                pod_topology, pod_gossip_every = None, 1
            return {
                "topology": label,
                "mixing_rate": self._chain.effective_mixing_rate(),
                "schedule": None,
                "schedule_period": self._chain.period,
                "pod_topology": pod_topology,
                "pod_gossip_every": pod_gossip_every,
                "levels": self._levels_info(),
            }
        if caps.family == "tv":
            return {
                "topology": f"tv:{self._tsched.spec}",
                "mixing_rate": self._tsched.windowed_mixing_rate(),
                "schedule": self._tsched.spec,
                "schedule_period": self._tsched.period,
                "pod_topology": None,
                "pod_gossip_every": 1,
                "levels": self._levels_info(),
            }
        if caps.family in ("graph", "push"):
            # For push the combiner may be row-stochastic only; sigma_2 is
            # still the reported contraction proxy (exact on the doubly
            # stochastic subfamily, where push-sum IS plain diffusion).
            label = self.cfg.topology
        elif caps.family == "ring":
            label = "ring"
        else:
            label = "full"
        return {
            "topology": label,
            "mixing_rate": topo.mixing_rate(self.combiner()),
            "schedule": None,
            "schedule_period": 1,
            "pod_topology": None,
            "pod_gossip_every": 1,
            "levels": self._levels_info(),
        }

    @property
    def gossip_schedule(self) -> Optional[dist.GraphSchedule]:
        """The compiled ppermute schedule (static graph modes only; the
        time-varying modes expose `gossip_schedules`; None otherwise)."""
        return self._gsched

    @property
    def gossip_schedules(self) -> Optional[Tuple[dist.GraphSchedule, ...]]:
        """The compiled per-step ppermute schedules: a length-P tuple for
        the time-varying modes, a 1-tuple for the static graph modes, None
        for ring/exact (whose data movement is not schedule-compiled)."""
        if self._gscheds is not None:
            return self._gscheds
        if self._gsched is not None:
            return (self._gsched,)
        return None

    @property
    def topology_schedule(self) -> Optional[topo.TopologySchedule]:
        """The validated `TopologySchedule` driving a time-varying coder
        (None for static modes)."""
        return self._tsched

    @property
    def hier_topology(self) -> Optional[topo.HierarchicalTopology]:
        """The validated two-level combiner driving a hierarchical coder
        (None for every flat mode)."""
        return self._htopo

    @property
    def hier_gossip_schedule(self) -> Optional[dist.HierSchedule]:
        """The compiled two-level ppermute plan (hier modes only): the
        intra-pod and inter-pod `GraphSchedule`s plus the gossip stride —
        benchmarks read per-axis message counts off it."""
        return self._hsched

    @property
    def chain(self) -> Optional[topo.KroneckerChain]:
        """The validated N-level Kronecker chain driving a hierarchical
        coder (hier/hier_q8/chain modes; None for every flat mode).  The
        hier modes see their two-level topology here as a length-2 chain,
        innermost (model) level first."""
        return self._chain

    @property
    def chain_gossip_schedule(self) -> Optional[dist.ChainSchedule]:
        """The compiled per-level ppermute plan (hierarchical family only):
        one `LevelPlan` per chain level, innermost-first, each carrying its
        axis name, `GraphSchedule`, stride, and wire format — benchmarks
        read per-level message counts off it."""
        return self._csched

    @property
    def schedule_period(self) -> int:
        """Length of the per-iteration combiner sequence before it repeats:
        the `TopologySchedule` period for the time-varying modes, the LCM
        of level strides for the hierarchical family, 1 for every static
        mode.  The service's schedule clock reduces its offset modulo
        this."""
        if self._tsched is not None:
            return self._tsched.period
        if self._chain is not None:
            return self._chain.period
        return 1

    @property
    def is_time_varying(self) -> bool:
        """Whether this coder's combiner changes per iteration (the service
        threads a persistent schedule offset t0 through solve/fit iff so).
        True for the graph_tv modes, and for the hierarchical family
        whenever the stride LCM exceeds 1 (some hop's firing phase then
        matters)."""
        caps = MODE_REGISTRY[self.cfg.mode]
        return caps.time_varying or (
            caps.hierarchical and self.schedule_period > 1
        )

    def wire_bytes_per_iter(
        self, b_loc: int, m: int
    ) -> Tuple[Tuple[str, float], ...]:
        """Analytic wire bytes per solve iteration per device, split by
        gossip level: ((axis_name, bytes), ...) innermost-first, for a
        (b_loc, m) per-device dual block.

        This is the SINGLE source of truth for the engine's byte
        accounting: benchmarks/gossip_modes.py reports these numbers and
        tools/analyze cross-checks them against bytes counted directly off
        the abstract jaxpr (`abstract_trace`), so the formula, the
        benchmark, and the traced program cannot drift apart.  One fp32
        message is `4*b_loc*m` bytes, one q8 message `b_loc*(m+4)` (int8
        payload + one fp32 scale per row); exact modes count their psum
        all-reduce at 2x the operand (reduce-scatter + all-gather);
        time-varying modes average over the schedule period and strided
        levels over their gossip stride; push-sum modes add 4 bytes per
        round for the scalar fp32 weight riding next to the message."""
        caps = MODE_REGISTRY[self.cfg.mode]
        ax = self.cfg.model_axis
        fp32 = 4 * b_loc * m
        q8 = b_loc * (m + 4)
        if caps.family == "exact":
            return ((ax, 2.0 * fp32),)
        if caps.family == "ring":
            # ring_shift: one ppermute to each neighbor per iteration
            return ((ax, 2.0 * (q8 if caps.quantized else fp32)),)
        if caps.family in ("graph", "tv", "push"):
            scheds = self.gossip_schedules
            rounds = sum(s.messages_per_iter for s in scheds) / len(scheds)
            msg = float(q8 if caps.quantized else fp32)
            if caps.family == "push":
                msg += 4.0  # the scalar fp32 weight channel, per round
            return ((ax, rounds * msg),)
        # hierarchical family: one entry per chain level, innermost-first
        per_level = dist.wire_bytes_per_level(self._csched, b_loc, m)
        return tuple(
            (lvl.axis, b) for lvl, b in zip(self._csched.levels, per_level)
        )

    def shard(self, W: Array, x: Array) -> Tuple[Array, Array]:
        """Place global arrays with the engine's shardings (for benchmarks)."""
        W = jax.device_put(W, NamedSharding(self.mesh, self._w_spec))
        x = jax.device_put(x, NamedSharding(self.mesh, self._x_spec))
        return W, x

    # -- serving hooks: double-buffer snapshot + elastic model-axis growth --

    def snapshot(self, W: Array) -> Array:
        """Read-side copy of W placed with the coder's sharding.

        `fit_batch` is functional (it returns a NEW buffer and leaves its
        input untouched), so double-buffering for a serving path is just
        reference management: readers keep coding against the last published
        snapshot while the learner advances the live copy; publishing is an
        atomic swap of the reference (see repro.runtime.service)."""
        return jax.device_put(W, NamedSharding(self.mesh, self._w_spec))

    def init_dictionary(self, key: jax.Array, m: int, k: int) -> Array:
        """A random unit-norm (M, K) dictionary (`core.dictionary.
        init_dictionary`) created directly in the engine's W sharding: each
        device draws only its own atom shard, so no device ever holds the
        whole W or its temporaries."""
        return self._init_w(key, m=m, k=k, nonneg=self.reg.nonneg)

    def grown(
        self, W: Array, extra_model: int, key: jax.Array, devices=None
    ) -> Tuple["DistributedSparseCoder", Array]:
        """Elastic growth: the distributed counterpart of
        `DictionaryLearner.expanded()` (paper Sec. IV-C — new atoms/agents
        arrive mid-stream).

        Returns (new_coder, W2): a coder on a mesh whose `model` axis is
        larger by `extra_model` devices, and the dictionary re-sharded onto
        it with the old atom shards preserved and `extra_model` fresh shards
        (unit-norm, nonneg-projected when the task demands it) appended.
        Re-sharding goes through the runtime/dist seam: the new mesh comes
        from `dist.make_mesh` and placement from the new coder's sharding.

        Growth is topology-aware: erdos combiners (static, every erdos step
        of a time-varying schedule, and the erdos intra-pod factor of a
        hierarchical coder) are grown from the current adjacency with
        `topology.erdos_renyi_grow` — existing agents keep their
        neighborhoods, only new-agent edges are sampled — while structured
        kinds re-derive at the larger size.  Time-varying coders re-derive
        the whole SEQUENCE (deterministically in topology_seed).

        Hierarchical coders grow on the innermost MODEL level only (the
        outer-level agent counts are fixed at mesh construction — inter-pod
        and inter-rack links are physical): every outer-level group gains
        `extra_model` fresh agents, all outer combiners are carried
        verbatim, and because the atom layout is outermost-major the fresh
        shards are interleaved per group — each existing agent keeps
        exactly the atom shard it already owned.

        `devices` is the flat pool the grown mesh is built from (the
        current devices plus the arrivals).  Default None = all of
        jax.devices() — right for a single-tenant coder, but a coder that
        owns a device SUBSET (one replica of a runtime/serving fleet) must
        pass its own enlarged pool or growth would annex its peers'
        devices.
        """
        if extra_model <= 0:
            raise ValueError(f"extra_model must be positive, got {extra_model}")
        sizes = dist.axis_sizes(self.mesh)
        n_old = sizes[self.cfg.model_axis]
        n_new = n_old + int(extra_model)
        names = tuple(self.mesh.axis_names)
        shape = tuple(
            n_new if nm == self.cfg.model_axis else sizes[nm] for nm in names
        )
        new_mesh = dist.make_mesh(shape, names, devices=devices)
        new_coder = DistributedSparseCoder(
            new_mesh, self.res, self.reg, self.cfg, grown_from=self
        )
        m, k = W.shape
        if self._chain is not None:
            outer = int(np.prod(self._chain.ns[1:])) if self._chain.n_levels > 1 else 1
            shards = outer * n_old
            if k % shards:
                raise ValueError(
                    f"K={k} not divisible by outer*model={shards}"
                )
            kb = k // shards
            # Outermost-major atom layout: outer group i owns columns
            # [i*n_old*kb, (i+1)*n_old*kb).  Append each group's fresh
            # atoms NEXT TO its existing block so old shards stay with
            # their owners.
            W_host = np.asarray(jax.device_get(W)).reshape(m, outer, n_old * kb)
            parts = []
            for i, kp in enumerate(jax.random.split(key, outer)):
                fresh = init_dictionary(
                    kp, m, kb * int(extra_model), nonneg=self.reg.nonneg
                )
                parts.append(
                    np.concatenate([W_host[:, i, :], np.asarray(fresh)], axis=1)
                )
            W2 = jnp.asarray(np.concatenate(parts, axis=1), W_host.dtype)
        else:
            if k % n_old:
                raise ValueError(f"K={k} not divisible by model={n_old}")
            kb = k // n_old
            fresh = init_dictionary(
                key, m, kb * int(extra_model), nonneg=self.reg.nonneg
            )
            W2 = jnp.concatenate([jax.device_get(W), fresh], axis=1)
        return new_coder, new_coder.snapshot(W2)

    def shrunk(
        self, W: Array, departing_ranks: Sequence[int]
    ) -> Tuple["DistributedSparseCoder", Array]:
        """Agent drain: the inverse of `grown()` — `departing_ranks` leave
        the network and the surviving atoms are re-sharded onto a smaller
        mesh WITHOUT restart.

        Returns (new_coder, W2): a coder whose `model` axis shrank by
        len(departing_ranks) devices, and the dictionary restricted to the
        survivors' atom shards — each surviving agent keeps exactly the
        shard it already owned, bit for bit (no re-init, no renorm).

        Shrink is topology-aware and deterministic: erdos combiners (static
        and every erdos step of a time-varying schedule) are RESTRICTED to
        the survivor-induced subgraph via `topology.shrink_adjacency`
        (survivors keep every edge among themselves; a deterministic ring
        repair kicks in only if the departures disconnected the graph),
        while structured kinds re-derive at the smaller size.  A
        `LinkFailureSchedule` re-applies its seeded dropout over the shrunk
        base, so a drained network keeps the same failure trace law.

        Hierarchical coders drain on the innermost MODEL level only (same
        contract as growth): every outer-level group loses the SAME model
        ranks, outer combiners are carried verbatim, and the outermost-major
        atom layout means each group's surviving shards stay contiguous with
        their owners.

        The shrunk mesh is carved from THIS coder's own device pool (not
        jax.devices()), so draining a fleet replica never migrates it onto
        devices owned by its peers.
        """
        sizes = dist.axis_sizes(self.mesh)
        n_old = sizes[self.cfg.model_axis]
        departing = sorted(set(int(r) for r in departing_ranks))
        if not departing:
            raise ValueError("departing_ranks is empty: nothing to drain")
        if departing[0] < 0 or departing[-1] >= n_old:
            raise ValueError(
                f"departing_ranks {departing} out of range for model axis "
                f"of size {n_old}"
            )
        survivors = tuple(r for r in range(n_old) if r not in set(departing))
        if not survivors:
            raise ValueError(
                f"cannot drain all {n_old} model ranks: at least one "
                f"survivor is required"
            )
        n_new = len(survivors)
        names = tuple(self.mesh.axis_names)
        shape = tuple(
            n_new if nm == self.cfg.model_axis else sizes[nm] for nm in names
        )
        new_mesh = dist.make_mesh(
            shape, names, devices=self.mesh.devices.reshape(-1)
        )
        new_coder = DistributedSparseCoder(
            new_mesh, self.res, self.reg, self.cfg,
            shrunk_from=(self, survivors),
        )
        m, k = W.shape
        sel = np.asarray(survivors, dtype=np.int64)
        if self._chain is not None:
            outer = int(np.prod(self._chain.ns[1:])) if self._chain.n_levels > 1 else 1
            shards = outer * n_old
            if k % shards:
                raise ValueError(
                    f"K={k} not divisible by outer*model={shards}"
                )
            kb = k // shards
            W_host = np.asarray(jax.device_get(W)).reshape(m, outer, n_old, kb)
            W2 = jnp.asarray(
                W_host[:, :, sel, :].reshape(m, outer * n_new * kb),
                W_host.dtype,
            )
        else:
            if k % n_old:
                raise ValueError(f"K={k} not divisible by model={n_old}")
            kb = k // n_old
            W_host = np.asarray(jax.device_get(W)).reshape(m, n_old, kb)
            W2 = jnp.asarray(
                W_host[:, sel, :].reshape(m, n_new * kb), W_host.dtype
            )
        return new_coder, new_coder.snapshot(W2)


# ---------------------------------------------------------------------------
# Abstract-trace hooks: device-free tracing of the shard_map bodies, the
# seam tools/analyze verifies protocol invariants through.  Everything here
# runs on an AbstractMesh — no devices, no XLA_FLAGS, no compilation.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TraceCase:
    """One abstractly-traceable engine configuration: `axis_sizes` is the
    ordered mesh (outermost axis first), `cfg` the mode under test.  The
    default catalog (`mode_trace_cases`) covers every MODE_REGISTRY mode,
    so the static analyzer's coverage check is `{case.cfg.mode} >= MODES`.

    `programs` lists the shard_map bodies to verify for this case — the
    keys of `DistributedSparseCoder.out_spec_meta`, i.e. the out-spec'd
    programs whose replication contracts the layer-3 verifier must prove
    (`abstract_trace(..., program=p)` traces each one)."""

    name: str
    cfg: DistConfig
    axis_sizes: Tuple[Tuple[str, int], ...]
    programs: Tuple[str, ...] = ("solve", "fit", "score", "mu")


def mode_trace_cases() -> Tuple[TraceCase, ...]:
    """The analyzer's trace matrix: at least one case per registry mode,
    on the smallest mesh that exercises the mode's collectives (flat modes
    on 4 agents; the hierarchical family on multi-pod meshes, including
    the benchmark's 3-level chain row so its static byte accounting is
    cross-checked, plus a stale-outermost-hop variant)."""
    flat = ((dist.DATA_AXIS, 1), (dist.MODEL_AXIS, 4))
    hier_axes = (
        (dist.POD_AXIS, 2), (dist.DATA_AXIS, 1), (dist.MODEL_AXIS, 2)
    )
    chain_axes = (
        (f"{dist.POD_AXIS}2", 2), (dist.POD_AXIS, 2),
        (dist.DATA_AXIS, 1), (dist.MODEL_AXIS, 2),
    )
    cases = []
    for mode, caps in MODE_REGISTRY.items():
        if caps.hierarchical:
            continue
        if caps.family == "push":
            # the acceptance combiner: genuinely row-stochastic-only, so
            # the trace exercises the weight channel doing real work.
            cfg = DistConfig(mode=mode, iters=2, topology="distar")
        else:
            cfg = DistConfig(mode=mode, iters=2)
        cases.append(TraceCase(mode, cfg, flat))
    cases.append(TraceCase(
        "graph_tv:linkfail",
        DistConfig(mode="graph_tv", iters=2, failure_p=0.3, failure_seed=5,
                   failure_steps=4),
        flat,
    ))
    cases.append(TraceCase(
        "hier",
        DistConfig(mode="hier", iters=2, topology="torus",
                   pod_topology="ring_metropolis"),
        hier_axes,
    ))
    cases.append(TraceCase(
        "hier_q8",
        DistConfig(mode="hier_q8", iters=2, topology="torus",
                   pod_topology="ring_metropolis", pod_gossip_every=2),
        hier_axes,
    ))
    # the benchmark's chain:3level row, verbatim — the analyzer's byte
    # cross-check ties the traced program to the reported numbers
    cases.append(TraceCase(
        "chain:3level",
        DistConfig(mode="chain", iters=2,
                   levels="ring_metropolis,ring_metropolis:2:q8,full:4:q8"),
        chain_axes,
    ))
    cases.append(TraceCase(
        "chain:stale",
        DistConfig(
            mode="chain", iters=2,
            levels="ring_metropolis,ring_metropolis:2:q8,full:4:q8:stale",
        ),
        chain_axes,
    ))
    return tuple(cases)


def abstract_trace(
    cfg: DistConfig,
    axis_sizes: Sequence[Tuple[str, int]],
    *,
    batch: int = 8,
    m: int = 32,
    kb: int = 4,
    task: str = "nmf",
    fit: bool = False,
    program: Optional[str] = None,
):
    """Trace one engine body abstractly: build the coder on a device-free
    `dist.abstract_mesh` with the given (outermost-first) axis sizes and
    `jax.make_jaxpr` one of its per-device bodies with every mesh axis
    bound in the trace's axis env.  `program` selects the body by its
    `out_spec_meta` key — "solve" (default), "fit", "score", or "mu";
    the legacy `fit=True` flag is shorthand for program="fit".

    Returns (coder, closed_jaxpr).  The jaxpr is the per-DEVICE program —
    exactly what shard_map stages — with psum/ppermute/pmax equations
    carrying their axis names, so protocol checks (collective parity
    across cond branches, permutation-table validity, wire-byte
    accounting, out-spec replication proofs) run without any devices.
    `kb` is the per-agent atom count and `batch` the GLOBAL batch
    (divided over the data axes)."""
    from repro.core.conjugates import make_task

    if program is None:
        program = "fit" if fit else "solve"
    names = tuple(n for n, _ in axis_sizes)
    sizes = tuple(s for _, s in axis_sizes)
    mesh = dist.abstract_mesh(sizes, names)
    res, reg = make_task(task)
    coder = DistributedSparseCoder(mesh, res, reg, cfg)
    size_of = dict(axis_sizes)
    b_loc = batch // int(
        np.prod([size_of[a] for a in cfg.data_axes], dtype=np.int64)
    )
    W_loc = jax.ShapeDtypeStruct((m, kb), jnp.float32)
    x_loc = jax.ShapeDtypeStruct((b_loc, m), jnp.float32)
    t0 = jax.ShapeDtypeStruct((), jnp.int32)
    axis_env = [(n, s) for n, s in axis_sizes]
    if program == "fit":
        nu = jax.ShapeDtypeStruct((b_loc, m), jnp.float32)
        y = jax.ShapeDtypeStruct((b_loc, kb), jnp.float32)
        mu_w = jax.ShapeDtypeStruct((), jnp.float32)
        jaxpr = jax.make_jaxpr(coder._fit_body, axis_env=axis_env)(
            W_loc, nu, y, mu_w
        )
    elif program == "score":
        jaxpr = jax.make_jaxpr(coder._score_body, axis_env=axis_env)(
            W_loc, x_loc, t0
        )
    elif program == "mu":
        jaxpr = jax.make_jaxpr(coder._mu_body, axis_env=axis_env)(W_loc)
    elif program == "solve":
        jaxpr = jax.make_jaxpr(coder._solve_body, axis_env=axis_env)(
            W_loc, x_loc, t0
        )
    else:
        raise ValueError(
            f"unknown program {program!r}; expected one of "
            f"('solve', 'fit', 'score', 'mu')"
        )
    return coder, jaxpr


# ---------------------------------------------------------------------------
# Helper: build a CPU debug mesh (tests force multi-device via XLA_FLAGS).
# Kept as a name here for callers of the engine; construction lives in the
# runtime layer.
# ---------------------------------------------------------------------------

make_debug_mesh = dist.debug_mesh
