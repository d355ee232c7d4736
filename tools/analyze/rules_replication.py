"""Replication-soundness verification (analyze layer 3; jax, no devices).

The engine runs every shard_map with `check_vma=False`, so the out_specs
are unchecked DECLARATIONS: an axis a spec omits is promised replicated
(same bytes on every device along it), and XLA will happily ship
device-dependent garbage as if it were replicated — exactly the PR 2 mu
bug, where `_safe_mu_local` forgot its pmax and every rank silently
stepped with a different step size.  This layer turns those declarations
into PROOF OBLIGATIONS: it re-uses `rules_jaxpr._JaxprChecker`'s
varying-axes dataflow (psum/pmax/pmin SUBTRACT their reduced axes from a
value's varying set — a reduction is the only way a value becomes
provably non-varying) and checks, for every `mode_trace_cases()` entry
and every program in its `programs` tuple, the engine's own
`DistributedSparseCoder.out_spec_meta` contract:

  out-spec-replication   every mesh axis an output's out_spec omits must
                         be proved non-varying along that axis.  Outputs
                         marked `consensus=True` (nu, the novelty score:
                         per-agent estimates, the documented
                         check_vma=False rationale) are exempt on the
                         AGENT axes only — other axes are still proved.
  step-size-replication  the adaptive step size (the "mu" program) must
                         be non-varying over ALL agent axes: every agent
                         must step with the one mu that is safe for the
                         worst shard, or the gossip iterates diverge
                         (paper Eq. 51 safety; the PR 2 regression).
                         Removing the pmax in `_safe_mu_local` makes mu
                         vary over the agent axes and this rule fire.
  varying-gate           no lax.cond/switch selector may vary over a mesh
                         axis, even when every branch issues identical
                         collectives (which keeps cond-collective-parity
                         silent): devices following different gossip
                         gates in the same step drift deterministically
                         apart — schedule gates must derive from the
                         replicated scan counter.
  quant-scale-pairing    every int8 payload ppermute must be paired, in
                         the same jaxpr body, with a non-int8 (scale)
                         ppermute under the IDENTICAL (axis, permutation)
                         table.  Quantization scales legitimately vary
                         per sender — soundness requires the scale to
                         travel with its payload so receivers dequantize
                         with the sender's scale, never their own.
  push-weight-pairing    push-family programs only: every non-scalar
                         (payload) ppermute must be paired, in the same
                         jaxpr body, with a SCALAR ppermute under the
                         identical (axis, permutation) table — the
                         ratio-consensus weight channel.  A payload hop
                         that leaves its weight behind breaks mass
                         conservation: the v/w ratio divides a mixed
                         numerator by an unmixed denominator and the
                         consensus silently biases toward the stranded
                         rank (the whole point of push-sum — correctness
                         on row-stochastic-only combiners — is lost).

Why out-spec ⊆ non-varying ⇒ cross-rank determinism: the varying set is a
may-analysis — an axis absent from a value's varying set means NO
equation path can make devices along that axis disagree (inputs declared
replicated stay replicated through pure ops; only axis_index/ppermute
introduce variation; only reductions remove it).  If every axis an
out_spec omits is absent from the output's varying set, the per-device
bodies are extensionally equal along those axes, so the unchecked
replication promise holds on every iterate — not just on the meshes CI
can build, but on any mesh shape.
"""

from __future__ import annotations

import pathlib
from typing import Dict, Iterable, List, Sequence, Tuple

from tools.analyze.report import Finding
from tools.analyze.walker import REPO
from tools.analyze.rules_jaxpr import (
    _ENGINE_FILE,
    _JaxprChecker,
    _as_names,
    _sub_jaxpr,
)

RULES = (
    "out-spec-replication",
    "step-size-replication",
    "varying-gate",
    "quant-scale-pairing",
    "push-weight-pairing",
)


def _spec_axes(spec: Iterable) -> frozenset:
    """Mesh axes a PartitionSpec-style tuple mentions (entries are None,
    an axis name, or a tuple of axis names)."""
    axes = set()
    for entry in spec:
        axes.update(_as_names(entry))
    return frozenset(axes)


class _ReplicationChecker(_JaxprChecker):
    """`_JaxprChecker` with the layer-3 varying-gate check, reporting ONLY
    this module's rules (the base rules already run in rules_jaxpr — a
    second emission here would double-report every layer-1 finding)."""

    def _finding(self, rule, eqn, message, record) -> None:
        if rule not in RULES:
            return
        super()._finding(rule, eqn, message, record)

    def _cond(self, eqn, env_v, env_p, record, in_scan, bytes_acc) -> None:
        idx_vary = self._read(env_v, eqn.invars[0], frozenset())
        if idx_vary:
            self._finding(
                "varying-gate", eqn,
                f"cond/switch selector varies over mesh axes "
                f"{sorted(idx_vary)}: even with collective-parity intact, "
                f"devices follow different gossip gates in the same step "
                f"and their iterates drift deterministically apart — "
                f"derive schedule gates from the replicated scan counter "
                f"(lax.rem(t, k)), never from axis_index or sharded data",
                record,
            )
        super()._cond(eqn, env_v, env_p, record, in_scan, bytes_acc)


def _iter_bodies(jaxpr):
    """Yield every jaxpr body reachable from `jaxpr` (itself, scan/cond/
    while/pjit sub-jaxprs, recursively).  A "body" is the pairing scope
    for quant-scale-pairing: the engine quantizes and ships payload+scale
    inside one gossip round, i.e. one body."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        params = eqn.params
        subs = []
        if eqn.primitive.name == "cond":
            subs = [b.jaxpr for b in params["branches"]]
        elif eqn.primitive.name == "while":
            subs = [params[k].jaxpr for k in ("cond_jaxpr", "body_jaxpr")
                    if params.get(k) is not None]
        else:
            pair = _sub_jaxpr(params)
            if pair is not None:
                subs = [pair[0]]
        for sub in subs:
            yield from _iter_bodies(sub)


def check_quant_pairing(
    closed_jaxpr,
    *,
    label: str,
    file: str = _ENGINE_FILE,
    root: pathlib.Path = REPO,
) -> List[Finding]:
    """Every int8 ppermute must have a same-body non-int8 ppermute with
    the identical (axis names, permutation table)."""
    findings: List[Finding] = []
    checker = _JaxprChecker({}, file=file, root=root)
    for body in _iter_bodies(closed_jaxpr.jaxpr):
        perms = []  # (is_int8, axes, perm, eqn)
        for eqn in body.eqns:
            if eqn.primitive.name != "ppermute":
                continue
            axes = tuple(_as_names(eqn.params.get("axis_name")))
            perm = tuple(tuple(p) for p in eqn.params["perm"])
            dtype = str(eqn.invars[0].aval.dtype)
            perms.append((dtype == "int8", axes, perm, eqn))
        for is_q, axes, perm, eqn in perms:
            if not is_q:
                continue
            paired = any(
                (not q2) and axes2 == axes and perm2 == perm
                for q2, axes2, perm2, _ in perms
            )
            if not paired:
                f, line = checker._where(eqn)
                findings.append(Finding(
                    "quant-scale-pairing", f, line,
                    f"[{label}] int8 payload ppermute over axes "
                    f"{list(axes)} has no same-body scale ppermute under "
                    f"the identical permutation {perm} — receivers would "
                    f"dequantize a neighbor's int8 payload with the wrong "
                    f"(local or differently-routed) scale, corrupting the "
                    f"gossip combine silently",
                ))
    return findings


def check_push_pairing(
    closed_jaxpr,
    *,
    label: str,
    file: str = _ENGINE_FILE,
    root: pathlib.Path = REPO,
) -> List[Finding]:
    """Push-sum soundness (push-family programs only): every non-scalar
    payload ppermute must have a same-body SCALAR ppermute with the
    identical (axis names, permutation table) — the weight channel that
    makes the v/w ratio consensus correct on row-stochastic-only A."""
    findings: List[Finding] = []
    checker = _JaxprChecker({}, file=file, root=root)
    for body in _iter_bodies(closed_jaxpr.jaxpr):
        perms = []  # (ndim, axes, perm, eqn)
        for eqn in body.eqns:
            if eqn.primitive.name != "ppermute":
                continue
            axes = tuple(_as_names(eqn.params.get("axis_name")))
            perm = tuple(tuple(p) for p in eqn.params["perm"])
            perms.append((eqn.invars[0].aval.ndim, axes, perm, eqn))
        for ndim, axes, perm, eqn in perms:
            if ndim == 0:
                continue
            paired = any(
                nd2 == 0 and axes2 == axes and perm2 == perm
                for nd2, axes2, perm2, _ in perms
            )
            if not paired:
                f, line = checker._where(eqn)
                findings.append(Finding(
                    "push-weight-pairing", f, line,
                    f"[{label}] push-sum payload ppermute over axes "
                    f"{list(axes)} has no same-body scalar weight ppermute "
                    f"under the identical permutation {perm} — the v/w "
                    f"ratio would divide a mixed numerator by an unmixed "
                    f"denominator, breaking mass conservation and silently "
                    f"biasing the consensus on any row-stochastic-only "
                    f"combiner",
                ))
    return findings


def check_program(
    closed_jaxpr,
    axis_sizes: Dict[str, int],
    *,
    out_meta: Sequence,
    in_varying: Sequence,
    agent_axes: Sequence[str],
    program: str,
    label: str,
    file: str = _ENGINE_FILE,
    root: pathlib.Path = REPO,
    push_family: bool = False,
) -> List[Finding]:
    """Verify one traced program against its replication contract:
    `out_meta` is one `OutSpecInfo`-shaped object (.name/.spec/.consensus)
    per jaxpr output.  Returns this module's findings only."""
    findings: List[Finding] = []
    mesh_axes = frozenset(axis_sizes)
    agents = frozenset(agent_axes)

    checker = _ReplicationChecker(axis_sizes, file=file, root=root)
    checker.run(closed_jaxpr, in_varying)
    findings.extend(checker.findings)

    line = 1
    for i, meta in enumerate(out_meta):
        if i >= len(checker.out_varying):
            break
        varying = checker.out_varying[i]
        declared_replicated = mesh_axes - _spec_axes(meta.spec)
        if meta.consensus:
            declared_replicated -= agents
        violated = varying & declared_replicated
        if violated:
            findings.append(Finding(
                "out-spec-replication", file, line,
                f"[{label}:{program}] output {meta.name!r} declares axes "
                f"{sorted(declared_replicated)} replicated in its "
                f"out_spec, but the body cannot be proved non-varying "
                f"over {sorted(violated)} — with check_vma=False the "
                f"compiled program ships device-dependent values as if "
                f"replicated; reduce (psum/pmax) over the offending axes "
                f"or shard the output",
            ))
        if program == "mu":
            drift = varying & agents
            if drift:
                findings.append(Finding(
                    "step-size-replication", file, line,
                    f"[{label}:mu] the adaptive step size varies over "
                    f"agent axes {sorted(drift)} — every agent must step "
                    f"with the one mu safe for the worst shard "
                    f"(pmax/psum the local curvature bound over the full "
                    f"agent network, as _safe_mu_local does), or the "
                    f"gossip iterates silently diverge (the PR 2 bug)",
                ))

    findings.extend(check_quant_pairing(
        closed_jaxpr, label=f"{label}:{program}", file=file, root=root
    ))
    if push_family:
        findings.extend(check_push_pairing(
            closed_jaxpr, label=f"{label}:{program}", file=file, root=root
        ))
    return findings


def run(root: pathlib.Path = REPO) -> List[Finding]:
    """Prove the replication contract of every `mode_trace_cases()` entry:
    each case's `programs` tuple is traced via `abstract_trace(...,
    program=p)` and checked against the coder's `out_spec_meta`."""
    from repro.core import distributed as D

    findings: List[Finding] = []
    for case in D.mode_trace_cases():
        sizes = dict(case.axis_sizes)
        for program in case.programs:
            coder, jaxpr = D.abstract_trace(
                case.cfg, case.axis_sizes, batch=8, m=32, program=program
            )
            agent_axes = frozenset(coder._agent_axes)
            data_axes = frozenset(case.cfg.data_axes)
            if program == "mu":
                in_varying = [agent_axes]
            elif program == "fit":
                # W, then the solve's duals nu and y (per agent and per
                # data shard), then mu_w
                duals = agent_axes | data_axes
                in_varying = [agent_axes, duals, duals, frozenset()]
            else:
                in_varying = [agent_axes, data_axes, frozenset()]
            meta = coder.out_spec_meta[program]
            findings.extend(check_program(
                jaxpr, sizes,
                out_meta=meta, in_varying=in_varying,
                agent_axes=coder._agent_axes, program=program,
                label=case.name, root=root,
                push_family=(
                    D.MODE_REGISTRY[case.cfg.mode].family == "push"
                ),
            ))
    return findings
