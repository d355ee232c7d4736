"""Jaxpr-level protocol verification (requires jax; no devices).

The engine's collective protocols are verified ABSTRACTLY: every mode in
`repro.core.distributed.MODE_REGISTRY` is traced on a device-free
AbstractMesh (`distributed.abstract_trace`) and the resulting per-device
jaxpr is interpreted by `_JaxprChecker`, which tracks, per value, the set
of mesh axes the value VARIES over (differs across devices along).  The
checks:

  cond-collective-parity  if a lax.cond/switch SELECTOR varies over mesh
                          axes, devices can take different branches in the
                          same step — so all branches must issue the
                          identical ordered collective signature
                          (primitive, axis names, permutation table), or
                          some device blocks in a rendezvous its peers
                          never enter: deadlock.  Replicated selectors
                          (the scan counter) may pick differing branches
                          freely — all devices switch together.
  branch-structure        all branches of a cond must produce the same
                          output avals/pytree (jax enforces the pytree at
                          trace time; `trace_check` converts that error
                          into a finding, and the interpreter re-checks
                          avals on successfully traced programs).
  ppermute-table          every ppermute permutation must be a true
                          bijection on [0, axis_size): a duplicated or
                          missing source/destination silently zero-fills
                          or drops a message at run time — jax does NOT
                          reject it at trace time.
  wire-bytes              bytes shipped per solve iteration, counted
                          directly off the collectives inside the scan
                          body (ppermute = operand bytes; psum/pmax/pmin
                          = 2x operand: reduce-scatter + all-gather;
                          cond branches weighted by firing fraction read
                          from the `rem`-based gate), must equal the
                          engine's analytic `wire_bytes_per_iter` — the
                          numbers benchmarks/gossip_modes.py reports.
  trace-coverage          every MODE_REGISTRY mode must appear in
                          `mode_trace_cases()`, so adding a mode without
                          wiring it into the verifier fails CI.

Firing fractions: the engine gates strided/time-varying hops on
`lax.rem(t, k)` where t is the scan counter (always >= 0), which traces
to a single `rem` equation with a literal divisor.  The interpreter
chases a cond's selector back through convert_element_type / clamp / eq
to that `rem`: `eq(rem(t, k), 0)` fires the true branch 1/k of
iterations; a switch on `rem(t, P)` over P branches fires each 1/P.
"""

from __future__ import annotations

import math
import pathlib
from typing import Dict, List, Optional, Sequence, Tuple

from tools.analyze.report import Finding
from tools.analyze.walker import REPO

RULES = (
    "cond-collective-parity", "branch-structure", "ppermute-table",
    "wire-bytes", "trace-coverage",
)

# The engine file jaxpr findings anchor to when an equation has no usable
# source frame.
_ENGINE_FILE = "src/repro/core/distributed.py"

_REDUCE_PRIMS = ("psum", "pmax", "pmin")
_SUBJAXPR_KEYS = ("jaxpr", "call_jaxpr", "fun_jaxpr")


def _is_literal(atom) -> bool:
    return hasattr(atom, "val")


def _nbytes(aval) -> int:
    import numpy as np

    return int(aval.size) * int(np.dtype(aval.dtype).itemsize)


def _as_names(axes) -> Tuple[str, ...]:
    """Normalize an axis_name / axes param to a tuple of axis-name strings
    (positional-axis ints are dropped)."""
    if axes is None:
        return ()
    if isinstance(axes, (str,)):
        return (axes,)
    try:
        return tuple(a for a in axes if isinstance(a, str))
    except TypeError:
        return ()


def _sub_jaxpr(params):
    """The (inner open jaxpr, consts) of a call-like primitive, or None."""
    for key in _SUBJAXPR_KEYS:
        sub = params.get(key)
        if sub is None:
            continue
        if hasattr(sub, "jaxpr"):  # ClosedJaxpr
            return sub.jaxpr, sub.consts
        return sub, []  # open Jaxpr (remat)
    return None


def signature(jaxpr) -> Tuple:
    """The ordered collective signature of an open jaxpr: what a device
    RUNNING this program commits to rendezvous on.  Sub-programs of
    call-like primitives are inlined; nested conds contribute a
    structured ('cond', (branch signatures...)) entry."""
    sig: List = []
    for eqn in jaxpr.eqns:
        p = eqn.primitive.name
        params = eqn.params
        if p == "ppermute":
            sig.append((
                "ppermute",
                _as_names(params.get("axis_name")),
                tuple(sorted(tuple(pair) for pair in params["perm"])),
            ))
        elif p in _REDUCE_PRIMS:
            axes = _as_names(params.get("axes") or params.get("axis_name"))
            if axes:
                sig.append((p, tuple(sorted(axes))))
        elif p == "cond":
            sig.append((
                "cond",
                tuple(signature(b.jaxpr) for b in params["branches"]),
            ))
        elif p == "scan":
            sig.append(("scan", signature(params["jaxpr"].jaxpr)))
        else:
            sub = _sub_jaxpr(params)
            if sub is not None:
                sig.extend(signature(sub[0]))
    return tuple(sig)


class _JaxprChecker:
    """Abstract interpreter over one traced engine body.

    Per value it tracks (a) the frozenset of mesh axes the value varies
    over and (b) a provenance tag for gate selectors (('rem', k) /
    ('eq0', k)).  Findings accumulate in `self.findings`; stride-averaged
    wire bytes (counted only inside scan bodies — per-iteration cost) in
    `self.bytes_by_axis`."""

    def __init__(
        self,
        axis_sizes: Dict[str, int],
        file: str = _ENGINE_FILE,
        root: pathlib.Path = REPO,
    ):
        self.axis_sizes = dict(axis_sizes)
        self.file = file
        self.root = pathlib.Path(root)
        self.findings: List[Finding] = []
        self.bytes_by_axis: Dict[str, float] = {}
        # per-output varying-axes sets of the last run() — the replication
        # layer reads these to prove out-spec contracts
        self.out_varying: List[frozenset] = []

    # -- helpers ----------------------------------------------------------

    def _where(self, eqn) -> Tuple[str, int]:
        """(repo-relative file, line) of an equation via its user source
        frame; falls back to (self.file, 1)."""
        try:
            from jax._src import source_info_util

            frame = source_info_util.user_frame(eqn.source_info)
            if frame is not None:
                fn = pathlib.Path(frame.file_name).resolve()
                line = int(
                    getattr(frame, "start_line", 0)
                    or getattr(frame, "line_num", 0) or 1
                )
                try:
                    return fn.relative_to(self.root).as_posix(), line
                except ValueError:
                    return self.file, line
        except Exception:
            pass
        return self.file, 1

    def _finding(self, rule: str, eqn, message: str, record: bool) -> None:
        if not record:
            return
        f, line = self._where(eqn)
        self.findings.append(Finding(rule, f, line, message))

    @staticmethod
    def _read(env, atom, default):
        if _is_literal(atom):
            return default
        return env.get(atom, default)

    # -- interpreter ------------------------------------------------------

    def run(self, closed_jaxpr, in_varying: Sequence = ()) -> None:
        """Interpret a ClosedJaxpr.  `in_varying` gives, per input, the
        mesh axes the caller shards that input over (e.g. W_loc varies
        over the agent axes, x_loc over the data axes, t0 over none)."""
        jaxpr = closed_jaxpr.jaxpr
        vary = [frozenset(v) for v in in_varying]
        vary += [frozenset()] * (len(jaxpr.invars) - len(vary))
        self.out_varying, _ = self._interp(
            jaxpr, vary, [None] * len(jaxpr.invars),
            record=True, in_scan=False, bytes_acc=self.bytes_by_axis,
        )

    def _interp(
        self,
        jaxpr,
        in_vary: Sequence[frozenset],
        in_prov: Sequence,
        *,
        record: bool,
        in_scan: bool,
        bytes_acc: Dict[str, float],
    ) -> Tuple[List[frozenset], List]:
        env_v: Dict = {v: frozenset() for v in jaxpr.constvars}
        env_p: Dict = {}
        for var, vy, pv in zip(jaxpr.invars, in_vary, in_prov):
            env_v[var] = frozenset(vy)
            if pv is not None:
                env_p[var] = pv

        for eqn in jaxpr.eqns:
            self._eqn(eqn, env_v, env_p, record, in_scan, bytes_acc)

        outs_v = [self._read(env_v, a, frozenset()) for a in jaxpr.outvars]
        outs_p = [self._read(env_p, a, None) for a in jaxpr.outvars]
        return outs_v, outs_p

    def _eqn(self, eqn, env_v, env_p, record, in_scan, bytes_acc) -> None:
        p = eqn.primitive.name
        params = eqn.params
        ivs = [self._read(env_v, a, frozenset()) for a in eqn.invars]
        union = frozenset().union(*ivs) if ivs else frozenset()

        if p == "axis_index":
            env_v[eqn.outvars[0]] = frozenset(_as_names(params.get("axis_name")))
            return

        if p == "ppermute":
            axes = _as_names(params.get("axis_name"))
            perm = tuple(tuple(pair) for pair in params["perm"])
            for ax in axes:
                n = self.axis_sizes.get(ax)
                if n is not None:
                    srcs = [s for s, _ in perm]
                    dsts = [d for _, d in perm]
                    if (
                        sorted(srcs) != list(range(n))
                        or sorted(dsts) != list(range(n))
                    ):
                        self._finding(
                            "ppermute-table", eqn,
                            f"ppermute table {perm} over axis {ax!r} "
                            f"(size {n}) is not a permutation: each of "
                            f"0..{n - 1} must appear exactly once as source "
                            f"and destination — jax silently zero-fills "
                            f"missing destinations and drops duplicated "
                            f"ones at run time",
                            record,
                        )
                if in_scan:
                    bytes_acc[ax] = (
                        bytes_acc.get(ax, 0.0) + _nbytes(eqn.invars[0].aval)
                    )
            env_v[eqn.outvars[0]] = union | frozenset(axes)
            return

        if p in _REDUCE_PRIMS:
            axes = frozenset(_as_names(params.get("axes")))
            # all-reduce = reduce-scatter + all-gather: 2x operand bytes
            for iv, ov in zip(eqn.invars, eqn.outvars):
                if in_scan:
                    for ax in axes:
                        bytes_acc[ax] = (
                            bytes_acc.get(ax, 0.0) + 2 * _nbytes(iv.aval)
                        )
                env_v[ov] = self._read(env_v, iv, frozenset()) - axes
            return

        if p == "scan":
            self._scan(eqn, env_v, env_p, record, in_scan, bytes_acc)
            return

        if p == "cond":
            self._cond(eqn, env_v, env_p, record, in_scan, bytes_acc)
            return

        if p == "while":
            # No engine program uses while; interpret both sub-jaxprs for
            # table checks but refuse byte accounting (unknown trip count).
            for key in ("cond_jaxpr", "body_jaxpr"):
                sub = params.get(key)
                if sub is not None:
                    throwaway: Dict[str, float] = {}
                    self._interp(
                        sub.jaxpr,
                        [union] * len(sub.jaxpr.invars),
                        [None] * len(sub.jaxpr.invars),
                        record=record, in_scan=False, bytes_acc=throwaway,
                    )
            for ov in eqn.outvars:
                env_v[ov] = union
            return

        sub = _sub_jaxpr(params)
        if sub is not None:
            inner, _ = sub
            outs_v, outs_p = self._interp(
                inner,
                ivs[len(ivs) - len(inner.invars):],
                [self._read(env_p, a, None) for a in eqn.invars][
                    len(ivs) - len(inner.invars):
                ],
                record=record, in_scan=in_scan, bytes_acc=bytes_acc,
            )
            for ov, vy, pv in zip(eqn.outvars, outs_v, outs_p):
                env_v[ov] = vy
                if pv is not None:
                    env_p[ov] = pv
            return

        # provenance for gate selectors
        if p == "rem" and len(eqn.invars) == 2 and _is_literal(eqn.invars[1]):
            try:
                env_p[eqn.outvars[0]] = ("rem", int(eqn.invars[1].val))
            except (TypeError, ValueError):
                pass
        elif p == "eq" and len(eqn.invars) == 2:
            for a, b in ((eqn.invars[0], eqn.invars[1]),
                         (eqn.invars[1], eqn.invars[0])):
                pv = self._read(env_p, a, None)
                if (
                    pv is not None and pv[0] == "rem"
                    and _is_literal(b) and int(b.val) == 0
                ):
                    env_p[eqn.outvars[0]] = ("eq0", pv[1])
                    break
        elif p == "convert_element_type":
            pv = self._read(env_p, eqn.invars[0], None)
            if pv is not None:
                env_p[eqn.outvars[0]] = pv
        elif p == "clamp" and len(eqn.invars) == 3:
            pv = self._read(env_p, eqn.invars[1], None)
            lo = eqn.invars[0]
            if pv is not None and _is_literal(lo) and int(lo.val) == 0:
                env_p[eqn.outvars[0]] = pv

        for ov in eqn.outvars:
            env_v[ov] = union

    def _scan(self, eqn, env_v, env_p, record, in_scan, bytes_acc) -> None:
        params = eqn.params
        sub = params["jaxpr"].jaxpr
        nc, ncar = params["num_consts"], params["num_carry"]
        ivs = [self._read(env_v, a, frozenset()) for a in eqn.invars]
        ips = [self._read(env_p, a, None) for a in eqn.invars]
        consts_v, carry_v, xs_v = ivs[:nc], list(ivs[nc:nc + ncar]), ivs[nc + ncar:]

        # fixpoint on the carry's varying axes: silent passes (no findings,
        # no bytes) until stable, then ONE real pass — body bytes count
        # once, i.e. per iteration.
        for _ in range(32):
            throwaway: Dict[str, float] = {}
            outs_v, _ = self._interp(
                sub, consts_v + carry_v + xs_v, ips,
                record=False, in_scan=True, bytes_acc=throwaway,
            )
            new_carry = [c | o for c, o in zip(carry_v, outs_v[:ncar])]
            if new_carry == carry_v:
                break
            carry_v = new_carry
        outs_v, outs_p = self._interp(
            sub, consts_v + carry_v + xs_v, ips,
            record=record, in_scan=True, bytes_acc=bytes_acc,
        )
        for ov, vy, pv in zip(eqn.outvars, outs_v, outs_p):
            env_v[ov] = vy
            if pv is not None:
                env_p[ov] = pv

    def _cond(self, eqn, env_v, env_p, record, in_scan, bytes_acc) -> None:
        params = eqn.params
        branches = params["branches"]
        idx = eqn.invars[0]
        idx_vary = self._read(env_v, idx, frozenset())
        idx_prov = self._read(env_p, idx, None)
        op_v = [self._read(env_v, a, frozenset()) for a in eqn.invars[1:]]
        op_p = [self._read(env_p, a, None) for a in eqn.invars[1:]]

        # branch-structure: identical output avals across branches
        avals = [tuple(map(str, b.out_avals)) for b in branches]
        if len(set(avals)) > 1:
            self._finding(
                "branch-structure", eqn,
                f"cond branches disagree on output structure: "
                f"{' vs '.join(sorted(set(map(str, avals))))} — all "
                f"branches must produce the same avals/pytree",
                record,
            )

        # cond-collective-parity: a device-varying selector with differing
        # collective signatures = rendezvous deadlock
        sigs = [signature(b.jaxpr) for b in branches]
        if idx_vary and len(set(sigs)) > 1:
            self._finding(
                "cond-collective-parity", eqn,
                f"cond selector varies over mesh axes "
                f"{sorted(idx_vary)} but its branches issue DIFFERENT "
                f"collective signatures — devices taking different "
                f"branches would block in rendezvous their peers never "
                f"enter (deadlock).  Either make every branch issue the "
                f"identical ordered collectives, or derive the selector "
                f"from a replicated value (the scan counter)",
                record,
            )

        # interpret each branch with its own byte accumulator, then merge
        # weighted by firing fraction
        branch_bytes: List[Dict[str, float]] = []
        branch_outs: List[List[frozenset]] = []
        for b in branches:
            acc: Dict[str, float] = {}
            outs_v, _ = self._interp(
                b.jaxpr, op_v, op_p,
                record=record, in_scan=in_scan, bytes_acc=acc,
            )
            branch_bytes.append(acc)
            branch_outs.append(outs_v)

        if in_scan and any(branch_bytes):
            if all(b == branch_bytes[0] for b in branch_bytes[1:]):
                weights: Optional[List[float]] = [1.0] + [0.0] * (len(branches) - 1)
            else:
                weights = self._firing_fractions(idx_prov, len(branches))
            if weights is None:
                self._finding(
                    "wire-bytes", eqn,
                    "cond branches ship different byte counts but the "
                    "selector's firing fraction is not statically "
                    "readable — gate strided/time-varying hops on "
                    "lax.rem(t, k) so the stride is visible in the jaxpr",
                    record,
                )
            else:
                for w, acc in zip(weights, branch_bytes):
                    for ax, v in acc.items():
                        bytes_acc[ax] = bytes_acc.get(ax, 0.0) + w * v

        for i, ov in enumerate(eqn.outvars):
            vy = frozenset(idx_vary)
            for outs in branch_outs:
                vy |= outs[i]
            env_v[ov] = vy

    @staticmethod
    def _firing_fractions(prov, n_branches: int) -> Optional[List[float]]:
        """Per-branch firing fractions from the selector's provenance:
        eq(rem(t, k), 0) -> (1 - 1/k, 1/k) for (false, true); a switch on
        rem(t, P) over P branches -> uniform 1/P."""
        if prov is None:
            return None
        kind, k = prov
        if kind == "eq0" and n_branches == 2 and k > 0:
            return [1.0 - 1.0 / k, 1.0 / k]
        if kind == "rem" and k == n_branches and k > 0:
            return [1.0 / k] * k
        return None


def check_jaxpr(
    closed_jaxpr,
    axis_sizes: Dict[str, int],
    *,
    in_varying: Sequence = (),
    file: str = _ENGINE_FILE,
    root: pathlib.Path = REPO,
) -> _JaxprChecker:
    """Run the full jaxpr verification over one traced program; returns the
    checker carrying `.findings` and `.bytes_by_axis`."""
    checker = _JaxprChecker(axis_sizes, file=file, root=root)
    checker.run(closed_jaxpr, in_varying)
    return checker


def trace_check(fn, args, axis_env, *, file: str, root: pathlib.Path = REPO):
    """`jax.make_jaxpr` with cond pytree-mismatch errors converted into a
    branch-structure finding: returns (closed_jaxpr | None, findings)."""
    import jax

    try:
        return jax.make_jaxpr(fn, axis_env=list(axis_env))(*args), []
    except TypeError as e:
        msg = str(e)
        if "same type structure" in msg or "same pytree structure" in msg:
            return None, [Finding(
                "branch-structure", file, 1,
                f"cond branches produce mismatched pytrees (trace-time): "
                f"{msg.splitlines()[0][:200]}",
            )]
        raise


def run(root: pathlib.Path = REPO) -> List[Finding]:
    """The repo's jaxpr verification matrix: every `mode_trace_cases()`
    case, solve AND fit bodies, plus MODE_REGISTRY trace coverage and the
    wire-byte cross-check of the solve body against the engine's analytic
    `wire_bytes_per_iter` (the numbers benchmarks/gossip_modes.py
    reports)."""
    from repro.core import distributed as D

    findings: List[Finding] = []
    cases = D.mode_trace_cases()
    covered = {c.cfg.mode for c in cases}
    for mode in D.MODES:
        if mode not in covered:
            findings.append(Finding(
                "trace-coverage", _ENGINE_FILE, 1,
                f"MODE_REGISTRY mode {mode!r} has no entry in "
                f"mode_trace_cases() — every mode must be abstractly "
                f"traced and protocol-checked",
            ))

    batch, m = 8, 32
    for case in cases:
        sizes = dict(case.axis_sizes)
        for fit in (False, True):
            coder, jaxpr = D.abstract_trace(
                case.cfg, case.axis_sizes, batch=batch, m=m, fit=fit
            )
            agent_axes = frozenset(coder._agent_axes)
            data_axes = frozenset(case.cfg.data_axes)
            in_varying = (
                [agent_axes, agent_axes | data_axes, agent_axes | data_axes,
                 frozenset()] if fit
                else [agent_axes, data_axes, frozenset()]
            )
            checker = check_jaxpr(
                jaxpr, sizes, in_varying=in_varying, root=root
            )
            findings.extend(checker.findings)
            if fit:
                continue
            # wire-byte cross-check (solve body only: the fit body is the
            # atom update, one data-axis psum and no iterations)
            b_loc = batch // int(
                math.prod(sizes[a] for a in case.cfg.data_axes)
            )
            expected = dict(coder.wire_bytes_per_iter(b_loc, m))
            measured = checker.bytes_by_axis
            for ax in sorted(set(expected) | set(measured)):
                e = float(expected.get(ax, 0.0))
                got = float(measured.get(ax, 0.0))
                if not math.isclose(e, got, rel_tol=1e-6, abs_tol=0.25):
                    findings.append(Finding(
                        "wire-bytes", _ENGINE_FILE, 1,
                        f"[{case.name}] axis {ax!r}: analytic "
                        f"wire_bytes_per_iter says {e} B/iter but the "
                        f"traced solve body ships {got} B/iter — the "
                        f"engine's byte accounting and its compiled "
                        f"collectives have drifted apart",
                    ))
    return findings
