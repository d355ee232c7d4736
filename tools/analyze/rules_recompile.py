"""Recompile-budget verification (analyze layer 3).

The ROADMAP's "ONE compiled program" invariant — every serving iterate,
schedule offset, and time-varying combiner stays inside a single XLA
executable — is enforced today only by convention (t0 traced not static,
dtypes pinned at jit boundaries).  This module enforces it two ways:

Dynamic (`recompile-budget`; requires jax WITH enough devices): every
`mode_trace_cases()` entry is built on a real debug mesh, its jitted
solve and fit are each executed twice with varied traced inputs (data
values, step size, and the schedule offset t0) and the jit compile cache
must hold exactly ONE entry afterwards — a second entry means something
leaked a Python value into the trace and every serving micro-batch would
recompile.  The same pass AOT-compiles each solve once and records its
optimized-HLO FLOPs / collective bytes via `launch/hlo_cost`, which the
cost-budget gate (rules_budget) pins against `budgets.json`.  When fewer
devices are visible than the largest trace mesh needs, the dynamic pass
is skipped (the CLI forces 8 host devices; see __main__).

Static (stdlib AST over `src/repro/{core,runtime}`): the retrace-hazard
patterns that produced real bugs in jax engines —

  weak-literal-carry   a Python numeric literal inside a `lax.scan` init:
                       the weak-typed carry meets the strongly-typed body
                       output and jax re-promotes (or retraces) per call
                       context — scans must start from explicitly-dtyped
                       arrays.
  asarray-dtype        `jnp.asarray(x)` without an explicit dtype in
                       engine code: the result dtype depends on the input
                       host type and the enable_x64 flag, so the same
                       call site can hand different-dtype (hence
                       differently-compiled) values across configs and
                       callers — every engine jit boundary pins dtypes.
  jit-cache-discipline `jax.jit(...)` called immediately (its cache dies
                       with the expression) or created inside a loop
                       (a fresh cache, i.e. a fresh compile, per
                       iteration).  Jits belong at module scope or in
                       `__init__`, compiled once and reused.
  scalar-closure       a lambda/local function handed to lax.scan / cond
                       / switch / jax.jit closing over a name bound from
                       `float(...)` / `int(...)` / `.item()`: the Python
                       scalar is baked into the trace — silently stale if
                       the function is cached, a recompile per value if
                       it is not (and `.item()` forces a device sync).
"""

from __future__ import annotations

import ast
import pathlib
from typing import Dict, List, Optional, Tuple

from tools.analyze.report import Finding
from tools.analyze.walker import REPO, iter_py_files, parse, rel

AST_RULES = (
    "weak-literal-carry",
    "asarray-dtype",
    "jit-cache-discipline",
    "scalar-closure",
)
DYNAMIC_RULES = ("recompile-budget",)
RULES = AST_RULES + DYNAMIC_RULES

_SUBDIRS = ("src/repro/core", "src/repro/runtime")

# shapes of the dynamic double-call probe (tiny on purpose: CI compiles
# every registry mode in the static-analysis lane's 5-minute budget)
_PROBE_M, _PROBE_KB, _PROBE_B = 32, 4, 8


# ---------------------------------------------------------------------------
# stdlib-AST rules
# ---------------------------------------------------------------------------


def _dotted(node) -> Tuple[str, ...]:
    """('jax', 'lax', 'scan')-style name chain of an expression, () if it
    is not a plain dotted name."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return tuple(reversed(parts))
    return ()


def _is_scan(call: ast.Call) -> bool:
    d = _dotted(call.func)
    return len(d) >= 2 and d[-2:] == ("lax", "scan")


def _is_hot_consumer(call: ast.Call) -> bool:
    d = _dotted(call.func)
    if not d:
        return False
    if d[-1] in ("scan", "cond", "switch") and "lax" in d[:-1]:
        return True
    return d[-2:] == ("jax", "jit") or d == ("jit",)


def _literal_in_init(node) -> Optional[ast.AST]:
    """A bare numeric literal in a scan-init expression (descending only
    through tuple/list displays — constants inside nested calls like
    `jnp.zeros((2,))` are shape arguments, not carries)."""
    if isinstance(node, ast.Constant) and isinstance(node.value, (int, float)):
        return node
    if isinstance(node, (ast.Tuple, ast.List)):
        for elt in node.elts:
            hit = _literal_in_init(elt)
            if hit is not None:
                return hit
    if isinstance(node, ast.UnaryOp):
        return _literal_in_init(node.operand)
    return None


def check_weak_literal_carry(path: pathlib.Path, root: pathlib.Path) -> List[Finding]:
    """`lax.scan(f, <python literal>, ...)` — weak-typed init carries."""
    findings: List[Finding] = []
    for node in ast.walk(parse(path)):
        if not (isinstance(node, ast.Call) and _is_scan(node)):
            continue
        init = None
        if len(node.args) >= 2:
            init = node.args[1]
        else:
            for kw in node.keywords:
                if kw.arg == "init":
                    init = kw.value
        if init is None:
            continue
        hit = _literal_in_init(init)
        if hit is not None:
            findings.append(Finding(
                "weak-literal-carry", rel(path, root), hit.lineno,
                "lax.scan init contains a bare Python literal: the "
                "weak-typed carry meets the body's strongly-typed output "
                "and jax re-promotes/retraces per call context — start "
                "the scan from an explicitly-dtyped array "
                "(jnp.asarray(v, dtype) / jnp.zeros(..., dtype))",
            ))
    return findings


def check_asarray_dtype(path: pathlib.Path, root: pathlib.Path) -> List[Finding]:
    """`jnp.asarray(x)` with no dtype in engine code."""
    findings: List[Finding] = []
    for node in ast.walk(parse(path)):
        if not isinstance(node, ast.Call):
            continue
        d = _dotted(node.func)
        if d not in (("jnp", "asarray"), ("jax", "numpy", "asarray")):
            continue
        has_dtype = len(node.args) >= 2 or any(
            kw.arg == "dtype" for kw in node.keywords
        )
        if not has_dtype:
            findings.append(Finding(
                "asarray-dtype", rel(path, root), node.lineno,
                "jnp.asarray without an explicit dtype: the result dtype "
                "follows the input's host type and the enable_x64 flag, "
                "so this jit boundary can hand different-dtype values "
                "across callers/configs — a silent recompile (and "
                "numerics fork) per dtype.  Pin it: "
                "jnp.asarray(x, jnp.float32) / (x, W.dtype)",
            ))
    return findings


def check_jit_cache_discipline(path: pathlib.Path, root: pathlib.Path) -> List[Finding]:
    """jax.jit called immediately, or created inside a loop body."""
    findings: List[Finding] = []

    def is_jit(call) -> bool:
        return isinstance(call, ast.Call) and (
            _dotted(call.func) == ("jax", "jit") or _dotted(call.func) == ("jit",)
        )

    tree = parse(path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and is_jit(node.func):
            findings.append(Finding(
                "jit-cache-discipline", rel(path, root), node.lineno,
                "jax.jit(...) called immediately: the compile cache dies "
                "with the expression, so EVERY call re-traces and "
                "re-compiles — bind the jitted function once (module "
                "scope or __init__) and reuse it",
            ))
        if isinstance(node, (ast.For, ast.While)):
            for sub in ast.walk(node):
                if sub is node:
                    continue
                if is_jit(sub):
                    findings.append(Finding(
                        "jit-cache-discipline", rel(path, root), sub.lineno,
                        "jax.jit(...) constructed inside a loop: each "
                        "iteration builds a fresh jitted function with an "
                        "empty cache — one full compile per iteration.  "
                        "Hoist the jit out of the loop",
                    ))
    return findings


def _free_names(func_node, params: set) -> set:
    """Names a lambda/def loads that are not its own params or locals."""
    body = func_node.body if isinstance(func_node, ast.Lambda) else func_node
    bound = set(params)
    for sub in ast.walk(body):
        if isinstance(sub, ast.Name) and isinstance(sub.ctx, ast.Store):
            bound.add(sub.id)
    return {
        sub.id for sub in ast.walk(body)
        if isinstance(sub, ast.Name) and isinstance(sub.ctx, ast.Load)
        and sub.id not in bound
    }


def _scalar_bindings(scope) -> Dict[str, int]:
    """{name: line} for names the scope binds from float()/int()/bool()
    conversions or `.item()` calls — Python scalars a traced closure must
    not capture."""
    out: Dict[str, int] = {}
    for sub in ast.walk(scope):
        if not (isinstance(sub, ast.Assign) and isinstance(sub.value, ast.Call)):
            continue
        v = sub.value
        is_scalar = (
            _dotted(v.func) in (("float",), ("int",), ("bool",))
            or (isinstance(v.func, ast.Attribute) and v.func.attr == "item")
        )
        if not is_scalar:
            continue
        for tgt in sub.targets:
            if isinstance(tgt, ast.Name):
                out[tgt.id] = sub.lineno
    return out


def check_scalar_closure(path: pathlib.Path, root: pathlib.Path) -> List[Finding]:
    """Hot-path callables closing over float()/int()/.item() scalars."""
    findings: List[Finding] = []
    tree = parse(path)
    for scope in ast.walk(tree):
        if not isinstance(scope, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        scalars = _scalar_bindings(scope)
        if not scalars:
            continue
        local_defs = {
            n.name: n for n in ast.walk(scope)
            if isinstance(n, ast.FunctionDef) and n is not scope
        }
        for call in ast.walk(scope):
            if not (isinstance(call, ast.Call) and _is_hot_consumer(call)):
                continue
            for arg in call.args:
                fn = None
                params: set = set()
                if isinstance(arg, ast.Lambda):
                    fn = arg
                    params = {a.arg for a in arg.args.args}
                elif isinstance(arg, ast.Name) and arg.id in local_defs:
                    fn = local_defs[arg.id]
                    params = {a.arg for a in fn.args.args}
                if fn is None:
                    continue
                captured = sorted(_free_names(fn, params) & set(scalars))
                if captured:
                    findings.append(Finding(
                        "scalar-closure", rel(path, root), arg.lineno,
                        f"traced callable closes over Python scalar(s) "
                        f"{captured} (bound via float()/int()/.item()): "
                        f"the value is baked into the trace — stale if "
                        f"the jit is cached, a recompile per value if "
                        f"not.  Pass it as a traced array argument "
                        f"(jnp.asarray(v, dtype)) instead",
                    ))
    return findings


def run_ast(root: pathlib.Path = REPO) -> List[Finding]:
    """All stdlib retrace-hazard rules over src/repro/{core,runtime}."""
    findings: List[Finding] = []
    for path in iter_py_files(root, _SUBDIRS):
        findings.extend(check_weak_literal_carry(path, root))
        findings.extend(check_asarray_dtype(path, root))
        findings.extend(check_jit_cache_discipline(path, root))
        findings.extend(check_scalar_closure(path, root))
    return findings


# ---------------------------------------------------------------------------
# dynamic double-call probe (jax + devices)
# ---------------------------------------------------------------------------


_RECORDS_CACHE: Dict[str, Tuple[Dict[str, dict], Optional[str]]] = {}


def _probe_mesh(axis_sizes):
    """The real debug mesh matching a TraceCase's (outermost-first)
    axis_sizes."""
    from repro.runtime import dist

    sizes = dict(axis_sizes)
    model = sizes[dist.MODEL_AXIS]
    data = sizes[dist.DATA_AXIS]
    pods = sizes.get(dist.POD_AXIS, 0)
    outer = tuple(
        s for n, s in axis_sizes
        if n not in (dist.MODEL_AXIS, dist.DATA_AXIS, dist.POD_AXIS)
    )
    return dist.debug_mesh(model=model, data=data, pods=pods, outer=outer)


def assert_no_retrace(jitted, args_a, args_b, *, label: str,
                      file: str, root: pathlib.Path = REPO) -> List[Finding]:
    """Call `jitted` twice with value-varied (shape-identical) inputs and
    require its compile cache to hold exactly one entry."""
    import jax

    jitted(*args_a)
    jitted(*args_b)
    n = jitted._cache_size()
    if n == 1:
        return []
    return [Finding(
        "recompile-budget", file, 1,
        f"[{label}] two value-varied calls left {n} compile-cache "
        f"entries (expected 1): some input reaches the trace as a "
        f"Python/static value, so every serving micro-batch would "
        f"recompile — route it through a dtype-pinned traced array "
        f"(the engine's t0 discipline)",
    )]


def collect_compiled(root: pathlib.Path = REPO):
    """Build every `mode_trace_cases()` entry on a real mesh, double-call
    its jitted solve AND fit with varied traced inputs, and AOT-compile
    the solve for HLO cost analysis.

    Returns (records, findings, skipped): `records` maps case name to
    {"flops", "collective_bytes", "compile_count", "fit_compile_count",
    "compile_s"}; `skipped` is a reason string when the host exposes
    fewer devices than the largest trace mesh needs (the CLI forces 8
    host devices before importing jax).  Memoized per root — the
    recompile and cost-budget rules share one compile pass.
    """
    key = str(root)
    if key in _RECORDS_CACHE:
        return _RECORDS_CACHE[key]

    import math as _math
    import time

    import jax
    import jax.numpy as jnp

    from repro.core import distributed as D
    from repro.core.conjugates import make_task
    from repro.launch.hlo_cost import analyze_compiled

    cases = D.mode_trace_cases()
    needed = max(
        _math.prod(s for _, s in c.axis_sizes) for c in cases
    )
    n_dev = len(jax.devices())
    if n_dev < needed:
        result = ({}, [], (
            f"{n_dev} device(s) visible but the trace matrix needs "
            f"{needed}; run via `python -m tools.analyze` (forces "
            f"--xla_force_host_platform_device_count) to enable the "
            f"dynamic recompile/cost gates"
        ))
        _RECORDS_CACHE[key] = result
        return result

    findings: List[Finding] = []
    records: Dict[str, dict] = {}
    res, reg = make_task("nmf")
    for case in cases:
        mesh = _probe_mesh(case.axis_sizes)
        coder = D.DistributedSparseCoder(mesh, res, reg, case.cfg)
        n_agents = _math.prod(
            dict(case.axis_sizes)[a] for a in coder._agent_axes
        )
        k = _PROBE_KB * n_agents
        kw, kx = jax.random.split(jax.random.PRNGKey(0))
        W = jnp.abs(jax.random.normal(kw, (_PROBE_M, k)))
        W = W / jnp.linalg.norm(W, axis=0)
        x1 = jax.random.normal(kx, (_PROBE_B, _PROBE_M))
        Ws, xs1 = coder.shard(W, x1)
        _, xs2 = coder.shard(W, x1 + 1.0)

        t0c = time.perf_counter()
        compiled = coder._solve.lower(
            Ws, xs1, jnp.asarray(0, jnp.int32)
        ).compile()
        compile_s = time.perf_counter() - t0c
        costs = analyze_compiled(compiled)

        label = case.name
        file = "src/repro/core/distributed.py"
        t = jnp.asarray
        findings.extend(assert_no_retrace(
            coder._solve,
            (Ws, xs1, t(0, jnp.int32)), (Ws, xs2, t(7, jnp.int32)),
            label=f"{label}:solve", file=file, root=root,
        ))
        nu1, y1 = coder._solve(Ws, xs1, t(0, jnp.int32))
        nu2, y2 = coder._solve(Ws, xs2, t(7, jnp.int32))
        findings.extend(assert_no_retrace(
            coder._fit,
            (Ws, nu1, y1, t(0.05, jnp.float32)),
            (Ws, nu2, y2, t(0.1, jnp.float32)),
            label=f"{label}:fit", file=file, root=root,
        ))
        records[label] = {
            "flops": float(costs.flops),
            "collective_bytes": float(costs.coll_bytes),
            "compile_count": int(coder._solve._cache_size()),
            "fit_compile_count": int(coder._fit._cache_size()),
            "compile_s": round(compile_s, 3),
        }

    result = (records, findings, None)
    _RECORDS_CACHE[key] = result
    return result


def run_dynamic(root: pathlib.Path = REPO) -> List[Finding]:
    """The recompile-budget gate ([] when devices are insufficient)."""
    _, findings, _skipped = collect_compiled(root)
    return findings
