"""Reading a profiler trace into intervals, and the reductions the
per-layer metrics use.  Times are nanoseconds on the trace's clock.

A device plane is one named `/device:TPU:<n>` (one per chip); on it the
line `XLA Modules` holds one event per program run (named after the jitted
function, e.g. `jit__solve_body(...)`) and `XLA Ops` one per operation.
Host spans written with `jax.profiler.TraceAnnotation` sit on the host
plane's thread lines: the harness's own (`bench.*`) and the service's and
engine's (`service.*`, `engine.*`, runtime/tracing.py), which name what
the host was doing while the device idled.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

Interval = Tuple[int, int]

COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
                        r"psum|ppermute")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_SPANS = ("bench.", "service.", "engine.")  # host events kept, by name prefix


def load(log_dir: str, pattern: str = "*.xplane.pb") -> dict:
    """{"devices": {n: {"modules": [(name, t0, t1)], "ops": [...]}},
        "host": [(name, t0, t1)]} from the newest trace file under log_dir."""
    import jax

    files = sorted(glob.glob(os.path.join(log_dir, "**", pattern), recursive=True),
                   key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = jax.profiler.ProfileData.from_file(files[-1])
    out = {"devices": {}, "host": [], "planes": []}
    for plane in pd.planes:
        lines = {ln.name: ln for ln in plane.lines}
        out["planes"].append((plane.name, sorted(lines)))
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = out["devices"].setdefault(int(m.group(1)), {"modules": [], "ops": []})
            for key, line in (("modules", "XLA Modules"), ("ops", "XLA Ops")):
                if line in lines:
                    dev[key] = [(_op_name(e.name), int(e.start_ns),
                                 int(e.start_ns + e.duration_ns)) for e in lines[line].events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend((e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                                   for e in line.events if e.name.startswith(HOST_SPANS))
    return out


def _op_name(name: str) -> str:
    """An op event is named by its whole HLO instruction
    ('%fusion.51 = f32[...] fusion(...)'): keep the instruction's own name,
    so that an operand's name ('%all-reduce.3') does not classify it."""
    return name.split(" = ", 1)[0].lstrip("%")


# ops that contain other ops (a loop and its body): not counted in top_ops
CONTAINERS = re.compile(r"^(while|conditional|call)(\.|$)")


def union(intervals: List[Interval]) -> List[Interval]:
    merged: List[List[int]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def clip(intervals: List[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def measure(intervals: List[Interval]) -> int:
    return sum(b - a for a, b in union(intervals))


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """The parts of union(a) not covered by union(b)."""
    out = []
    b = union(b)
    for lo, hi in union(a):
        cur = lo
        for blo, bhi in b:
            if bhi <= cur or blo >= hi:
                continue
            if blo > cur:
                out.append((cur, blo))
            cur = max(cur, bhi)
            if cur >= hi:
                break
        if cur < hi:
            out.append((cur, hi))
    return out


def window(tr: dict) -> Optional[Interval]:
    """The measured window, from the harness's `bench.window` span."""
    spans = [(a, b) for n, a, b in tr["host"] if n == "bench.window"]
    return spans[0] if spans else None


def busy(tr: dict, win: Interval) -> Dict[int, int]:
    """Per device, ns in the window in which some operation ran."""
    return {d: measure(clip([(a, b) for _, a, b in v["ops"]], *win))
            for d, v in tr["devices"].items() if v["ops"]}


def module_times(tr: dict, prefix: str, win: Interval) -> List[int]:
    """Durations (ns) of every run of programs whose name starts with
    `prefix` that overlaps the window, over all devices (the device's and
    the host's clocks may disagree by microseconds at the window's edges)."""
    return [b - a for v in tr["devices"].values() for n, a, b in v["modules"]
            if n.startswith(prefix) and b > win[0] and a < win[1]]


def exposed_collectives(tr: dict, win: Interval, prefix: str) -> Dict[int, Tuple[int, int]]:
    """Per device, (ns in which a collective ran and no other operation did,
    inside runs of programs named `prefix...` that overlap the window; the
    number of those runs).  A loop that holds the collective is no other
    operation: it spans its whole body."""
    out = {}
    for d, v in tr["devices"].items():
        runs = [(a, b) for n, a, b in v["modules"]
                if n.startswith(prefix) and b > win[0] and a < win[1]]
        if not runs:
            continue
        lo, hi = min(a for a, _ in runs), max(b for _, b in runs)
        inside = [(n, a, b) for n, a, b in v["ops"] if b > lo and a < hi]
        coll = [(a, b) for n, a, b in inside if COLLECTIVE.search(n)]
        comp = [(a, b) for n, a, b in inside
                if not COLLECTIVE.search(n) and not CONTAINERS.match(n)]
        bare = subtract(coll, comp)
        exposed = [iv for r in runs for iv in clip(bare, *r)]
        out[d] = (measure(exposed), len(runs))
    return out


def top_ops(tr: dict, win: Interval, n: int = 10) -> List[Tuple[str, float]]:
    """The n operations that took most device time in the window, in
    seconds, averaged over devices; loops, which hold other ops, left out."""
    tot: Dict[str, int] = {}
    ndev = max(1, sum(1 for v in tr["devices"].values() if v["ops"]))
    for v in tr["devices"].values():
        for name, a, b in clip_named(v["ops"], win):
            if not CONTAINERS.match(name):
                tot[name] = tot.get(name, 0) + (b - a)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, t / ndev / 1e9] for k, t in best]


def clip_named(events, win: Interval):
    lo, hi = win
    return [(nm, max(a, lo), min(b, hi)) for nm, a, b in events if b > lo and a < hi]


def idle_gaps(tr: dict, win: Interval, n: int = 10) -> List[Tuple[str, float]]:
    """The n longest gaps on the first device in which no operation ran,
    each named by the host spans in flight at the gap's middle, the
    service's and the engine's beside the harness's ("service host work"
    when none was)."""
    devs = sorted(d for d, v in tr["devices"].items() if v["ops"])
    if not devs:
        return []
    ops = union(clip([(a, b) for _, a, b in tr["devices"][devs[0]]["ops"]], *win))
    edges = [win[0]] + [x for ab in ops for x in ab] + [win[1]]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    spans = [(nm, a, b) for nm, a, b in tr["host"] if nm != "bench.window"]
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (a + b) // 2
        what = sorted({nm for nm, s0, s1 in spans if s0 <= mid < s1})
        label = "+".join(what) if what else "service host work"
        out.append([f"{label} at +{(a - win[0]) / 1e9:.3f}s", (b - a) / 1e9])
    return out


def mean_module_ms(ctx: dict, prefix: str) -> Optional[float]:
    """Mean device time (ms) of one run of the programs named `prefix...`
    in the traced window, over runs and devices; None if none ran."""
    tr, win = ctx.get("trace"), ctx.get("trace_window")
    if not tr or not win:
        return None
    t = module_times(tr, prefix, win)
    return sum(t) / len(t) / 1e6 if t else None


def idle_share(ctx: dict) -> Optional[float]:
    """Percent of the traced window in which the devices ran nothing,
    averaged over devices."""
    tr, win = ctx.get("trace"), ctx.get("trace_window")
    if not tr or not win:
        return None
    b = busy(tr, win)
    if not b:
        return None
    return 100.0 * (1.0 - sum(b.values()) / len(b) / (win[1] - win[0]))


SOLVE, FIT = "jit__solve_body", "jit__fit_body"
