import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""Perf-iteration profiler: lower one cell and break its collectives down by
kind and by tensor shape (the dry-run 'profile' the §Perf loop reads, since
there is no wall-clock on this container).

  PYTHONPATH=src python -m repro.launch.analyze --arch kimi_k2_1t_a32b \\
      --shape train_4k [--multi-pod] [--top 20]
"""

import argparse
import collections
import re

from repro.configs import get_config
from repro.configs.base import SHAPES
from repro.launch.dryrun import _DT_BYTES, _SHAPE_RE
from repro.launch.mesh import TARGET_KIND, make_production_mesh, peaks
from repro.optim import optimizers as opt_mod
from repro.runtime import steps as S

_LINE_RE = re.compile(
    r"=\s*(\([^)]*\)|\S+)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\((.*)$"
)


def _bytes_of(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DT_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DT_BYTES[dt]
    return total


def breakdown(hlo: str, top: int = 20):
    rows = collections.Counter()
    counts = collections.Counter()
    for line in hlo.splitlines():
        if "-done(" in line:
            continue
        m = _LINE_RE.search(line)
        if not m:
            continue
        shape_str, kind = m.group(1), m.group(2)
        b = _bytes_of(shape_str)
        if kind == "all-reduce":
            b *= 2
        # strip layout braces for readability
        clean = re.sub(r"\{[^}]*\}", "", shape_str)
        rows[(kind, clean)] += b
        counts[(kind, clean)] += 1
    print(f"{'bytes/dev':>14}  {'count':>5}  op")
    for (kind, shape), b in rows.most_common(top):
        print(f"{b:14,}  {counts[(kind, shape)]:5}  {kind:18s} {shape}")
    return rows


def lower_cell(arch: str, shape_name: str, multi_pod: bool, rules_overrides=None):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    from repro.runtime import sharding as shd

    rules = shd.rules_for(cfg, rules_overrides)
    if shape.kind == "train":
        opt = opt_mod.for_arch(cfg)
        return S.lower_train(cfg, mesh, opt, shape, rules=rules), mesh
    if shape.kind == "prefill":
        return S.lower_prefill(cfg, mesh, shape, rules=rules), mesh
    return S.lower_decode(cfg, mesh, shape, rules=rules), mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--top", type=int, default=20)
    args = ap.parse_args()

    from repro.launch.hlo_cost import analyze_hlo

    lowered, mesh = lower_cell(args.arch, args.shape, args.multi_pod)
    compiled = lowered.compile()
    costs = analyze_hlo(compiled.as_text())
    print(f"== {args.arch} x {args.shape} (trip-count weighted) ==")
    print(f"per-device flops {costs.flops:.3e}  bytes {costs.bytes:.3e}  "
          f"coll {costs.coll_bytes:.3e}")
    print(f"t_compute {costs.flops / peaks(TARGET_KIND)['peak_flops_bf16']:.3e}s  "
          f"t_memory {costs.bytes / peaks(TARGET_KIND)['hbm_bw']:.3e}s  "
          f"t_coll {costs.coll_bytes / peaks(TARGET_KIND)['ici_bw']:.3e}s")
    for k in costs.coll:
        if costs.coll_counts[k]:
            print(f"  {k:20s} n={costs.coll_counts[k]:6.0f}  {costs.coll[k]:16,.0f} B")
    print(f"\n{'wire bytes/dev':>16}  op (trip-weighted)")
    for (kind, shape), b in sorted(costs.coll_detail.items(), key=lambda kv: -kv[1])[: args.top]:
        print(f"{b:16,.0f}  {kind:18s} {shape[:120]}")


if __name__ == "__main__":
    main()
