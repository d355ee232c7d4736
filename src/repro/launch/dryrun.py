import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell on the
production mesh and record memory / cost / collective statistics.

The two lines above MUST run before any other import (jax locks the device
count at first init); do not move them.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3_32b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--resume]
  PYTHONPATH=src python -m repro.launch.dryrun --dictlearn   # paper's own arch

Outputs one JSON per cell under experiments/dryrun/<mesh>/, consumed by
benchmarks/roofline.py and EXPERIMENTS.md.
"""

import argparse
import json
import pathlib
import re
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config
from repro.configs.base import SHAPES, ShapeConfig, cell_supported
from repro.launch.mesh import TARGET_KIND, make_production_mesh, peaks
from repro.optim import optimizers as opt_mod
from repro.runtime import dist
from repro.runtime import steps as S

OUT_ROOT = pathlib.Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


# ---------------------------------------------------------------------------
# HLO collective-byte sweep
# ---------------------------------------------------------------------------

_DT_BYTES = {
    "f64": 8, "s64": 8, "u64": 8,
    "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "f8e4m3fn": 1, "f8e5m2": 1, "s8": 1, "u8": 1, "pred": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"=\s*(\([^)]*\)|\S+)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\("
)


def _shape_bytes(shape_str: str) -> int:
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


def collective_stats(hlo_text: str) -> dict:
    """Per-device wire bytes by collective kind, from the partitioned HLO.

    Convention (documented in EXPERIMENTS.md): bytes = output-shape bytes,
    x2 for all-reduce (ring reduce-scatter + all-gather phases).  `-done`
    ops of async pairs are skipped to avoid double counting.
    """
    out = {k: {"count": 0, "bytes": 0} for k in
           ("all-gather", "all-reduce", "reduce-scatter", "all-to-all", "collective-permute")}
    for line in hlo_text.splitlines():
        if "-done(" in line:
            continue
        m = _COLL_RE.search(line)
        if not m:
            continue
        shape_str, kind = m.group(1), m.group(2)
        b = _shape_bytes(shape_str)
        if kind == "all-reduce":
            b *= 2
        out[kind]["count"] += 1
        out[kind]["bytes"] += b
    out["total_bytes"] = sum(v["bytes"] for k, v in out.items() if isinstance(v, dict))
    return out


# ---------------------------------------------------------------------------
# One cell
# ---------------------------------------------------------------------------


def analyze(lowered, n_chips: int, extra: dict) -> dict:
    """Compile a cell and derive trip-count-honest roofline terms.

    Uses launch/hlo_cost.py (instruction-level walk with while trip counts)
    rather than compiled.cost_analysis(), which counts every lax.scan body
    exactly once (underestimating a 64-layer stack by 64x) — see the module
    docstring there.  Memory term note: the bytes come from the CPU-backend
    HLO, whose fusion is less aggressive than TPU's, so t_memory is an
    UPPER bound on real HBM traffic.
    """
    from repro.launch.hlo_cost import analyze_hlo

    t0 = time.time()
    compiled = lowered.compile()
    compile_s = time.time() - t0
    ma = compiled.memory_analysis()
    hlo = compiled.as_text()
    costs = analyze_hlo(hlo)

    flops = costs.flops
    bytes_acc = costs.bytes
    coll_bytes = costs.coll_bytes

    t_compute = flops / peaks(TARGET_KIND)["peak_flops_bf16"]
    t_memory = bytes_acc / peaks(TARGET_KIND)["hbm_bw"]
    t_coll = coll_bytes / peaks(TARGET_KIND)["ici_bw"]
    dominant = max(
        (("compute", t_compute), ("memory", t_memory), ("collective", t_coll)),
        key=lambda kv: kv[1],
    )[0]

    top = sorted(costs.coll_detail.items(), key=lambda kv: -kv[1])[:8]
    rec = {
        **extra,
        "n_chips": n_chips,
        "compile_seconds": round(compile_s, 2),
        "per_device": {
            "hlo_flops": flops,
            "hlo_bytes_accessed": bytes_acc,
            "collective_bytes": coll_bytes,
            "peak_memory_bytes": int(ma.peak_memory_in_bytes),
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
        },
        "collectives": {
            k: {"count": costs.coll_counts[k], "bytes": costs.coll[k]}
            for k in costs.coll
        },
        "top_collectives": [
            {"kind": k, "shape": s, "bytes": b} for (k, s), b in top
        ],
        "roofline_seconds": {
            "compute": t_compute,
            "memory": t_memory,
            "collective": t_coll,
            "dominant": dominant,
        },
    }
    return rec


def run_cell(arch: str, shape_name: str, multi_pod: bool, outdir: pathlib.Path,
             resume: bool = False, rules_overrides: dict | None = None,
             tag: str = "") -> dict | None:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    out = outdir / mesh_name / f"{arch}-{shape_name}{tag}.json"
    if resume and out.exists():
        cached = json.loads(out.read_text())
        # only green/skip cells are resumable; errored cells re-run (their
        # failure may be fixed code, not a property of the cell)
        if cached.get("status") != "error":
            print(f"[skip-cached] {arch} x {shape_name} ({mesh_name})")
            return cached
        print(f"[retry-errored] {arch} x {shape_name} ({mesh_name})")

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, reason = cell_supported(cfg, shape)
    rec_base = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "kind": shape.kind, "seq_len": shape.seq_len,
        "global_batch": shape.global_batch,
    }
    out.parent.mkdir(parents=True, exist_ok=True)
    if not ok:
        rec = {**rec_base, "status": "skip", "reason": reason}
        out.write_text(json.dumps(rec, indent=2))
        print(f"[skip] {arch} x {shape_name}: {reason}")
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    t0 = time.time()
    try:
        if shape.kind == "train":
            opt = opt_mod.for_arch(cfg)
            lowered = S.lower_train(cfg, mesh, opt, shape, rules=_rules(cfg, rules_overrides))
        elif shape.kind == "prefill":
            lowered = S.lower_prefill(cfg, mesh, shape, rules=_rules(cfg, rules_overrides))
        else:  # decode
            lowered = S.lower_decode(cfg, mesh, shape, rules=_rules(cfg, rules_overrides))
        lower_s = time.time() - t0
        counts = cfg.param_counts()
        rec = analyze(lowered, n_chips, rec_base)
        rec["status"] = "ok"
        rec["lower_seconds"] = round(lower_s, 2)
        # MODEL_FLOPS = 6*N*D (dense) / 6*N_active*D (MoE); decode D = batch
        # tokens per step; train/prefill D = batch x seq tokens.
        tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
        n_for_flops = counts["active"]
        factor = 6 if shape.kind == "train" else 2
        model_flops = factor * n_for_flops * tokens
        total_hlo = rec["per_device"]["hlo_flops"] * n_chips
        rec["model_flops"] = {
            "params_total": counts["total"],
            "params_active": counts["active"],
            "tokens": tokens,
            "factor": factor,
            "model_flops": model_flops,
            "useful_ratio": (model_flops / total_hlo) if total_hlo else None,
        }
        out.write_text(json.dumps(rec, indent=2))
        r = rec["roofline_seconds"]
        print(
            f"[ok] {arch} x {shape_name} ({mesh_name}): "
            f"compute {r['compute']:.3e}s memory {r['memory']:.3e}s "
            f"coll {r['collective']:.3e}s -> {r['dominant']} "
            f"(peak {rec['per_device']['peak_memory_bytes']/1e9:.2f} GB/dev, "
            f"compile {rec['compile_seconds']}s)"
        )
        return rec
    except Exception as e:  # a failing cell is a bug in the system — record it
        rec = {**rec_base, "status": "error", "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-4000:]}
        out.write_text(json.dumps(rec, indent=2))
        print(f"[ERROR] {arch} x {shape_name}: {type(e).__name__}: {e}")
        return rec


def _rules(cfg, overrides):
    from repro.runtime import sharding as shd

    return shd.rules_for(cfg, overrides)


# ---------------------------------------------------------------------------
# The paper's own production-scale config (extra rows beyond the 40 cells)
# ---------------------------------------------------------------------------


def run_dictlearn(multi_pod: bool, outdir: pathlib.Path, resume: bool = False,
                  mode: str = "exact_fista", iters: int = 30,
                  m_dim: int = 8192, k_atoms: int = 262144, batch: int = 4096) -> dict | None:
    """Dry-run the paper's distributed dictionary-learning step at production
    scale: atoms sharded over `model`, samples over `pod`x`data`."""
    from repro.core.conjugates import make_task
    from repro.core.distributed import DistConfig, DistributedSparseCoder

    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    tag = f"dictlearn_{mode}"
    out = outdir / mesh_name / f"{tag}-fit.json"
    if resume and out.exists():
        cached = json.loads(out.read_text())
        if cached.get("status") != "error":
            print(f"[skip-cached] {tag} ({mesh_name})")
            return cached
        print(f"[retry-errored] {tag} ({mesh_name})")
    out.parent.mkdir(parents=True, exist_ok=True)

    mesh = make_production_mesh(multi_pod=multi_pod)
    res, reg = make_task("nmf", gamma=0.05, delta=0.1)
    data_axes = (
        (dist.POD_AXIS, dist.DATA_AXIS) if multi_pod else (dist.DATA_AXIS,)
    )
    coder = DistributedSparseCoder(
        mesh, res, reg,
        DistConfig(mode=mode, iters=iters, data_axes=data_axes),
    )
    W = jax.ShapeDtypeStruct((m_dim, k_atoms), jnp.float32)
    x = jax.ShapeDtypeStruct((batch, m_dim), jnp.float32)
    mu_w = jax.ShapeDtypeStruct((), jnp.float32)
    rec_base = {
        "arch": f"dictlearn[{mode}]", "shape": f"M{m_dim}xK{k_atoms}xB{batch}x{iters}it",
        "mesh": mesh_name, "kind": "dict_fit", "seq_len": 0, "global_batch": batch,
    }
    try:
        with mesh:
            lowered = coder._fit.lower(W, x, mu_w)
        rec = analyze(lowered, mesh.devices.size, rec_base)
        rec["status"] = "ok"
        # Useful FLOPs: per iteration 2*(2*B*M*K) for the two matmuls + the
        # final recovery; the dictionary step adds 2*B*M*K.
        useful = iters * 4 * batch * m_dim * k_atoms + 2 * batch * m_dim * k_atoms
        total_hlo = rec["per_device"]["hlo_flops"] * mesh.devices.size
        rec["model_flops"] = {
            "useful_flops": useful,
            "useful_ratio": useful / total_hlo if total_hlo else None,
        }
        out.write_text(json.dumps(rec, indent=2))
        r = rec["roofline_seconds"]
        print(f"[ok] {tag} ({mesh_name}): compute {r['compute']:.3e}s "
              f"memory {r['memory']:.3e}s coll {r['collective']:.3e}s -> {r['dominant']}")
        return rec
    except Exception as e:
        rec = {**rec_base, "status": "error", "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-4000:]}
        out.write_text(json.dumps(rec, indent=2))
        print(f"[ERROR] {tag}: {type(e).__name__}: {e}")
        return rec


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", type=str, default=None)
    ap.add_argument("--shape", type=str, default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--all", action="store_true", help="run every (arch x shape)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--dictlearn", action="store_true",
                    help="also dry-run the paper's dictionary-learning step")
    ap.add_argument("--dict-mode", type=str, default="exact_fista")
    ap.add_argument("--resume", action="store_true", help="skip cells with cached JSON")
    ap.add_argument("--out", type=str, default=str(OUT_ROOT))
    args = ap.parse_args()

    outdir = pathlib.Path(args.out)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    if args.dictlearn:
        for mp in meshes:
            run_dictlearn(mp, outdir, resume=args.resume, mode=args.dict_mode)
        if not (args.all or args.arch):
            return

    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]

    n_err = 0
    for mp in meshes:
        for arch in archs:
            for shape in shapes:
                rec = run_cell(arch, shape, mp, outdir, resume=args.resume)
                if rec and rec.get("status") == "error":
                    n_err += 1
    if n_err:
        raise SystemExit(f"{n_err} cells FAILED — see experiments/dryrun/*.json")
    print("dry-run complete: all requested cells green")


if __name__ == "__main__":
    main()
