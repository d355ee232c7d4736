"""Operations and bytes of one engine call, counted from a configuration's
shapes, and the least time a chip could take for them.

Counts are per chip: a solve of a micro-batch of B samples runs `iters`
iterations, each two products of B x M by M x K_loc (W^T nu and y W^T),
so 4 B M K_loc operations; a fit (`jit__fit_body`) is the atom update
alone, the gradient nu^T y from the duals its batch's solve returned,
2 B M K_loc operations.  The bytes are the least any implementation must move:
W (M x K_loc, larger than on-chip memory) read once per iteration, plus
the batch's nu and y written once.  The step-size estimate (a power
iteration over W in every call) is not counted: no implementation needs
it per call.
"""

from __future__ import annotations

import json
import pathlib

HERE = pathlib.Path(__file__).resolve().parent

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}


def atoms_per_chip(cfg: dict) -> int:
    data, model = cfg["mesh"]
    if cfg["atoms"] % model:
        raise ValueError(f"{cfg['atoms']} atoms do not split over {model} agents")
    return cfg["atoms"] // model


def solve_flops(cfg: dict, batch: int) -> float:
    """Operations of one solve of `batch` samples, on one chip."""
    return 4.0 * cfg["iters"] * batch * cfg["m"] * atoms_per_chip(cfg)


def fit_flops(cfg: dict, batch: int) -> float:
    """Operations of one fit step of `batch` samples, on one chip: the atom
    update from the duals of the batch's solve, which the solve counts."""
    return 2.0 * batch * cfg["m"] * atoms_per_chip(cfg)


def solve_bytes(cfg: dict, batch: int) -> float:
    """Least bytes one solve must move through HBM, on one chip."""
    b = DTYPE_BYTES[cfg["dtype"]]
    k = atoms_per_chip(cfg)
    return cfg["iters"] * cfg["m"] * k * b + batch * (cfg["m"] + k) * b


def peaks(device_kind: str) -> dict:
    """The peak row of one chip kind; an unknown kind is an error."""
    table = json.loads((HERE / "peaks.json").read_text())["kinds"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: {sorted(table)}")
    return table[device_kind]


def least_time(flops: float, nbytes: float, peak: dict) -> tuple:
    """(seconds, bound): the larger of operations over peak FLOP/s and bytes
    over peak bandwidth, and which of the two it is."""
    t_c = flops / peak["peak_flops_bf16"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
