"""The solve's share of its roofline: the least time the chip could take
for its operations and bytes (bench/work.py, at the padded micro-batch),
over the traced device time of one solve."""

from trace import SOLVE, mean_module_ms
from work import least_time, peaks, solve_bytes, solve_flops


def read(ctx):
    ms = mean_module_ms(ctx, SOLVE)
    if not ms:
        return None
    cfg, b = ctx["cfg"], ctx["cfg"]["micro_batch"]
    t, _ = least_time(solve_flops(cfg, b), solve_bytes(cfg, b), peaks(ctx["kind"]))
    return 100.0 * t / (ms / 1e3)
