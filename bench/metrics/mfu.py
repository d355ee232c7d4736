"""Whole-service share of the chips' bf16 peak: (coded/s x solve operations
per sample + learned/s x fit operations per sample) over chips x peak,
counting only the samples' own operations (no padding, no step-size
estimate)."""

from window import rate
from work import fit_flops, peaks, solve_flops


def read(ctx):
    cfg = ctx["cfg"]
    coded = rate(ctx["batches"], ctx["seconds"]) or 0.0
    learned = rate(ctx["fits"], ctx["seconds"]) or 0.0
    if not coded and not learned:
        return None
    per_chip = coded * solve_flops(cfg, 1) + learned * fit_flops(cfg, 1)
    return 100.0 * per_chip / peaks(ctx["kind"])["peak_flops_bf16"]
