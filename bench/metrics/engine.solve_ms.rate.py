"""Device time of one run of the engine's solve program (ms), from the trace."""

from trace import SOLVE, mean_module_ms


def read(ctx):
    return mean_module_ms(ctx, SOLVE)
