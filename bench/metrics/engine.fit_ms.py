"""Device time of one run of the engine's fit program (ms), from the trace."""

from trace import FIT, mean_module_ms


def read(ctx):
    return mean_module_ms(ctx, FIT)
