"""Samples that went through a completed fit step, per second of the
window that closes on the first fit step ending at or after --seconds."""

from window import rate


def read(ctx):
    return rate(ctx["fits"], ctx["seconds"])
