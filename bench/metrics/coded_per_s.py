"""Samples whose (nu, y) came back, per second of the window that closes
on the first coded micro-batch at or after --seconds."""

from window import rate


def read(ctx):
    return rate(ctx["batches"], ctx["seconds"])
