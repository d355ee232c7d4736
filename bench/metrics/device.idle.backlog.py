"""Percent of the traced window in which no operation ran on the device,
averaged over the chips."""

from trace import idle_share


def read(ctx):
    return idle_share(ctx)
