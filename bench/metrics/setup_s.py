"""Process start to the window's open: JAX and the chip, W0, the service's
compiles and warm-up, the first samples."""


def read(ctx):
    return ctx["setup_s"]
