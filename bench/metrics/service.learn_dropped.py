"""Share of the micro-batches offered to the learner that its reservoir
discarded: learn_dropped / learn_seen, from the service's stats()."""


def read(ctx):
    st = ctx["stats"]
    if not st["learn_seen"]:
        return None
    return 100.0 * st["learn_dropped"] / st["learn_seen"]
