"""Share of the padded micro-batch rows that held a sample:
coded / (batches x micro_batch), from the service's stats() at the close."""


def read(ctx):
    st = ctx["stats"]
    if not st["batches"]:
        return None
    return 100.0 * st["coded"] / (st["batches"] * ctx["cfg"]["micro_batch"])
