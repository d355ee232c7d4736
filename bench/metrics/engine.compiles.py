"""Programs compiled by the service's batcher and learner threads after
its start (the warm-up's compiles not counted): the service's counter
`compiles` in stats() at the close.  A shape the warm-up missed shows here."""


def read(ctx):
    counters = ctx["stats"].get("counters", {})
    return float(counters["compiles"]) if "compiles" in counters else None
