"""Mean time the batcher spent filling a micro-batch (ms): from the first
sample taken to the flush, the size-or-deadline wait; the service's span
`service.collect`, total over count, from stats() at the close."""


def read(ctx):
    span = ctx["stats"].get("spans", {}).get("service.collect")
    if not span or not span["count"]:
        return None
    return span["total_ms"] / span["count"]
