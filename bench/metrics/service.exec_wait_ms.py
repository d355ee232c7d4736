"""Mean wait of a coding micro-batch for the engine's exec lock (ms), the
time it stands behind a fit: the service's span `service.exec_wait.solve`,
total over count, from stats() at the close."""


def read(ctx):
    span = ctx["stats"].get("spans", {}).get("service.exec_wait.solve")
    if not span or not span["count"]:
        return None
    return span["total_ms"] / span["count"]
