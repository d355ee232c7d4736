"""p95 of a coded sample's wait in the service's queue (ms): from its
submit to the flush of its micro-batch, the service's `queue_wait_ms` in
stats() at the close.  None where the program keeps no such record."""


def read(ctx):
    waits = ctx["stats"].get("queue_wait_ms")
    return None if waits is None else waits["p95"]
