"""Mean host time per engine call under the exec lock with no engine
program in flight (ms): the self time of the service's spans
`service.exec.solve` and `service.exec.fit` (the batch's copy to the
device, the result's copy back, schedule bookkeeping) over their count,
from stats() at the close.  The device idles for all of it."""


def read(ctx):
    spans = ctx["stats"].get("spans", {})
    calls = [spans[n] for n in ("service.exec.solve", "service.exec.fit") if n in spans]
    count = sum(s["count"] for s in calls)
    if not count:
        return None
    return sum(s["self_ms"] for s in calls) / count
