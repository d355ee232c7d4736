"""95th percentile over every sample due in the window of the time from
its due time to its result; a sample that failed counts as never done."""

from window import latency_tail


def read(ctx):
    if not ctx["due"]:
        return None
    return 1e3 * latency_tail(ctx["due"], ctx["done"], 95)
