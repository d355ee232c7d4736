"""How late the load generator ran: 95th percentile of submit time minus
due time over the samples due in the window (bench clock)."""

from window import percentile


def read(ctx):
    late = [s - d for s, d in zip(ctx["submitted"], ctx["due"]) if s is not None]
    return 1e3 * percentile(late, 95) if late else None
