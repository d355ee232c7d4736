"""Collective time per solve in which no other operation ran on that
device (ms), inside the solve program's runs, averaged over devices."""

from trace import SOLVE, COLLECTIVE, exposed_collectives


def read(ctx):
    tr, win = ctx.get("trace"), ctx.get("trace_window")
    if not tr or not win:
        return None
    if not any(COLLECTIVE.search(n) for v in tr["devices"].values() for n, _, _ in v["ops"]):
        return None
    per = [ns / runs / 1e6 for ns, runs in exposed_collectives(tr, win, SOLVE).values()]
    return sum(per) / len(per) if per else None
