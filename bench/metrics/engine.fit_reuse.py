"""Share of the learner's fits that reused the codes their batch's solve
computed, instead of solving the batch again (%): the service's counters
`fits_reused` and `fits_resolved` in stats() at the close.  A program
without those counters reports nothing."""


def read(ctx):
    counters = ctx["stats"].get("counters", {})
    if "fits_reused" not in counters or "fits_resolved" not in counters:
        return None
    fits = counters["fits_reused"] + counters["fits_resolved"]
    return 100.0 * counters["fits_reused"] / fits if fits else None
