"""Median wall-clock queue wait: from a request's due time to the start
of the scheduler tick that ran its first prefill chunk (`TickRecord.waits`,
over the window's ticks); nothing where the ticks carry no waits."""


def read(run):
    waits = [w for x in run.ticks for _, w in getattr(x, "waits", None) or ()]
    return run.percentile(waits, 50) * 1e3 if waits else None
