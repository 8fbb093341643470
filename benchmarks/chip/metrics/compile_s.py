"""Seconds JAX spent tracing, lowering and compiling (or loading from the
persistent cache) inside the window's scheduler ticks
(`TickRecord.compile_s`); nothing where the ticks carry no compile
record."""


def read(run):
    if not run.ticks or not hasattr(run.ticks[0], "compile_s"):
        return None
    return sum(s for x in run.ticks for _, s in x.compile_s)
