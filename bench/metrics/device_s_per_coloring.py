"""Device busy seconds in the traced window over the colorings behind the
answers given inside it."""

from bench.harness.readers import colorings, in_trace


def read(run):
    n = colorings(in_trace(run))
    return run.trace["busy_s"] / n if n and run.trace["busy_s"] > 0 else None
