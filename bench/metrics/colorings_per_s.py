"""Colorings behind the answers of the window, over the whole window (first
send to last answer)."""

from bench.harness.readers import answered, colorings


def read(run):
    w = run.window
    n = colorings(answered(run))
    return n / (w.t_end - w.t0) if n else None
