"""Least device time of the colorings answered in the traced window (the
least bytes over HBM bandwidth or least operations over peak compute,
whichever is larger, from ``harness/least.py`` and ``peaks.json``) as a
share of the device's busy time in it."""

from bench.harness.readers import in_trace, own_samples


def read(run):
    if run.least_s is None or not run.trace or run.trace["busy_s"] <= 0:
        return None
    least = sum(run.least_s[r.template] * int(r.answer["iterations"])
                for r in own_samples(in_trace(run)))
    return 100.0 * least / run.trace["busy_s"] if least > 0 else None
