"""Seconds of the traced window in which the device was idle while the
host did anything but wait for work (every idle gap not labelled
``service.idle``), over the requests answered in it."""

from bench.harness.readers import in_trace

WAITING = "service.idle"


def read(run):
    n = len(in_trace(run))
    if not n:
        return None
    idle = sum(t for label, t in run.trace["idle_by_span"].items()
               if label != WAITING)
    return idle / n if idle > 0 else None
