"""Arithmetic the metric readers share (each reader is ``metrics/<name>.py``).

``run`` is a :class:`bench.harness.runner.RunData`. A reader returns None
when its run holds nothing for it to read; the harness then leaves the
metric out of the result line.
"""

from __future__ import annotations

import math

INF = float("inf")


def percentile(xs, p: float) -> float | None:
    """Nearest-rank percentile (``p`` in 0..100); None for no values."""
    xs = sorted(xs)
    if not xs:
        return None
    return xs[max(0, math.ceil(p / 100.0 * len(xs)) - 1)]


def latencies(run) -> list[float]:
    """Scheduled send to answer, per request of the window; a request that
    was shed, failed or never answered counts as infinitely late."""
    w = run.window
    out = []
    for r in w.requests:
        if r.status == "done" and r.t_done is not None:
            out.append(r.t_done - (w.t0 + r.t_sched))
        else:
            out.append(INF)
    return out


def answered(run) -> list:
    return [r for r in run.window.requests if r.status == "done" and r.answer]


def in_trace(run) -> list:
    """Answered requests sent and answered inside the traced sub-window."""
    tr = run.trace
    if tr is None:
        return []
    return [r for r in answered(run)
            if r.t_send >= tr["started"] and r.t_done <= tr["stopped"]]


def own_samples(reqs) -> list:
    """Answers whose samples were drawn for them: not from the results
    cache and not joined to another request's sample stream."""
    return [r for r in reqs if not r.answer.get("from_cache")
            and not r.answer.get("shared_group")]


def colorings(reqs) -> int:
    """Colorings behind the answers that drew their own samples."""
    return sum(int(r.answer["iterations"]) for r in own_samples(reqs))


def idle_share(run) -> float | None:
    tr = run.trace
    if tr is None or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def kernel_s_per_coloring(run, kernel: str) -> float | None:
    """Self seconds of the device ops under a ``kernel`` scope (in any plan
    node, summed over the chips) in the traced window, over the colorings
    answered in it."""
    n = colorings(in_trace(run))
    if not n:
        return None
    s = sum(t for key, t in run.trace["device_scopes"].items()
            if kernel in key.split("/"))
    return s / n if s > 0 else None
