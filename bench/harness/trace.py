"""Device trace of a sub-window: the profiler session and its reduction.

A traced run opens ``jax.profiler`` itself around the sub-window its traffic
names, routes the program's own spans (``repro.obs.tracing``) into the
profiler as ``TraceAnnotation``\\ s so that they share the device's clock,
and marks the sub-window with one ``bench.window`` annotation. The
reduction reads the ``.xplane.pb`` with nothing but JAX:

* busy time: the union of the device-op intervals on each device plane's
  op line, clipped to the window and averaged over the devices;
* the device ops that took the most time, by short name and self time
  (time not covered by ops nested inside them);
* the longest idle gaps, each labelled by the innermost host span open at
  the gap's midpoint.

A session's reduction adds, from the same events, the device time by the
program's scopes and the idle time by host span
(:mod:`bench.harness.xplane`).
"""

from __future__ import annotations

import glob
import os
import re
import time

WINDOW_SPAN = "bench.window"
DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
TOP = 10


def union_length(intervals) -> tuple[float, list[tuple[float, float]]]:
    """Total length of a union of ``(start, end)`` intervals and the merged
    intervals, sorted."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), [tuple(m) for m in merged]


def gaps_between(merged, lo: float, hi: float) -> list[tuple[float, float]]:
    """The idle stretches of ``[lo, hi]`` not covered by ``merged``."""
    out, cur = [], lo
    for s, e in merged:
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def label_gap(gap, host_spans) -> str:
    """Innermost host span (latest start) open at the gap's midpoint."""
    mid = 0.5 * (gap[0] + gap[1])
    best = None
    for name, s, e in host_spans:
        if s <= mid <= e and name != WINDOW_SPAN:
            if best is None or s > best[1]:
                best = (name, s)
    return best[0] if best else "no host span"


def short_name(hlo: str) -> str:
    """``%fusion.291 = f32[262144,2]{1,0:T(8,128)} fusion(...)`` ->
    ``fusion.291 f32[262144,2] fusion``; other names pass through."""
    lhs, sep, rest = hlo.partition(" = ")
    if not sep:
        return hlo[:80]
    shape = "tuple" if rest.startswith("(") else re.sub(
        r"\{[^}]*\}", "", rest.split(" ", 1)[0])
    m = re.search(r"[\]})] ([a-z][a-z0-9\-]*)\(", rest)
    return f"{lhs.lstrip('%')} {shape} {m.group(1) if m else '?'}"


def self_times(ops) -> dict:
    """Seconds per op name not covered by ops nested inside it (a ``while``
    holds its body's ops on the same line)."""
    out: dict = {}
    stack: list[list] = []           # [end, name, self seconds]
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][0] <= s:
            end, n, t = stack.pop()
            out[n] = out.get(n, 0.0) + t
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    for end, n, t in stack:
        out[n] = out.get(n, 0.0) + t
    return out


def reduce_events(device_ops: dict, host_spans, window) -> dict:
    """The reduction on plain data, so that it can be checked on a small
    recorded trace: ``device_ops`` maps a device to ``[(name, start,
    end)]``, ``host_spans`` is ``[(name, start, end)]``, ``window`` is
    ``(start, end)``; times in seconds on one clock."""
    lo, hi = window
    busy, by_name, merged_all = [], {}, []
    for ops in device_ops.values():
        clipped = [(name, max(s, lo), min(e, hi)) for name, s, e in ops
                   if e > lo and s < hi]
        length, merged = union_length([(s, e) for _, s, e in clipped])
        busy.append(length)
        merged_all.append(merged)
        for name, t in self_times(clipped).items():
            short = short_name(name)
            by_name[short] = by_name.get(short, 0.0) + t
    busy_s = sum(busy) / len(busy) if busy else 0.0
    gaps = []
    for merged in merged_all:
        gaps.extend(gaps_between(merged, lo, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    top_ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "busy_s": busy_s, "window_s": hi - lo,
        "device_ops": [[n, s] for n, s in top_ops],
        "idle_gaps": [[label_gap(g, host_spans), g[1] - g[0]]
                      for g in gaps[:TOP]],
    }


def _cpu_op(line_name: str, ev_name: str) -> bool:
    """A CPU backend's op event (rehearsals only: XLA's CPU thread pools)."""
    return (line_name.startswith("tf_XLA") and "::" not in ev_name)


def read_events(path: str, cpu: bool = False):
    """``(device_ops, host_spans, window)`` of one ``.xplane.pb`` in the
    shapes :func:`reduce_events` takes; ``window`` is None where the trace
    has no ``bench.window`` span. ``cpu`` takes the device ops from the CPU
    backend's threads, for a rehearsal."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device_ops, host_spans, window = {}, [], None
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops[plane.name] = [
                        (ev.name, ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9)
                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    e = s + ev.duration_ns * 1e-9
                    if cpu and _cpu_op(line.name, ev.name):
                        device_ops.setdefault("cpu", []).append(
                            (ev.name, s, e))
                    elif ev.name == WINDOW_SPAN:
                        window = (s, e)
                    elif ev.duration_ns > 0:
                        host_spans.append((ev.name, s, e))
    if not device_ops:
        raise RuntimeError(f"no {OPS_LINE!r} line on any "
                           f"{DEVICE_PLANE_PREFIX}* plane in {path}")
    return device_ops, host_spans, window


def _window_events(path: str, cpu: bool = False):
    device_ops, host_spans, window = read_events(path, cpu)
    if window is None:
        raise RuntimeError(f"no {WINDOW_SPAN!r} span in {path}")
    return device_ops, host_spans, window


def read_xplane(path: str, cpu: bool = False) -> dict:
    """Reduce one ``.xplane.pb`` over its ``bench.window`` span (see
    :func:`reduce_events` and :func:`read_events`)."""
    return reduce_events(*_window_events(path, cpu))


def profiler_spans():
    """A process tracer for the program (``repro.obs.tracing``) that turns
    each of its spans into a ``TraceAnnotation`` on the profiler's clock
    and keeps nothing itself. Installed only while a trace is open."""
    import jax.profiler
    from repro.obs.tracing import Tracer

    class _ProfilerSpans(Tracer):
        def _push(self, sp) -> None:
            ann = jax.profiler.TraceAnnotation(sp.name)
            ann.__enter__()
            self._stack().append(ann)

        def _pop(self, sp) -> None:
            st = self._stack()
            if st:
                st.pop().__exit__(None, None, None)

    return _ProfilerSpans(enabled=True, sync=False)


class Session:
    """One profiler session over one sub-window (start, then stop)."""

    def __init__(self, log_dir: str, cpu: bool = False):
        self.log_dir = log_dir
        self.cpu = cpu
        self._window = None
        self._old_tracer = None
        self.started = self.stopped = None

    def start(self) -> None:
        import jax.profiler
        from repro.obs import tracing

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self._old_tracer = tracing.get_tracer()
        tracing.set_tracer(profiler_spans())
        self._window = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._window.__enter__()
        self.started = time.perf_counter()

    def stop(self) -> None:
        import jax.profiler
        from repro.obs import tracing

        if self.started is None or self.stopped is not None:
            return
        self.stopped = time.perf_counter()
        self._window.__exit__(None, None, None)
        tracing.set_tracer(self._old_tracer)
        jax.profiler.stop_trace()

    def reduce(self) -> dict:
        """:func:`reduce_events` of the window, with the same events'
        ``device_scopes`` and ``idle_by_span``
        (:func:`bench.harness.xplane.reduce_scoped`)."""
        from bench.harness import xplane

        paths = glob.glob(os.path.join(self.log_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            raise RuntimeError(f"the profiler wrote no trace under "
                               f"{self.log_dir}")
        path = max(paths, key=os.path.getmtime)
        events = _window_events(path, self.cpu)
        return dict(reduce_events(*events),
                    **xplane.reduce_scoped(*events, xplane.tf_ops(path)))
