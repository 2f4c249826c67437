"""One run of one cell: set up, drive the window, check, report.

The steps, in order: the cell's files; the chip check; the graph from the
seed; the service with a fresh results cache and ledger; one warm-up
request per template (every build and compile lands here); the timed
window (traced when asked); peak device memory; the service closed and its
device state freed; the reference on a sample of the answers; the metrics.
"""

from __future__ import annotations

import collections
import dataclasses
import json
import shutil
import sys
import tempfile
import time

from bench.harness import cell as cells
from bench.harness import check, device, least, traffic
from bench.harness.graph import kronecker_edges, simple_adjacency
from bench.harness.readers import latencies, percentile

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_MISS = "/jax/compilation_cache/cache_misses"
COUNTERS = ("kernel_fallbacks_total", "dispatch_retries_total",
            "service_shed_total", "engine_rebuilds_total")


@dataclasses.dataclass
class RunData:
    """What the metric readers read."""

    setup_s: float
    window: traffic.Window
    trace: dict | None              # reduction plus started/stopped
    least_s: dict | None            # template -> least seconds per coloring


class CompileCounter:
    """Backend compiles, jaxpr traces and persistent-cache hits and misses,
    with their times, from JAX's monitoring events."""

    def __init__(self):
        import jax.monitoring
        self.events: list[tuple[str, float, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, name: str, secs: float, **kw) -> None:
        if name in (COMPILE_EVENT, TRACE_EVENT):
            self.events.append((name, time.perf_counter(), secs))

    def _on_event(self, name: str, **kw) -> None:
        if name in (CACHE_HIT, CACHE_MISS):
            self.events.append((name, time.perf_counter(), 0.0))

    def close(self) -> None:
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._on)
        jax.monitoring.unregister_event_listener(self._on_event)

    def between(self, name: str, lo: float, hi: float) -> int:
        return sum(1 for n, t, _ in self.events if n == name and lo <= t <= hi)

    def summary(self, lo: float, hi: float) -> str:
        comp = [s for n, t, s in self.events
                if n == COMPILE_EVENT and lo <= t <= hi]
        return (f"{len(comp)} backend compiles ({sum(comp):.3f}s), "
                f"{self.between(TRACE_EVENT, lo, hi)} traces, "
                f"{self.between(CACHE_HIT, lo, hi)} cache hits, "
                f"{self.between(CACHE_MISS, lo, hi)} cache misses")


def _counters() -> dict:
    from repro.obs import metrics
    snap = metrics.snapshot()["counters"]
    out = {}
    for key, v in snap.items():
        base = key.split("{", 1)[0]
        if base in COUNTERS:
            out[key] = v
    return out


def rehearsal_config(cfg: dict) -> dict:
    """A rehearsal's configuration: the same deployment at the tiny scale
    its ``rehearsal`` entry gives."""
    return dict(cfg, **cfg["rehearsal"])


def run(workload: str, seed: int, seconds: float, trace: bool,
        t_start: float, rehearsal: bool = False, dtype: str | None = None,
        log=print) -> dict:
    """Run one cell and return its result line as a dict. ``dtype`` runs
    the service in another storage precision (the control)."""
    cell = cells.load(workload)
    cfg = rehearsal_config(cell.config) if rehearsal else cell.config
    tr = cell.traffic
    devs = device.check_devices(cell.chips, rehearsal)
    dev = device.describe(devs)
    peaks = None if rehearsal else device.peaks_for(dev["kind"])
    import jax
    log(f"compile cache: {jax.config.jax_compilation_cache_dir}")

    from repro.graph.structure import Graph
    from bench.harness.service import Service

    compiles = CompileCounter()
    phases = [("start", t_start), ("imports and devices", time.perf_counter())]
    n, edges = kronecker_edges(cfg, seed)
    phases.append(("edges", time.perf_counter()))
    g = Graph.from_edges(n, edges)
    phases.append(("graph", time.perf_counter()))
    svc = Service(cfg, g, tr["templates"], float(tr.get("timeout_s", 300)),
                  dtype=dtype)
    phases.append(("service", time.perf_counter()))
    scratch = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        svc.warm(tr, phases)
        setup_s = time.perf_counter() - t_start
        log("set-up: " + ", ".join(
            f"{name} {t - t_prev:.3f}s" for (_, t_prev), (name, t)
            in zip(phases, phases[1:])))
        log(f"set-up compiles: {compiles.summary(t_start, t_start + setup_s)}")
        session = None
        if trace:
            from bench.harness.trace import Session
            session = Session(scratch, cpu=rehearsal)
        loop = {"closed": traffic.run_closed, "open": traffic.run_open}
        window = loop[tr["loop"]](svc.client, tr, seed, seconds,
                                  trace=session)
        mem = device.memory_peak_bytes(devs)
        counters = _counters()
        stats = svc.svc.stats()
        svc.close()
        reduced = None
        if session is not None:
            reduced = dict(session.reduce(), started=session.started,
                           stopped=session.stopped)
    finally:
        if svc.svc is not None:
            svc.close()
        shutil.rmtree(scratch, ignore_errors=True)
        shutil.rmtree(svc.workdir, ignore_errors=True)

    itemsize = 2 if (dtype or cfg.get("dtype")) == "bfloat16" else 4
    least_s = None if peaks is None else {
        t: least.least_seconds(cfg["templates"][t]["edges"],
                               cfg["templates"][t].get("root", 0), g.n,
                               g.m, itemsize, peaks)
        for t in tr["templates"]}
    data = RunData(setup_s, window, reduced, least_s)

    reqs = window.requests
    sent = [r for r in reqs if r.t_send is not None]
    late = sorted(r.t_send - (window.t0 + r.t_sched) for r in sent)
    log(f"window: {window.t_close - window.t0:.3f}s of sends, "
        f"{window.t_end - window.t0:.6f}s to the last answer; "
        f"{len(reqs)} requests")
    log(f"compiles in window: {compiles.summary(window.t0, window.t_end)}")
    if tr["loop"] == "open" and late:
        log(f"generator lateness: median {late[len(late) // 2]:.6f}s, "
            f"max {late[-1]:.6f}s")
    bad = [r for r in reqs if r.status not in ("done", "shed")]
    if bad:
        log(f"not answered: {len(bad)}; first: " + "; ".join(
            f"#{r.idx} {r.template} seed {r.seed} {r.status} http {r.http}: "
            f"{r.error}" for r in bad[:5]))
    lat = sorted(latencies(data))
    if lat:
        q = {p: percentile(lat, p) for p in (50, 90, 95, 99, 100)}
        tail = sorted(reqs, key=lambda r: -(r.t_done or 1e18)
                      + (window.t0 + r.t_sched))[: max(1, len(reqs) // 20)]
        log("latency quantiles: " + ", ".join(
            f"p{p} {v:.6f}s" for p, v in q.items()) + "; slowest 5%: "
            f"{sum(1 for r in tail if r.seed)} fresh-seed, "
            f"{sum(1 for r in tail if r.answer and r.answer.get('from_cache'))}"
            f" cached, templates "
            f"{json.dumps(dict(collections.Counter(r.template for r in tail)))}")
    its = [int(r.answer["iterations"]) for r in reqs if r.answer]
    log(f"colorings per request: {its[:64]}{' ...' if len(its) > 64 else ''}")
    log(f"program counters: {json.dumps(counters, sort_keys=True)}")
    log(f"service: {json.dumps(stats, sort_keys=True, default=str)}")

    t_ref = time.perf_counter()
    src, dst = simple_adjacency(n, edges)
    from bench.harness.reference import Reference
    ref = Reference(n, src, dst)
    correct, checks = check.compare(
        reqs, ref, cfg["templates"], seed, int(tr.get("check_sample", 4)),
        cfg["limits"])
    t_done = time.perf_counter()
    log(f"reference: {t_done - t_ref:.3f}s, "
        f"{compiles.summary(t_ref, t_done)}")
    compiles.close()

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = m.read(data)
        if v is not None:
            metrics[m.name] = {"value": v, "unit": m.unit}
    dev["memory_peak_bytes"] = mem
    out = {"correct": bool(correct), "attempted": len(reqs),
           "failed": check.failed(reqs), "metrics": metrics, "device": dev}
    if reduced is not None:
        dev["busy_s"] = reduced["busy_s"]
        dev["window_s"] = reduced["window_s"]
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    if rehearsal:
        out["rehearsal"] = True
    out["checks"] = checks
    return out


def print_checks(checks: dict) -> None:
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
