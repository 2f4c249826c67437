"""Zero-dependency span tracer with a no-op fast path, and device scopes.

One process-wide :class:`Tracer` (swap it with :func:`set_tracer`) produces
nested, labeled :class:`Span`\\ s via the :func:`span` context manager::

    from repro.obs import tracing
    with tracing.span("service.dispatch", group="u5", n=8):
        ...

Disabled (the default), :func:`span` returns one shared no-op context
manager — no allocation beyond the kwargs dict, no clock read — so hot
loops can be instrumented unconditionally. The tests bound this overhead.

Spans measure *host wall time of the code they wrap*. Device work is named
instead, by :func:`device_scope` (a ``jax.named_scope``): every device op
traced inside carries the scope path in its HLO ``op_name`` metadata, e.g.
``jit(seeded)/plan.node3/kernel.spmm/while/body/scatter-add``, which a
profiler trace reports per op. A scope changes metadata only, never the
compiled program, so it costs nothing at run time. The scope names are the
constants below, kept stable so that per-kernel device time can be compared
across rewrites of a kernel.

Two timing refinements:

* ``sync=True`` makes :func:`sync_ready` call ``jax.block_until_ready``
  inside the enclosing span, so the span measures device time instead of
  async dispatch time (jax is imported lazily; the tracer itself has no
  jax dependency).
* ``profiler=True`` opens a ``jax.profiler.TraceAnnotation`` with each span
  (its attributes included), putting the program's spans on the profiler's
  clock beside the device ops. :func:`profile` turns it on for a whole run
  while a ``jax.profiler`` trace is written.
"""

from __future__ import annotations

import contextlib
import threading
import time

__all__ = [
    "Span", "Tracer", "get_tracer", "set_tracer", "configure", "span",
    "enabled", "sync_ready", "device_scope", "profile",
    "KERNEL_LEAF", "KERNEL_SPMM", "KERNEL_EMA", "KERNEL_FUSED", "KERNEL_ROOT",
    "PLAN_NODE",
]

# Device scope names (see :func:`device_scope`). A plan node's scope is
# ``PLAN_NODE`` followed by its index in the plan, e.g. ``plan.node3``.
KERNEL_LEAF = "kernel.leaf"      # one-hot leaf tables of a coloring
KERNEL_SPMM = "kernel.spmm"      # neighbour sums, every SpMM backend
KERNEL_EMA = "kernel.ema"        # split combination of child tables
KERNEL_FUSED = "kernel.fused"    # SpMM and eMA in one Pallas launch
KERNEL_ROOT = "kernel.root"      # sums over the root tables
PLAN_NODE = "plan.node"


class Span:
    """One timed, labeled region; nested spans become children."""

    __slots__ = ("name", "attrs", "t0", "t1", "children", "_tracer", "_ann")

    def __init__(self, tracer: "Tracer", name: str, attrs: dict):
        self._tracer = tracer
        self.name = name
        self.attrs = attrs
        self.t0 = 0.0
        self.t1 = 0.0
        self.children: list[Span] = []
        self._ann = None

    @property
    def seconds(self) -> float:
        return max(0.0, self.t1 - self.t0)

    def set(self, **attrs) -> "Span":
        """Attach attributes mid-span (e.g. a result computed inside)."""
        self.attrs.update(attrs)
        return self

    def __enter__(self) -> "Span":
        self._tracer._push(self)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = time.perf_counter()
        self._tracer._pop(self)
        return False

    def to_dict(self) -> dict:
        return {"name": self.name, "seconds": self.seconds,
                "attrs": dict(self.attrs),
                "children": [c.to_dict() for c in self.children]}

    def __repr__(self) -> str:
        return f"Span({self.name}, {self.seconds * 1e3:.3f}ms, " \
               f"{len(self.children)} children)"


class _NullSpan:
    """Shared do-nothing span: the disabled-tracer fast path."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **attrs) -> "_NullSpan":
        return self


_NULL = _NullSpan()


class Tracer:
    """Collects finished root spans; nesting follows a per-thread stack.
    With ``profiler`` set, each span is also a ``TraceAnnotation``."""

    def __init__(self, enabled: bool = True, sync: bool = False,
                 max_roots: int = 10_000, profiler: bool = False):
        self.enabled = bool(enabled)
        self.sync = bool(sync)
        self.profiler = bool(profiler)
        self.max_roots = int(max_roots)
        self.roots: list[Span] = []
        self._local = threading.local()

    # ------------------------------------------------------------- plumbing
    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def _push(self, sp: Span) -> None:
        if self.profiler:
            import jax.profiler
            # TraceMe encodes attributes as "name#k=v,...#": keep their
            # values free of the two separators
            sp._ann = jax.profiler.TraceAnnotation(sp.name, **{
                k: str(v).replace(",", " ").replace("#", " ")
                for k, v in sp.attrs.items()})
            sp._ann.__enter__()
        self._stack().append(sp)

    def _pop(self, sp: Span) -> None:
        st = self._stack()
        if st and st[-1] is sp:
            st.pop()
        if st:
            st[-1].children.append(sp)
        elif len(self.roots) < self.max_roots:
            self.roots.append(sp)
        if sp._ann is not None:
            sp._ann.__exit__(None, None, None)
            sp._ann = None

    # ------------------------------------------------------------------ api
    def span(self, name: str, **attrs):
        if not self.enabled:
            return _NULL
        return Span(self, name, attrs)

    def reset(self) -> None:
        self.roots = []
        self._local = threading.local()

    def breakdown(self) -> dict[str, dict]:
        """Aggregate ``{span name: {count, seconds}}`` over the whole tree."""
        agg: dict[str, dict] = {}

        def walk(sp: Span) -> None:
            ent = agg.setdefault(sp.name, {"count": 0, "seconds": 0.0})
            ent["count"] += 1
            ent["seconds"] += sp.seconds
            for c in sp.children:
                walk(c)

        for r in self.roots:
            walk(r)
        return agg


# ---------------------------------------------------------------- globals
_tracer = Tracer(enabled=False)


def get_tracer() -> Tracer:
    return _tracer


def set_tracer(t: Tracer) -> Tracer:
    global _tracer
    _tracer = t
    return t


def configure(enabled: bool | None = None, sync: bool | None = None) -> Tracer:
    """Flip the process tracer's switches in place; returns it."""
    if enabled is not None:
        _tracer.enabled = bool(enabled)
    if sync is not None:
        _tracer.sync = bool(sync)
    return _tracer


def span(name: str, **attrs):
    """Context manager for one span on the process tracer (no-op when
    tracing is disabled — safe in hot loops)."""
    t = _tracer
    if not t.enabled:
        return _NULL
    return Span(t, name, attrs)


def enabled() -> bool:
    return _tracer.enabled


def sync_ready(x) -> None:
    """Block on a jax value inside the enclosing span iff the tracer asks
    for device-sync timing (``sync=True``); otherwise free."""
    if _tracer.enabled and _tracer.sync:
        import jax
        jax.block_until_ready(x)


def device_scope(name: str):
    """Context manager naming the device ops traced inside it: a thin
    ``jax.named_scope`` over one of the scope constants of this module."""
    import jax
    return jax.named_scope(name)


@contextlib.contextmanager
def profile(log_dir: str):
    """Write a ``jax.profiler`` trace of the enclosed run to ``log_dir``,
    with the process tracer enabled in profiler mode, so that its spans
    (and their attributes) share the trace's clock with the device ops.
    The tracer's switches are restored on exit. A profiler that fails to
    start raises: a run that asked for a trace must not run untraced."""
    import jax.profiler

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # spans, not every Python call
    opts.host_tracer_level = 2        # runtime events under the spans
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    t = _tracer
    saved = (t.enabled, t.profiler)
    t.enabled = t.profiler = True
    try:
        yield t
    finally:
        t.enabled, t.profiler = saved
        jax.profiler.stop_trace()
