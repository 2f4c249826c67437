"""First-class query API: the one-stop facade over the counting stack.

The unit of a query is a :class:`~repro.core.templates.TemplateSpec` — a
serializable tree description (edge list + root + optional name) that
coerces from registry names, ``TreeTemplate`` objects, and raw edge lists.
A :class:`CountQuery` bundles N specs with a precision contract
(``rel_stderr`` target and/or ``max_iters`` budget) and engine knobs;
:func:`compile_query` lowers it onto a graph as one fused
:class:`~repro.core.engines.CountingEngine` per template size k, so
canonical rooted sub-templates shared *across* the bundle (leaf one-hots,
shared paths/stars, common caterpillar arms) are computed once per
coloring instead of once per template. Template identity everywhere is the
:attr:`~repro.core.templates.TreeTemplate.canonical_hash`, never a name.

Typical use::

    from repro.api import count, count_many, TemplateSpec

    res = count(g, "u5", rel_stderr=0.05)            # registry sugar
    print(res.estimate, "+-", res.stderr, res.ci95)

    bundle = ["u5", "path5", "star5", "u7"]          # motif vector
    for spec, r in zip(bundle, count_many(g, bundle, max_iters=64)):
        print(spec, r.estimate)

    chair = TemplateSpec(edges=((0, 1), (1, 2), (1, 3)))   # arbitrary tree
    count(g, chair, max_iters=32)
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from repro.core.colorsets import colorful_probability
from repro.core.engines import CountingEngine, build_engine
from repro.core.motif_features import motif_features
from repro.core.templates import TemplateSpec, as_template
from repro.launch.compile_cache import enable_compile_cache
from repro.service.requests import RequestResult, RunningStat

__all__ = [
    "TemplateSpec", "as_template", "CountQuery", "CompiledQuery",
    "RequestResult", "compile_query", "count", "count_many", "template",
    "motif_features", "DEFAULT_MAX_ITERS",
]

# hard iteration ceiling for queries that only set a rel_stderr target
DEFAULT_MAX_ITERS = 64


@dataclasses.dataclass
class CountQuery:
    """N templates + a precision contract + a budget, as one declarative
    query. ``templates`` coerces each entry through
    :meth:`TemplateSpec.of`; the contract mirrors the service's
    :class:`~repro.service.requests.CountRequest` (``rel_stderr`` adaptive
    target and/or ``max_iters`` cap, ``min_iters`` early-stop guard);
    ``memory_budget_bytes`` bounds each fused engine's device tables via
    the executor's memory model; ``reorder`` ("rcm" or "degree") permutes
    the graph once per engine for BSR locality, with results mapped back to
    the caller's vertex ids at the boundary."""

    templates: tuple[TemplateSpec, ...]
    rel_stderr: float | None = None
    max_iters: int | None = None
    min_iters: int = 4
    seed: int = 0
    engine: str = "pgbsc"
    plan: str = "optimized"
    round_size: int = 8
    memory_budget_bytes: int | None = None
    batch_size: int | None = None
    reorder: str | None = None

    def __post_init__(self):
        tpls = self.templates
        if isinstance(tpls, str) or not isinstance(tpls, (list, tuple)):
            tpls = (tpls,)
        self.templates = tuple(TemplateSpec.of(t) for t in tpls)

    def validate(self) -> None:
        if not self.templates:
            raise ValueError("query needs at least one template")
        if self.rel_stderr is None and self.max_iters is None:
            raise ValueError("query needs a precision contract: "
                             "rel_stderr and/or max_iters")
        if self.rel_stderr is not None and self.rel_stderr <= 0:
            raise ValueError(f"rel_stderr must be > 0, got {self.rel_stderr}")
        if self.max_iters is not None and self.max_iters < 1:
            raise ValueError(f"max_iters must be >= 1, got {self.max_iters}")

    @property
    def cap(self) -> int:
        return self.max_iters if self.max_iters is not None \
            else DEFAULT_MAX_ITERS


class CompiledQuery:
    """A :class:`CountQuery` lowered onto one graph.

    Templates are grouped by k (one coloring stream per k) and each group
    becomes a single fused-plan engine; :meth:`run` drives adaptive rounds
    per group and returns one :class:`RequestResult` per template, in query
    order. ``engines`` exposes the group engines (dispatch counters
    included) for introspection and tests.
    """

    def __init__(self, g, query: CountQuery, engine_cache=None):
        query.validate()
        self.g = g
        self.query = query
        by_k: dict[int, list[int]] = {}
        for i, spec in enumerate(query.templates):
            by_k.setdefault(spec.k, []).append(i)
        kw = {}
        if query.memory_budget_bytes is not None:
            kw["memory_budget_bytes"] = int(query.memory_budget_bytes)
        if query.reorder:
            kw["reorder"] = query.reorder
        self.groups: list[tuple[list[int], CountingEngine]] = []
        for k in sorted(by_k):
            idxs = by_k[k]
            specs = [query.templates[i] for i in idxs]
            tpl = specs if len(specs) > 1 else specs[0]
            if engine_cache is not None:
                eng = engine_cache.get(g, tpl, query.engine, query.plan, **kw)
            else:
                trees = [s.tree for s in specs]
                eng = build_engine(g, trees if len(trees) > 1 else trees[0],
                                   query.engine, plan=query.plan, **kw)
            self.groups.append((idxs, eng))

    @property
    def engines(self) -> list[CountingEngine]:
        return [eng for _, eng in self.groups]

    def _satisfied(self, stat: RunningStat) -> bool:
        q = self.query
        if stat.n >= q.cap:
            return True
        return (q.rel_stderr is not None
                and stat.n >= min(q.min_iters, q.cap)
                and stat.rel_stderr <= q.rel_stderr)

    def run(self) -> list[RequestResult]:
        q = self.query
        out: list[RequestResult | None] = [None] * len(q.templates)
        for idxs, eng in self.groups:
            t0 = time.time()
            p = colorful_probability(eng.k)
            scales = [1.0 / (q.templates[i].automorphisms * p) for i in idxs]
            stats = [RunningStat() for _ in idxs]
            cursor = 0
            while not all(self._satisfied(s) for s in stats):
                n_new = min(q.round_size, q.cap - cursor)
                if n_new <= 0:
                    break
                ids = list(range(cursor, cursor + n_new))
                per = eng.count_iterations_batch(ids, seed=q.seed,
                                                 batch_size=q.batch_size)
                for it in ids:
                    vals = np.atleast_1d(np.asarray(per[it]))
                    for j, stat in enumerate(stats):
                        # retired templates stop consuming, exactly like
                        # service requests that met their target
                        if not self._satisfied(stat):
                            stat.update(float(vals[j]) * scales[j])
                cursor += n_new
            seconds = time.time() - t0
            for j, i in enumerate(idxs):
                stat = stats[j]
                out[i] = RequestResult(
                    estimate=stat.mean, stderr=stat.stderr,
                    rel_stderr=stat.rel_stderr, ci95=stat.ci95,
                    iterations=stat.n,
                    target_met=(q.rel_stderr is None
                                or stat.rel_stderr <= q.rel_stderr),
                    shared_group=len(idxs) > 1, seconds=seconds)
        return out


def compile_query(g, query: CountQuery, engine_cache=None) -> CompiledQuery:
    """Lower a :class:`CountQuery` onto ``g``: one fused engine per k-group
    (served from ``engine_cache`` when given — keys are canonical hashes,
    so two spellings of the same tree share one engine). Turns on the
    persistent compilation cache first (:func:`enable_compile_cache`)."""
    enable_compile_cache()
    return CompiledQuery(g, query, engine_cache=engine_cache)


def count_many(g, templates, *, rel_stderr: float | None = None,
               max_iters: int | None = None, min_iters: int = 4,
               seed: int = 0, engine: str = "pgbsc", plan: str = "optimized",
               round_size: int = 8, memory_budget_bytes: int | None = None,
               batch_size: int | None = None, reorder: str | None = None,
               engine_cache=None) -> list[RequestResult]:
    """Estimate counts for N templates with cross-template subplan sharing.

    Accepts any mix of registry names, :class:`TemplateSpec`, TreeTemplate
    objects, and raw edge lists; returns one result per template, in input
    order. Same-k templates run on ONE fused plan, so their shared
    canonical sub-templates cost one SpMM per coloring for the whole
    bundle; each template's samples still come from exactly the colorings a
    solo :func:`count` with the same seed would draw, so the estimates
    agree with per-template runs to floating-point reassociation.
    """
    if rel_stderr is None and max_iters is None:
        max_iters = DEFAULT_MAX_ITERS
    if isinstance(templates, str):    # a bare name is one template, not
        templates = (templates,)      # an iterable of characters
    query = CountQuery(
        templates=tuple(templates), rel_stderr=rel_stderr,
        max_iters=max_iters, min_iters=min_iters, seed=seed, engine=engine,
        plan=plan, round_size=round_size,
        memory_budget_bytes=memory_budget_bytes, batch_size=batch_size,
        reorder=reorder)
    return compile_query(g, query, engine_cache=engine_cache).run()


def count(g, template, **kw) -> RequestResult:
    """Estimate the count of one template (see :func:`count_many` for the
    accepted template forms and keywords)."""
    return count_many(g, [template], **kw)[0]


def template(obj) -> TemplateSpec:
    """Coerce anything template-ish into a :class:`TemplateSpec`."""
    return TemplateSpec.of(obj)
