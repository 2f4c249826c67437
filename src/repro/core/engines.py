"""The three counting engines: FASCIA, PFASCIA, PGBSC (paper §3-4).

All three compute the same quantity — the number of colorful rooted
embeddings of each sub-template, bottom-up over the execution plan — but with
the paper's three performance regimes:

* ``fascia``   Algorithm 1: vertex-centric; the neighbor sum of the passive
               child is recomputed for every (color set, split) pair —
               O(E * C(k,t) * C(t,t_p)) per sub-template. Row-major (N, C)
               tables, padded-neighbor (ELL) traversal.
* ``pfascia``  + pruning (§4.1-4.2): neighbor sums hoisted out and computed
               once per distinct passive color set —
               O(E * C(k,t_p) + V * C(k,t) * C(t,t_a)). Still row-major.
* ``pgbsc``    + GraphBLAS (§4.3-4.5): combination-major (C, N) tables
               (vertices on TPU lanes), SpMM = A_G x M_p batched over all
               passive color sets, eMA fused multiply-add — optionally via
               the Pallas TPU kernels.

Exact arithmetic would make them identical (paper §7.4); floating-point
reassociation yields ~1e-6 relative differences, which the tests bound.

All three engines execute their plan through the shared
:class:`repro.core.executor.PlanExecutor`: one liveness-managed,
min-peak-scheduled bottom-up walk, parameterized only by the passive
transform (SpMM vs. hoisted neighbor sum vs. none) and the combine step.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from math import comb
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import colorsets as cs
from repro.core import executor as pexec
from repro.core.templates import (ExecutionPlan, as_template,
                                  compile_fused_plan)
from repro.graph.reorder import ORDERINGS, apply_order, inverse_order
from repro.graph.structure import Graph
from repro.kernels import resolve_interpret
from repro.kernels.ema import ops as ema_ops
from repro.kernels.fused import ops as fused_ops
from repro.kernels.spmm import ops as spmm_ops
from repro.obs import metrics as _metrics
from repro.obs import tracing as _tracing

__all__ = ["CountingEngine", "build_engine", "ENGINES"]

ENGINES = ("fascia", "pfascia", "pgbsc")


@dataclasses.dataclass
class WorkEstimate:
    """Static op counts for ONE coloring (used by benchmarks/roofline).

    All per-coloring fields share units, so flops/bytes ratios are valid
    arithmetic intensities. ``table_bytes`` is dtype-aware (C(k,t) x N x
    itemsize summed over internal plan nodes); ``batch`` records the
    engine's dispatch batch size, and the ``dispatch_*`` properties give
    the per-device-call totals.
    """

    spmm_flops: int = 0
    ema_flops: int = 0
    table_bytes: int = 0
    batch: int = 1

    @property
    def total_flops(self) -> int:
        return self.spmm_flops + self.ema_flops

    @property
    def dispatch_flops(self) -> int:
        return self.total_flops * self.batch

    @property
    def dispatch_table_bytes(self) -> int:
        return self.table_bytes * self.batch


class CountingEngine:
    """Counts colorful embeddings of one template — or a fused bundle of
    same-k templates — for a given coloring.

    Call :meth:`count_colorful` with an (n,) int32 coloring; returns the
    scalar sum over the root table (= alpha x #colorful copies) and the root
    table itself. :meth:`estimate` runs the full color-coding estimator.

    Multi-template fusion
    ---------------------
    Passing a list/tuple of equal-k templates builds ONE fused
    :class:`~repro.core.templates.FusedPlan`: canonical rooted sub-templates
    shared across the bundle are computed once per coloring (tables and
    their passive SpMMs alike), every template's root table is a kept output
    of the same walk, and the totals come back as a ``(T,)`` vector (or
    ``(B, T)`` batched). ``n_spmm_cols_dispatched`` counts the SpMM
    column-ops actually dispatched, so the cross-template savings are
    directly observable against a per-template engine sum.

    Memory management
    -----------------
    Plan execution is scheduled by ``core/executor.py``: node tables and
    cached SpMM results are freed at their statically computed last use and
    the bottom-up walk is ordered to minimize the peak live table bytes.
    A single ``memory_budget_bytes`` knob (default
    ``executor.DEFAULT_MEMORY_BUDGET_BYTES``) is turned into the coloring
    ``batch_size`` by the analytic memory model; when even one coloring
    exceeds the budget (large k), the pgbsc SpMM/eMA switch to
    colorset-chunked execution that splits the ``C(k, t_p)`` passive axis
    so the neighbor-sum table is never materialized whole. Pass
    ``batch_size`` explicitly to override the derived batch.

    Batching
    --------
    Color-coding iterations are independent, so the execution plan admits a
    batch dimension over colorings. :meth:`count_colorful_batch` takes a
    (B, n) batch and runs the whole plan as ONE jitted device call: for
    ``pgbsc`` the count tables become (B, C, N) and the SpMM/eMA kernels fold
    the batch into their row dimension (one kernel launch per plan node for
    the whole batch); for ``fascia``/``pfascia`` the single-coloring program
    is ``vmap``-ed. :meth:`count_iterations_batch` goes further and derives
    the colorings device-side from ``fold_in(seed, iteration)`` *inside* the
    jit, so an estimator checkpoint batch is a single dispatch with no
    host->device coloring transfers.

    ``batch_size`` bounds peak memory: a batch of B colorings holds, per live
    plan node of size t, a ``B x C(k, t) x N`` table (plus one SpMM output
    of the same shape), so chunks of ``batch_size`` colorings are
    dispatched at a time and ragged tails are padded to keep one compiled
    program shape. Batched results match the per-coloring path to ~1e-6
    relative error (floating-point reassociation only).
    """

    def __init__(self, g: Graph, template, engine: str = "pgbsc",
                 spmm_method: str = "segment", use_pallas_ema: bool = False,
                 interpret: bool | None = None, dedup: bool = False,
                 plan: str | None = None, dtype=jnp.float32,
                 batch_size: int | None = None,
                 memory_budget_bytes: int | None = None,
                 fuse_spmm_ema: bool = False,
                 autotune_blocks: bool = False,
                 reorder: str | None = None):
        if engine not in ENGINES:
            raise ValueError(f"unknown engine {engine!r}")
        if reorder not in (None, "", *ORDERINGS):
            raise ValueError(f"unknown reorder {reorder!r}; "
                             f"choose from {sorted(ORDERINGS)} or None")
        if isinstance(template, (list, tuple)):
            if not template:
                raise ValueError("engine needs at least one template")
            templates = tuple(as_template(t) for t in template)
        else:
            templates = (as_template(template),)
        ks = sorted({t.k for t in templates})
        if len(ks) != 1:
            raise ValueError(
                f"one engine fuses equal-k templates only, got k={ks}; "
                "group by k first (repro.api.count_many does)")
        # Vertex reordering: permute the graph ONCE here; the entire plan
        # walk runs in the permuted vertex space, and only the engine
        # boundary permutes (colorings in, root tables out) — see
        # _wrap_reorder. Block-count/density before vs after are published
        # as gauges so the locality win is observable per graph.
        self.reorder = reorder or None
        self.g_orig = g
        if self.reorder:
            before = g.bsr_block_stats()
            self._order = ORDERINGS[self.reorder](g)
            g = apply_order(g, self._order)
            after = g.bsr_block_stats()
            for stage, stats in (("before", before), ("after", after)):
                _metrics.gauge("reorder_bsr_occupied_blocks",
                               reorder=self.reorder, stage=stage
                               ).set(stats["occupied_blocks"])
                _metrics.gauge("reorder_bsr_block_density",
                               reorder=self.reorder, stage=stage
                               ).set(stats["block_density"])
        else:
            self._order = None
        self.g = g
        self.templates = templates
        self.template = templates[0]
        self.fused = len(templates) > 1
        self.engine = engine
        self.k = ks[0]
        self.dtype = dtype
        self.spmm_method = spmm_method
        self.memory_budget_bytes = memory_budget_bytes
        plan_name = plan or ("dedup" if dedup else "plain")
        if self.fused:
            if plan_name == "plain":
                raise ValueError(
                    "plan='plain' is meaningless for a fused multi-template "
                    "engine: cross-template fusion IS canonical dedup; use "
                    "plan='dedup' or plan='optimized'")
            # cross-template canonical dedup: one plan, one root per template
            fp = compile_fused_plan(templates,
                                    optimize=(plan_name == "optimized"))
            self.plan: ExecutionPlan = fp.plan
            self.roots: tuple[int, ...] = fp.roots
        else:
            self.plan = {
                "plain": self.template.plan, "dedup": self.template.plan_dedup,
                "optimized": self.template.plan_optimized}[plan_name]
            self.roots = (self.plan.n_nodes - 1,)
        self.use_pallas_ema = use_pallas_ema
        self.interpret = resolve_interpret(interpret)
        self.autotune_blocks = autotune_blocks
        self.fuse_spmm_ema = bool(fuse_spmm_ema and engine == "pgbsc")
        # per-node fusion decisions (idx -> "admitted" | "admitted_shared" |
        # rejection reason); empty when fusion was not requested
        self.fusion_report: dict[int, str] = {}
        fused_nodes, fused_groups = (self._fused_candidates()
                                     if self.fuse_spmm_ema else ((), ()))

        # budget -> (derived batch size, liveness schedule, chunking); an
        # explicit batch_size only overrides the batch, not the schedule.
        # Every fused root is a kept output (never freed by the walk).
        keep = tuple(i for i in self.roots if i != self.plan.n_nodes - 1)
        self.exec_choice = pexec.pick_execution(
            self.plan, self.k, g.n,
            memory_budget_bytes=memory_budget_bytes, dtype=dtype,
            passive_cache=(engine != "fascia"),
            allow_chunking=(engine == "pgbsc"), keep=keep,
            fused=fused_nodes, fused_groups=fused_groups)
        self.schedule = self.exec_choice.schedule
        self.batch_size = int(batch_size if batch_size is not None
                              else self.exec_choice.batch_size)

        self._materialize()
        self.work = self._estimate_work()
        self.spmm_cols_per_coloring = self._spmm_cols_per_coloring()
        # what the last program :meth:`warm` compiled holds (see
        # :meth:`execution_report`); empty until a width is compiled
        self.compiled_report: dict = {}
        for name, value in self.execution_decision().items():
            _metrics.gauge(f"engine_{name}", **self._mem_labels).set(value)
        # dispatch accounting (service/benchmark introspection): device calls
        # through the batched pipeline, coloring rows computed by them
        # (padding rows included — they are real device work), and SpMM
        # column-ops those colorings cost (the fused-plan savings metric)
        self.n_batch_dispatches = 0
        self.n_colorings_dispatched = 0
        self.n_spmm_cols_dispatched = 0

    def _fused_candidates(self) -> tuple[tuple[int, ...],
                                         tuple[tuple[int, ...], ...]]:
        """Plan nodes eligible for the fused SpMM->eMA kernel, plus the
        shared-passive groups among them — returns ``(fused, groups)``.

        A sole consumer of its passive child fuses alone when (a) its
        resident tables fit one VMEM grid step and (b) the table dtype runs
        on the kernel path in this mode (otherwise the explicit XLA fallback
        would materialize y and the memory model would lie).

        Consumers SHARING a passive child fuse as a group: one launch whose
        SpMM leg runs once into shared VMEM scratch (the y-cache's dedup win
        without the HBM round-trip). A group is admitted only when it covers
        the passive's ENTIRE consumer set — partial groups would re-run the
        SpMM for the leftovers, regressing the once-per-child column count
        the y-cache guarantees — and only when it can actually run as one
        launch: no member's active child is itself a member (the launch
        cannot consume its own outputs), every member fits a singleton grid
        step, the combined working set passes the group VMEM fit, and the
        members can be made consecutive in program order (no outside
        consumer of a member sits at or before the latest member). The
        chain-shaped consumer sets of path-like templates fail the
        intra-dependency test by construction and stay on the y-cache; the
        win case is template ROOTS sharing a canonical passive sub-template
        (they have no consumers at all).

        Every decision lands in :attr:`fusion_report` (``{plan node idx:
        "admitted" | "admitted_shared" | rejection reason}``) and in the
        reason-labeled ``fusion_admissions_total`` counters, so a user
        asking for fusion can see exactly which nodes got it and why the
        rest did not.
        """
        dtype_ok = ema_ops.pallas_supports_dtype(self.dtype, self.interpret)
        consumers: dict[int, list[int]] = {}
        cons_any: dict[int, list[int]] = {}
        for idx, node in enumerate(self.plan.nodes):
            if node.is_leaf:
                continue
            consumers.setdefault(node.passive, []).append(idx)
            cons_any.setdefault(node.active, []).append(idx)
            cons_any.setdefault(node.passive, []).append(idx)

        def dims(idx: int) -> tuple[int, int, int, int]:
            node = self.plan.nodes[idx]
            t = node.size
            t_a = self.plan.nodes[node.active].size
            return (comb(self.k, t_a), comb(self.k, t - t_a),
                    comb(self.k, t), comb(t, t_a))

        def group_fits(members: list[int]) -> bool:
            c_p = dims(members[0])[1]
            c_as = [dims(m)[0] for m in members]
            ss = [dims(m)[2] for m in members]
            ls = [dims(m)[3] for m in members]
            return fused_ops.fused_group_fits_vmem(c_as, c_p, ss, ls,
                                                   dtype=self.dtype)

        def order_ok(members: list[int]) -> bool:
            # regrouping moves members to the LAST member's slot; any
            # outside consumer of a member scheduled at or before that slot
            # would then precede its producer
            anchor = max(members)
            mset = set(members)
            return all(c > anchor or c in mset
                       for m in members for c in cons_any.get(m, []))

        out: list[int] = []
        groups: list[tuple[int, ...]] = []
        for idx, node in enumerate(self.plan.nodes):
            if node.is_leaf:
                continue
            if not dtype_ok:
                self.fusion_report[idx] = "dtype_unsupported"
            elif len(consumers[node.passive]) == 1:
                if group_fits([idx]):
                    self.fusion_report[idx] = "admitted"
                    out.append(idx)
                else:
                    self.fusion_report[idx] = "vmem_overflow"
            else:
                # default for shared-passive consumers; members of an
                # accepted group are upgraded to "admitted_shared" below
                self.fusion_report[idx] = "multi_consumer"
        if dtype_ok:
            for p, cons in sorted(consumers.items()):
                if len(cons) < 2:
                    continue
                mset = set(cons)
                if (all(group_fits([i]) for i in cons)
                        and not any(self.plan.nodes[m].active in mset
                                    for m in cons)
                        and group_fits(cons)
                        and order_ok(cons)):
                    grp = tuple(sorted(cons))
                    groups.append(grp)
                    for m in grp:
                        self.fusion_report[m] = "admitted_shared"
                        out.append(m)
        for idx, verdict in self.fusion_report.items():
            if verdict == "admitted":
                _metrics.counter("fusion_admissions_total",
                                 outcome="admitted").inc()
            elif verdict == "admitted_shared":
                _metrics.counter("fusion_admissions_total",
                                 outcome="admitted", mode="shared").inc()
            else:
                _metrics.counter("fusion_admissions_total",
                                 outcome="rejected", reason=verdict).inc()
        return tuple(sorted(out)), tuple(groups)

    # -------------------------------------------------------- device state
    def _materialize(self) -> None:
        """Build device arrays and compiled callables (see :meth:`release`)."""
        with _tracing.span("engine.materialize", engine=self.engine,
                           k=self.k):
            self._materialize_inner()

    def _materialize_inner(self) -> None:
        g = self.g
        if self._order is not None:
            # device copies of the boundary permutation (order: coloring in,
            # inv: root table out); rebuilt after release() like every prep
            self._order_dev = jnp.asarray(self._order, jnp.int32)
            self._inv_dev = jnp.asarray(inverse_order(self._order), jnp.int32)
        else:
            self._order_dev = self._inv_dev = None
        if self.engine == "pgbsc":
            self._spmm_prep = spmm_ops.prepare(
                g, self.spmm_method, interpret=self.interpret,
                dtype=self.dtype, reorder=self.reorder or "")
            self._nbr = self._mask = None
            self._fused_prep = (
                fused_ops.prepare_fused(g, interpret=self.interpret,
                                        dtype=self.dtype,
                                        reorder=self.reorder or "")
                if self.schedule.fused else None)
        else:
            nbr, mask = g.ell()
            self._spmm_prep = None
            self._fused_prep = None
            self._nbr = jnp.asarray(nbr)
            self._mask = jnp.asarray(mask)

        # Static split tables per internal plan node (+ chunked repacking
        # for nodes the memory model decided to colorset-chunk).
        self._splits: dict[int, tuple[jnp.ndarray, jnp.ndarray]] = {}
        self._chunk_packs: dict[int, ema_ops.ChunkedSplits] = {}
        chunk_map = self.schedule.chunk_map
        for idx, node in enumerate(self.plan.nodes):
            if node.is_leaf:
                continue
            t = node.size
            t_a = self.plan.nodes[node.active].size
            ia, ip = cs.split_tables(self.k, t, t_a)
            self._splits[idx] = (jnp.asarray(ia), jnp.asarray(ip))
            q = chunk_map.get(idx, 1)
            if q > 1:
                self._chunk_packs[idx] = ema_ops.pack_chunked_splits(
                    ia, ip, comb(self.k, t - t_a), q,
                    pair_block=pexec.PAIR_BLOCK)

        self._count_fn = jax.jit(self._build())
        self._batch_fn = None    # built lazily on first batched call
        self._seeded_fn = None   # jit(seed, iteration ids) -> batch totals
        self._warm_widths: set[int] = set()   # seeded widths compiled
        self._released = False
        # trace-time watermark: peak live table bytes observed by the
        # executor's on_step probe (high-watermark across traced shapes)
        self._trace_peak_bytes = 0
        # pre-resolved registry counters: one attribute add per dispatch
        label = self.templates[0].name or "t"
        self._m_dispatches = _metrics.counter(
            "engine_dispatches_total", engine=self.engine)
        self._m_colorings = _metrics.counter(
            "engine_colorings_dispatched_total", engine=self.engine)
        self._m_spmm_cols = _metrics.counter(
            "engine_spmm_cols_dispatched_total", engine=self.engine)
        self._mem_labels = dict(engine=self.engine, template=label,
                                k=self.k)

    def _operands(self) -> dict:
        """The graph-sized device arrays every count program reads. They
        are passed to the jitted programs as arguments: an array a program
        closes over is embedded in it as a constant, and at RMAT scale 20
        that makes each executable hundreds of MB to compile and cache."""
        return {"spmm": self._spmm_prep, "fused": self._fused_prep,
                "nbr": self._nbr, "mask": self._mask,
                "order": self._order_dev, "inv": self._inv_dev}

    def _peak_probe(self, step: int, live_bytes: int) -> None:
        """Executor ``on_step`` hook: record the measured (trace-time) peak
        live table bytes of the plan walk — the watermark the memory-model
        validation gauges publish next to the analytic prediction."""
        if live_bytes > self._trace_peak_bytes:
            self._trace_peak_bytes = live_bytes

    @property
    def measured_peak_bytes(self) -> int:
        """Watermark from the last traced plan walk(s); 0 before any
        count call. Compare against :attr:`peak_table_bytes` (the model)."""
        return self._trace_peak_bytes

    def _publish_memory_gauges(self, batch: int) -> None:
        measured = self._trace_peak_bytes
        if not measured:
            return
        model = self.exec_choice.peak_bytes_per_coloring * max(batch, 1)
        _metrics.gauge("memory_measured_peak_bytes",
                       **self._mem_labels).set(measured)
        _metrics.gauge("memory_model_peak_bytes",
                       **self._mem_labels).set(model)
        if model:
            _metrics.gauge("memory_model_ratio",
                           **self._mem_labels).set(measured / model)

    def release(self) -> None:
        """Drop device arrays and compiled executables.

        Called by the service's :class:`~repro.service.cache.EngineCache`
        on eviction so a bounded cache actually bounds device memory. The
        engine stays usable: the next count call rebuilds lazily from the
        host-side graph.
        """
        for name in ("_count_fn", "_batch_fn", "_seeded_fn"):
            fn = getattr(self, name, None)
            if fn is not None and hasattr(fn, "clear_cache"):
                try:
                    fn.clear_cache()
                except Exception:
                    pass
        self._count_fn = self._batch_fn = self._seeded_fn = None
        self._warm_widths = set()
        self._spmm_prep = None
        self._fused_prep = None
        self._nbr = self._mask = None
        self._order_dev = self._inv_dev = None
        self._splits = {}
        self._chunk_packs = {}
        self._released = True

    def _ensure(self) -> None:
        if self._released:
            self._materialize()

    # ------------------------------------------------------------------ api
    def count_colorful(self, colors: jax.Array) -> tuple[jax.Array, jax.Array]:
        """-> (sum over root table, root table).

        For a fused engine the sum is a ``(T,)`` vector (one entry per
        template) and the second element is the tuple of root tables.
        """
        self._ensure()
        self.n_spmm_cols_dispatched += self.spmm_cols_per_coloring
        self._m_spmm_cols.inc(self.spmm_cols_per_coloring)
        with _tracing.span("engine.dispatch", engine=self.engine, batch=1):
            out = self._count_fn(self._operands(), jnp.asarray(colors))
            _tracing.sync_ready(out)
        self._publish_memory_gauges(1)
        return out

    def count_colorful_batch(self, colorings: jax.Array,
                             batch_size: int | None = None
                             ) -> tuple[jax.Array, jax.Array]:
        """Batched :meth:`count_colorful` over a (B, n) coloring batch.

        -> (totals (B,), root tables (B, ...)); a fused engine returns
        totals (B, T) and a T-tuple of root-table batches. The batch is
        chunked to
        ``batch_size`` (default: the budget-derived knob) colorings per
        device call; ragged tails are padded with the last coloring (and
        sliced off) so every chunk reuses one compiled program shape.
        """
        self._ensure()
        colorings = jnp.asarray(colorings)
        if colorings.ndim != 2:
            raise ValueError(f"expected (B, n) colorings, got "
                             f"{colorings.shape}")
        b = colorings.shape[0]
        if b == 0:
            # totals come out of the accumulator-dtype reduction, so the
            # empty case must match (f32 for bf16 storage)
            empty = jnp.zeros((0, len(self.templates)) if self.fused
                              else (0,), ema_ops.accum_dtype(self.dtype))
            return empty, (() if self.fused else empty)
        # clamped to b: steady-state short calls (e.g. a runner checkpointing
        # every 4 with knob 16) must not pay 4x padded compute; the cost is
        # at most one extra compiled shape per distinct call length, and
        # ragged tails within a call still pad to bs below
        bs = min(batch_size or self.batch_size or b, b)
        if self._batch_fn is None:
            self._batch_fn = jax.jit(self._build_batch())
        totals, roots = [], []
        for base in range(0, b, bs):
            chunk = colorings[base: base + bs]
            pad = bs - chunk.shape[0]
            if pad:
                fill = jnp.broadcast_to(chunk[-1:], (pad,) + chunk.shape[1:])
                chunk = jnp.concatenate([chunk, fill])
            first = self.n_batch_dispatches == 0
            with _tracing.span("engine.dispatch", engine=self.engine,
                               batch=bs, first=first):
                tot, root = self._batch_fn(self._operands(), chunk)
                _tracing.sync_ready(tot)
            self.n_batch_dispatches += 1
            self.n_colorings_dispatched += bs
            self.n_spmm_cols_dispatched += self.spmm_cols_per_coloring * bs
            self._m_dispatches.inc()
            self._m_colorings.inc(bs)
            self._m_spmm_cols.inc(self.spmm_cols_per_coloring * bs)
            totals.append(tot[: bs - pad])
            roots.append(tuple(r[: bs - pad] for r in root) if self.fused
                         else root[: bs - pad])
        self._publish_memory_gauges(bs)
        if self.fused:
            root_out = tuple(jnp.concatenate([r[j] for r in roots])
                             for j in range(len(self.roots)))
        else:
            root_out = jnp.concatenate(roots)
        return jnp.concatenate(totals), root_out

    def count_iterations_batch(self, iterations, seed: int = 0,
                               batch_size: int | None = None
                               ) -> dict:
        """Colorful sums for explicit iteration ids, batched device-side.

        -> ``{iteration id: colorful sum}`` — a float per id, or a ``(T,)``
        float array per id for a fused engine (template order =
        ``self.templates``). The colorings are derived from
        ``fold_in(seed, iteration)`` *inside* the jit (no host-side
        generation or transfer) and the full execution plan runs once per
        ``batch_size`` chunk. Per-iteration values are bitwise independent
        of the batch composition, which keeps the fault-tolerant runner's
        resume-equals-straight invariant intact.
        """
        self._ensure()
        its = [int(i) for i in iterations]
        if not its:
            return {}
        bs = self._dispatch_width(len(its), batch_size)
        seeded_fn = self._seeded()
        ops = self._operands()
        out: dict = {}
        for base in range(0, len(its), bs):
            chunk = its[base: base + bs]
            padded = chunk + [chunk[-1]] * (bs - len(chunk))
            first = self.n_batch_dispatches == 0
            with _tracing.span("engine.dispatch", engine=self.engine,
                               batch=bs, first=first):
                # np.asarray already blocks on the device result, so this
                # span measures real device time without an extra sync
                totals = np.asarray(seeded_fn(
                    ops, jnp.int32(seed), jnp.asarray(padded, jnp.int32)))
            self.n_batch_dispatches += 1
            self.n_colorings_dispatched += bs
            self.n_spmm_cols_dispatched += self.spmm_cols_per_coloring * bs
            self._m_dispatches.inc()
            self._m_colorings.inc(bs)
            self._m_spmm_cols.inc(self.spmm_cols_per_coloring * bs)
            for i, it in enumerate(chunk):
                out[it] = totals[i].copy() if self.fused else float(totals[i])
        self._publish_memory_gauges(bs)
        return out

    def _dispatch_width(self, n_ids: int, batch_size: int | None) -> int:
        """Colorings per seeded dispatch for a call of ``n_ids`` ids —
        clamped to the call, the same tradeoff as count_colorful_batch."""
        return min(batch_size or self.batch_size or n_ids, n_ids)

    def _seeded(self) -> Callable:
        """jit(operands, seed, iteration ids) -> batch totals, built on
        first use."""
        if self._seeded_fn is None:
            n, k = self.g.n, self.k

            def seeded(ops, seed_, ids):
                from repro.graph.coloring import batch_colorings
                colorings = batch_colorings(seed_, ids, n, k)
                totals, _ = self._build_batch()(ops, colorings)
                return totals

            self._seeded_fn = jax.jit(seeded)
        return self._seeded_fn

    def warm(self, n_ids: int, batch_size: int | None = None) -> None:
        """Compile, without running, the seeded dispatch program that a
        :meth:`count_iterations_batch` call of ``n_ids`` ids will launch.

        The service calls this when it builds a dispatch group, so the
        compile is build time and the dispatch watchdog times execution
        only. A width compiled once is not compiled again.
        """
        self._ensure()
        bs = self._dispatch_width(int(n_ids), batch_size)
        if bs < 1 or bs in self._warm_widths:
            return
        with _tracing.span("engine.warm", engine=self.engine,
                           batch=bs) as sp:
            compiled = self._seeded().lower(
                self._operands(), jax.ShapeDtypeStruct((), jnp.int32),
                jax.ShapeDtypeStruct((bs,), jnp.int32)).compile()
            self._record_compiled(bs, compiled)
            sp.set(**self.compiled_report)
        self._warm_widths.add(bs)

    def execution_decision(self) -> dict:
        """What the memory model decided for this engine: colorings a
        dispatch, how many plan nodes run colorset-chunked, and the
        modeled peak live table bytes of one dispatch. Published as the
        ``engine_<key>`` gauges when the engine is built."""
        return {"batch_size": self.batch_size,
                "chunked_nodes": len(self.schedule.chunks),
                "modeled_peak_bytes": self.peak_table_bytes}

    def execution_report(self) -> dict:
        """:meth:`execution_decision` with the template's name and, once
        :meth:`warm` has compiled a dispatch width, what the last program
        it compiled holds: ``width`` (colorings), ``compiled_temp_bytes``
        (XLA's temporaries; absent where the backend gives no memory
        analysis) and ``spmm_steps`` (:meth:`segment_spmm_steps`)."""
        return {"template": self._mem_labels["template"],
                **self.execution_decision(), **self.compiled_report}

    def _record_compiled(self, width: int, compiled) -> None:
        """Publish what the program compiled for ``width`` colorings holds
        as :attr:`compiled_report` and the ``engine_compiled_temp_bytes``
        and per-node ``engine_spmm_steps`` gauges. Host-side only: the
        compiled program is not touched."""
        steps = self.segment_spmm_steps(width)
        rep: dict = {"width": width}
        try:
            rep["compiled_temp_bytes"] = int(
                compiled.memory_analysis().temp_size_in_bytes)
        except (AttributeError, NotImplementedError, RuntimeError):
            pass                      # no analysis on this backend
        else:
            _metrics.gauge("engine_compiled_temp_bytes", **self._mem_labels
                           ).set(rep["compiled_temp_bytes"])
        rep["spmm_steps"] = steps
        for idx, n in steps.items():
            _metrics.gauge("engine_spmm_steps", node=idx,
                           **self._mem_labels).set(n)
        self.compiled_report = rep

    def segment_spmm_steps(self, width: int) -> dict[int, int]:
        """``{plan node: steps}`` of the segment SpMM in one dispatch of
        ``width`` colorings, keyed by the node whose ``plan.node<i>`` scope
        runs the SpMM: the first consumer of each y-cached passive table
        (one SpMM of ``width x C(k, t_p)`` rows), and every colorset-chunked
        node (one SpMM per chunk). Fused nodes, shared-passive group
        members among them, run no segment SpMM, nor does any engine but
        ``pgbsc`` on the ``segment`` method (empty dict)."""
        if self.engine != "pgbsc" or self.spmm_method != "segment":
            return {}
        sched = self.schedule
        chunks = sched.chunk_map
        out: dict[int, int] = {}
        seen: set[int] = set()
        for idx in sched.order:
            node = self.plan.nodes[idx]
            if node.is_leaf:
                continue
            q = chunks.get(idx, 1)
            rows = comb(self.k, self.plan.nodes[node.passive].size)
            if q > 1:
                out[idx] = q * spmm_ops.segment_steps(
                    width * -(-rows // q), self.g.m, self.dtype)
            elif idx not in sched.fused_set and node.passive not in seen:
                seen.add(node.passive)
                out[idx] = spmm_ops.segment_steps(width * rows, self.g.m,
                                                  self.dtype)
        return out

    def estimate(self, n_iters: int, seed: int = 0,
                 start_iteration: int = 0,
                 batch_size: int | None = None) -> dict:
        """Color-coding estimate averaged over ``n_iters`` colorings.

        Iterations run through the batched pipeline (``batch_size`` per
        device call); samples are identical to the sequential per-coloring
        loop because the colorings derive from the same fold_in keys.
        """
        if self.fused:
            raise ValueError("estimate() is single-template; fused engines "
                             "use estimate_many()")
        return self.estimate_many(n_iters, seed=seed,
                                  start_iteration=start_iteration,
                                  batch_size=batch_size)[0]

    def estimate_many(self, n_iters: int, seed: int = 0,
                      start_iteration: int = 0,
                      batch_size: int | None = None) -> list[dict]:
        """Per-template color-coding estimates from ONE fused plan run.

        Returns one :meth:`estimate`-shaped dict per template (in
        ``self.templates`` order); every template's samples come from the
        same colorings, so a template also counted solo with the same seed
        reproduces its samples to floating-point reassociation.
        """
        p = cs.colorful_probability(self.k)
        ids = range(start_iteration, start_iteration + n_iters)
        per = self.count_iterations_batch(ids, seed=seed,
                                          batch_size=batch_size)
        vals = np.stack([np.atleast_1d(np.asarray(per[it])) for it in ids])
        results = []
        for j, t in enumerate(self.templates):
            alpha = t.automorphisms
            samples = [float(v) / (alpha * p) for v in vals[:, j]]
            arr = np.asarray(samples)
            results.append({
                "count": float(arr.mean()),
                "std": float(arr.std(ddof=1)) if len(arr) > 1 else 0.0,
                "samples": samples,
                "n_iters": n_iters,
                "alpha": alpha,
                "colorful_probability": p,
            })
        return results

    # ------------------------------------------------------------- builders
    def _wrap_reorder(self, fn: Callable) -> Callable:
        """Boundary permutation around a built count program: colorings are
        permuted INTO the engine's reordered vertex space on the way in and
        the root tables are inverse-permuted back to the caller's original
        vertex ids on the way out. Totals are sums over the whole table, so
        they need nothing (permutation-invariant up to float reassociation).
        """
        if self._order is None:
            return fn
        # pgbsc tables are combination-major (..., C, N); fascia/pfascia are
        # row-major (..., N, C) — the vertex axis moves accordingly
        vaxis = -1 if self.engine == "pgbsc" else -2
        is_fused = self.fused

        def wrapped(ops: dict, colors: jax.Array):
            inv_dev = ops["inv"]
            totals, roots = fn(ops, jnp.take(colors, ops["order"], axis=-1))
            if is_fused:
                roots = tuple(jnp.take(r, inv_dev, axis=vaxis)
                              for r in roots)
            else:
                roots = jnp.take(roots, inv_dev, axis=vaxis)
            return totals, roots

        return wrapped

    def _build(self) -> Callable:
        if self.engine == "pgbsc":
            return self._wrap_reorder(self._build_pgbsc())
        return self._wrap_reorder(
            self._build_rowmajor(pruned=self.engine == "pfascia"))

    def _build_batch(self) -> Callable:
        """(B, n) colorings -> (totals (B,), root tables (B, ...)).

        ``pgbsc`` executes the plan directly on (B, C, N) tables (the
        kernels are batch-aware); the row-major engines vmap the
        single-coloring program over the batch dimension.
        """
        if self.engine == "pgbsc":
            return self._wrap_reorder(self._build_pgbsc())
        return self._wrap_reorder(
            jax.vmap(self._build_rowmajor(pruned=self.engine == "pfascia"),
                     in_axes=(None, 0)))

    def _leaf_table_cn(self, colors: jax.Array) -> jnp.ndarray:
        """(..., k, N) one-hot of vertex colors — combination-major leaves.

        A leading batch dimension on ``colors`` broadcasts straight through.
        """
        with _tracing.device_scope(_tracing.KERNEL_LEAF):
            return (jnp.arange(self.k, dtype=colors.dtype)[:, None]
                    == colors[..., None, :]).astype(self.dtype)

    def _build_pgbsc(self) -> Callable:
        splits, packs = self._splits, self._chunk_packs
        runner = pexec.PlanExecutor(self.plan, self.schedule)
        autotune = self.autotune_blocks

        # the callbacks taking ``ops`` get the traced dispatch's graph
        # operands (:meth:`_operands`) bound by ``run``
        def passive_op(ops, p_idx, m_p):
            # SpMM over *all* passive color sets at once (Algorithm 4 l.3);
            # with plan dedup, shared passive children reuse the result.
            return spmm_ops.spmm(m_p, ops["spmm"], autotune=autotune)

        def combine(idx, m_a, y_p):
            ia, ip = splits[idx]
            return ema_ops.ema(
                m_a, y_p, ia, ip,
                use_pallas=self.use_pallas_ema, interpret=self.interpret,
                autotune=autotune)

        def combine_direct(ops, idx, m_a, m_p):
            # direct (no materialized y_p) nodes; chunking wins over fusion
            # when the memory model assigned both (Schedule.fused_set doc)
            if idx in packs:
                # colorset-chunked node: the passive SpMM output is produced
                # and consumed one C(k, t_p)-axis slice at a time
                return ema_ops.ema_chunked(
                    m_a, m_p, packs[idx],
                    lambda m: spmm_ops.spmm(m, ops["spmm"],
                                            autotune=autotune))
            # fused node: SpMM and eMA in one Pallas launch — the
            # (B, C(k,t_p), N) neighbor-sum table never leaves VMEM
            ia, ip = splits[idx]
            return fused_ops.fused_spmm_ema(m_a, m_p, ia, ip, ops["fused"])

        def combine_group(ops, members, m_as, m_p):
            # shared-passive group: ONE launch computes the passive child's
            # neighbor sums once in VMEM scratch and applies every member's
            # split combination against it
            ias = tuple(splits[m][0] for m in members)
            ips = tuple(splits[m][1] for m in members)
            return fused_ops.fused_spmm_ema_shared(m_as, m_p, ias, ips,
                                                   ops["fused"])

        # sub-f32 storage sums its root tables in the accumulator dtype
        # (f32 for bf16) — the final reduction must not halve its mantissa
        acc_dt = ema_ops.accum_dtype(self.dtype)

        def run(ops: dict, colors: jax.Array):
            # colors: (N,) or batched (B, N) — every step below is
            # polymorphic over the leading batch dimension.
            leaf = self._leaf_table_cn(colors)
            outs = runner.run(leaf, passive_op=partial(passive_op, ops),
                              combine=combine,
                              combine_direct=partial(combine_direct, ops),
                              combine_group=partial(combine_group, ops),
                              on_step=self._peak_probe,
                              outputs=self.roots)
            with _tracing.device_scope(_tracing.KERNEL_ROOT):
                if not self.fused:
                    root = outs[0]
                    return root.astype(acc_dt).sum(axis=(-2, -1)), root
                # one fused walk, one (..., T) totals vector — template
                # j's entry comes from its own root table
                totals = jnp.stack(
                    [r.astype(acc_dt).sum(axis=(-2, -1)) for r in outs],
                    axis=-1)
                return totals, outs

        return run

    def _build_rowmajor(self, pruned: bool) -> Callable:
        """FASCIA / PFASCIA: row-major (N, C) tables + ELL traversal."""
        splits = self._splits
        runner = pexec.PlanExecutor(self.plan, self.schedule)

        acc_dt = ema_ops.accum_dtype(self.dtype)

        # the functions taking ``ops`` get the traced dispatch's ELL
        # operands (:meth:`_operands`) bound by ``run``
        def nbr_sum(ops, m_cols: jnp.ndarray) -> jnp.ndarray:
            # m_cols: (N, R) -> out[i, r] = sum_d m_cols[nbr[i, d], r] * mask
            # Accumulate in acc_dt (f32 for bf16 tables) and downcast once at
            # the end — the scan carry must keep one dtype throughout.
            def body(acc, nd):
                col_ids, msk = nd
                gathered = m_cols[col_ids, :].astype(acc_dt)
                return acc + gathered * msk.astype(acc_dt)[:, None], None

            with _tracing.device_scope(_tracing.KERNEL_SPMM):
                acc0 = jnp.zeros(m_cols.shape, acc_dt)
                acc, _ = jax.lax.scan(body, acc0,
                                      (ops["nbr"].T, ops["mask"].T))
                return acc.astype(m_cols.dtype)

        def passive_op(ops, p_idx, m_p):
            # PFASCIA: one neighbor sweep per distinct passive set.
            return nbr_sum(ops, m_p)

        def combine(idx, m_a, y_p):
            ia, ip = splits[idx]

            def body(acc, idx_l):
                ia_l, ip_l = idx_l
                prod = (m_a[:, ia_l].astype(acc_dt)
                        * y_p[:, ip_l].astype(acc_dt))
                return acc + prod, None

            with _tracing.device_scope(_tracing.KERNEL_EMA):
                acc0 = jnp.zeros((m_a.shape[0], ia.shape[0]), acc_dt)
                acc, _ = jax.lax.scan(body, acc0, (ia.T, ip.T))
                return acc.astype(self.dtype)

        def combine_direct(ops, idx, m_a, m_p):
            # FASCIA: the neighbor sweep is *inside* the split loop —
            # the redundancy of paper §3.1, preserved deliberately.
            ia, ip = splits[idx]

            def body(acc, idx_l):
                y_l = nbr_sum(ops, m_p[:, idx_l[1]])   # (N, S) per split
                prod = m_a[:, idx_l[0]].astype(acc_dt) * y_l.astype(acc_dt)
                return acc + prod, None

            # the sweep's own ops nest a kernel.spmm scope inside this one
            with _tracing.device_scope(_tracing.KERNEL_EMA):
                acc0 = jnp.zeros((m_a.shape[0], ia.shape[0]), acc_dt)
                acc, _ = jax.lax.scan(body, acc0, (ia.T, ip.T))
                return acc.astype(self.dtype)

        def run(ops: dict, colors: jax.Array):
            leaf = self._leaf_table_cn(colors).T  # (N, k)
            outs = runner.run(
                leaf,
                passive_op=partial(passive_op, ops) if pruned else None,
                combine=combine, combine_direct=partial(combine_direct, ops),
                on_step=self._peak_probe,
                outputs=self.roots)
            with _tracing.device_scope(_tracing.KERNEL_ROOT):
                if not self.fused:
                    root = outs[0]
                    return root.astype(acc_dt).sum(), root
                totals = jnp.stack([r.astype(acc_dt).sum() for r in outs])
                return totals, outs

        return run

    # ------------------------------------------------------------- analysis
    @property
    def flops_per_iteration(self) -> int:
        return self.work.total_flops

    @property
    def peak_table_bytes(self) -> int:
        """Modeled peak live table bytes of one batched dispatch."""
        return self.exec_choice.peak_bytes_per_coloring * self.batch_size

    def _spmm_cols_per_coloring(self) -> int:
        """Static SpMM (passive-transform) column count of one coloring.

        ``pgbsc``/``pfascia`` pay ``C(k, t_p)`` columns once per *distinct*
        passive child (the executor's y-cache), which is where fused plans
        win: a passive sub-template shared across templates is one SpMM for
        the whole bundle. A shared-passive fused GROUP keeps that once-per-
        child cost — its single launch runs the SpMM leg once for every
        member. Singleton-fused and colorset-chunked nodes bypass the cache
        and pay per consumer; ``fascia`` recomputes the sweep inside the
        split loop (``C(k, t)`` columns per split, paper §3.1).
        """
        cols = 0
        seen: set[int] = set()
        counted_groups: set[tuple[int, ...]] = set()
        chunk_map = self.schedule.chunk_map
        fused_set = self.schedule.fused_set
        group_of = self.schedule.group_of
        for idx, node in enumerate(self.plan.nodes):
            if node.is_leaf:
                continue
            t = node.size
            t_a = self.plan.nodes[node.active].size
            if self.engine == "fascia":
                cols += comb(self.k, t) * comb(t, t_a)
            elif idx in group_of and chunk_map.get(idx, 1) <= 1:
                grp = group_of[idx]
                if grp not in counted_groups:
                    counted_groups.add(grp)
                    cols += comb(self.k, t - t_a)
            elif chunk_map.get(idx, 1) > 1 or idx in fused_set:
                cols += comb(self.k, t - t_a)
            elif node.passive not in seen:
                seen.add(node.passive)
                cols += comb(self.k, t - t_a)
        return cols

    def _estimate_work(self) -> WorkEstimate:
        w = WorkEstimate(batch=max(1, self.batch_size))
        n, e, k = self.g.n, self.g.m, self.k
        itemsize = jnp.dtype(self.dtype).itemsize
        for idx, node in enumerate(self.plan.nodes):
            if node.is_leaf:
                continue
            t = node.size
            t_a = self.plan.nodes[node.active].size
            t_p = t - t_a
            n_sets, n_splits = comb(k, t), comb(t, t_a)
            if self.engine == "fascia":
                w.spmm_flops += e * n_sets * n_splits
            else:
                w.spmm_flops += e * comb(k, t_p)
            w.ema_flops += 2 * n * n_sets * n_splits
            w.table_bytes += itemsize * n * n_sets
        return w


def build_engine(g: Graph, template, engine: str = "pgbsc",
                 **kw) -> CountingEngine:
    """Convenience constructor (see CountingEngine). ``template`` accepts a
    TreeTemplate / TemplateSpec / registry name, or a list of them (equal k)
    for a fused multi-template engine."""
    return CountingEngine(g, template, engine=engine, **kw)
