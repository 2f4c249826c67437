"""Distributed PGBSC over a (pod, data, model) device mesh.

Mapping of the algorithm's parallel axes (DESIGN.md §4):

* vertices  → ``data`` axis. The distributed SpMM is a **ring schedule**:
  each data shard owns a block of destination vertices and the matching
  column block of A_G (grouped by source block); count-table blocks rotate
  around the ring via ``collective_permute`` while each device accumulates
  the contribution of the currently-resident source block — compute and
  communication overlap across ring steps. This realizes the paper's
  future-work §2 (distributed memory) with jax-native collectives.
* color combinations → ``model`` axis. SpMM is embarrassingly parallel over
  combinations (each model shard rings over its own combo rows); the eMA
  all-gathers the (small) child tables over ``model`` once per sub-template,
  then each shard produces its own slice of output color sets.
* color-coding iterations → ``pod`` axis. Each pod runs an independent
  coloring derived from ``fold_in(seed, iteration)``; pods never communicate
  until the final mean. Iterations are the unit of fault tolerance
  (core/runner.py).

Tables are (C, N) sharded P(model, data) with both dims zero-padded to the
mesh multiples; padded combo rows are masked out of the final reduction.
"""

from __future__ import annotations

import dataclasses
from math import comb

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import colorsets as cs
from repro.core import executor as pexec
from repro.core.templates import TreeTemplate, as_template
from repro.graph.structure import Graph

__all__ = ["DistributedPgbsc", "build_ring_edges", "coloring_for_seed"]


def coloring_for_seed(seed, n_pad: int, n_true: int, k: int) -> jnp.ndarray:
    """Global coloring for an iteration seed; padding vertices get an
    out-of-range color so they never contribute. Mesh-shape independent."""
    key = jax.random.fold_in(jax.random.PRNGKey(0), seed)
    colors = jax.random.randint(key, (n_pad,), 0, k, dtype=jnp.int32)
    vid = jnp.arange(n_pad)
    return jnp.where(vid < n_true, colors, k + 1)


def build_ring_edges(g: Graph, n_shards: int,
                     pad_vertices_to: int = 128) -> dict[str, np.ndarray]:
    """Per-(dst shard, src block) padded edge arrays for the ring SpMM.

    Returns arrays of shape (n_shards, n_shards, e_max):
      src_local[d, s] — src offset within block s for edges into dst shard d
      dst_local[d, s] — dst offset within shard d
      mask[d, s]      — 1.0 for real edges
    plus n_pad (the padded vertex count; n_pad % (n_shards*pad_vertices_to)==0).
    """
    block = -(-g.n // (n_shards * pad_vertices_to)) * pad_vertices_to
    n_pad = block * n_shards
    src, dst = g.edges_by_dst
    d_shard = dst // block
    s_block = src // block
    counts = np.zeros((n_shards, n_shards), np.int64)
    np.add.at(counts, (d_shard, s_block), 1)
    e_max = max(int(counts.max()), 1)

    src_local = np.zeros((n_shards, n_shards, e_max), np.int32)
    dst_local = np.zeros((n_shards, n_shards, e_max), np.int32)
    mask = np.zeros((n_shards, n_shards, e_max), np.float32)
    if len(src):
        order = np.lexsort((s_block, d_shard))
        src_s, dst_s = src[order], dst[order]
        ds, ss = d_shard[order], s_block[order]
        # vectorized position-within-group: index minus group start
        key = ds * n_shards + ss
        change = np.r_[True, key[1:] != key[:-1]]
        group_start = np.maximum.accumulate(
            np.where(change, np.arange(len(key)), 0))
        pos = np.arange(len(key)) - group_start
        src_local[ds, ss, pos] = (src_s - ss * block).astype(np.int32)
        dst_local[ds, ss, pos] = (dst_s - ds * block).astype(np.int32)
        mask[ds, ss, pos] = 1.0
    return {
        "src_local": src_local, "dst_local": dst_local, "mask": mask,
        "n_pad": n_pad, "block": block, "e_max": e_max,
    }


@dataclasses.dataclass
class _NodeMeta:
    width: int          # true combo count C(k, t)
    width_pad: int      # padded to model-axis multiple
    ia: np.ndarray | None
    ip: np.ndarray | None
    active: int | None
    passive: int | None


class DistributedPgbsc:
    """PGBSC sharded over a Mesh with ('data', 'model') [+ leading 'pod'].

    ``count_step(seeds)`` is the jit-able unit the launcher lowers: for a
    multi-pod mesh it evaluates one coloring iteration per pod and returns
    the per-pod colorful sums.
    """

    def __init__(self, g: Graph | None, template, mesh: Mesh,
                 *, plan: str = "dedup", abstract_dims: dict | None = None,
                 memory_budget_bytes: int | None = None):
        # registry names / TemplateSpec / edge lists coerce like everywhere
        # else in the query API; TreeTemplate passes through untouched
        template: TreeTemplate = as_template(template)
        self.template = template
        self.k = template.k
        self.mesh = mesh
        self.axes = mesh.axis_names
        assert self.axes[-2:] == ("data", "model"), self.axes
        self.has_pod = len(self.axes) == 3
        self.d_data = mesh.shape["data"]
        self.d_model = mesh.shape["model"]
        self.plan = {"plain": template.plan, "dedup": template.plan_dedup,
                     "optimized": template.plan_optimized}[plan]
        self.abstract = g is None
        self.memory_budget_bytes = memory_budget_bytes
        # same liveness-managed, min-peak-ordered walk as the single-device
        # engines; each freed buffer here is a model/data-sharded table
        self.exec_schedule = pexec.compute_schedule(self.plan, self.k,
                                                    passive_cache=True)

        if g is not None:
            ring = build_ring_edges(g, self.d_data)
            self.n_pad = int(ring["n_pad"])
            self.block = int(ring["block"])
            self.edge_arrays = {k: ring[k]
                                for k in ("src_local", "dst_local", "mask")}
            self.n_true = g.n
        else:
            # dry-run mode: shapes only, nothing built or allocated
            n, e = abstract_dims["n"], abstract_dims["e"]
            block = -(-n // (self.d_data * 128)) * 128
            self.n_pad = block * self.d_data
            self.block = block
            self.n_true = n
            e_max = int(abstract_dims.get(
                "e_max", 1.3 * e / (self.d_data ** 2)) + 1)
            shp = (self.d_data, self.d_data, e_max)
            self.edge_arrays = {
                "src_local": jax.ShapeDtypeStruct(shp, jnp.int32),
                "dst_local": jax.ShapeDtypeStruct(shp, jnp.int32),
                "mask": jax.ShapeDtypeStruct(shp, jnp.float32),
            }

        # per-node metadata + padded split tables
        self.meta: list[_NodeMeta] = []
        for node in self.plan.nodes:
            width = comb(self.k, node.size)
            width_pad = -(-width // self.d_model) * self.d_model
            if node.is_leaf:
                self.meta.append(_NodeMeta(width, width_pad, None, None,
                                           None, None))
            else:
                t_a = self.plan.nodes[node.active].size
                ia, ip = cs.split_tables(self.k, node.size, t_a)
                ia_pad = np.zeros((width_pad, ia.shape[1]), np.int32)
                ip_pad = np.zeros((width_pad, ip.shape[1]), np.int32)
                ia_pad[:width] = ia
                ip_pad[:width] = ip
                self.meta.append(_NodeMeta(width, width_pad, ia_pad, ip_pad,
                                           node.active, node.passive))

    # ---------------------------------------------------------------- local
    def _ring_spmm(self, m_loc: jnp.ndarray, src_l, dst_l, msk) -> jnp.ndarray:
        """m_loc: (C_loc, block) — my combo rows, my vertex block.

        src_l/dst_l/msk: (D, e_max) edge arrays for MY dst shard, indexed by
        the owning source block. Ring: at step s the resident block belongs
        to shard (my + s) % D.
        """
        d = self.d_data
        my = jax.lax.axis_index("data")
        perm = [(i, (i - 1) % d) for i in range(d)]

        # The ring is unrolled (d is static): each step overlaps the permute
        # of the resident block with the local accumulate, and every step's
        # collective/segment-sum cost is visible to HLO cost analysis.
        m_cur, acc = m_loc, jnp.zeros_like(m_loc)
        for step in range(d):
            owner = (my + step) % d
            s = jax.lax.dynamic_index_in_dim(src_l, owner, 0, keepdims=False)
            t = jax.lax.dynamic_index_in_dim(dst_l, owner, 0, keepdims=False)
            w = jax.lax.dynamic_index_in_dim(msk, owner, 0, keepdims=False)
            contrib = m_cur[:, s] * w[None, :]            # (C_loc, e_max)
            acc = acc + jax.ops.segment_sum(
                contrib.T, t, num_segments=self.block).T  # (C_loc, block)
            if step < d - 1:  # rotate; last step has nothing left to feed
                m_cur = jax.lax.ppermute(m_cur, "data", perm)
        return acc

    def _ema_local(self, m_a_full, y_p_full, ia, ip) -> jnp.ndarray:
        # unrolled over the (static, small) split count for HLO-visible cost
        acc = jnp.zeros((ia.shape[0], m_a_full.shape[1]), m_a_full.dtype)
        for l in range(ia.shape[1]):
            acc = acc + m_a_full[ia[:, l], :] * y_p_full[ip[:, l], :]
        return acc

    def _ema_scatter(self, m_a_loc, y_p_full, ia, ip, a_rows: int
                     ) -> jnp.ndarray:
        """eMA without gathering the active child: each model shard computes
        the split-terms whose m_a row it owns (masked local gather), then the
        partial outputs are summed across the model axis and my output slice
        is kept (an all-reduce+slice = reduce-scatter). Cheaper than
        gathering both children when the active table is wider than the
        output (adaptive choice in _count_one; §Perf iteration P3).

        ia/ip here are the FULL padded split tables (S_pad, L).
        """
        my_m = jax.lax.axis_index("model")
        lo = my_m * a_rows
        acc = jnp.zeros((ia.shape[0], y_p_full.shape[1]), y_p_full.dtype)
        for l in range(ia.shape[1]):
            ga = ia[:, l]
            own = (ga >= lo) & (ga < lo + a_rows)
            local_idx = jnp.clip(ga - lo, 0, a_rows - 1)
            term = m_a_loc[local_idx, :] * y_p_full[ip[:, l], :]
            acc = acc + jnp.where(own[:, None], term, 0.0)
        total = jax.lax.psum(acc, "model")          # (S_pad, block)
        s_rows = ia.shape[0] // self.d_model
        return jax.lax.dynamic_slice_in_dim(total, my_m * s_rows, s_rows, 0)

    def _count_one(self, colors_loc: jnp.ndarray, src_l, dst_l, msk,
                   split_tabs: dict) -> jnp.ndarray:
        """Inside shard_map: colors_loc (block,) for my data shard.

        The plan walk itself (order, y-cache, buffer frees) is the shared
        :class:`~repro.core.executor.PlanExecutor`; only the callbacks are
        mesh-aware. Every table is stored model-sharded (my slice of the
        padded combo rows), so each freed buffer releases its slice on all
        model shards at once.
        """
        k = self.k
        my_m = jax.lax.axis_index("model")
        leaf_full = (jnp.arange(k, dtype=jnp.int32)[:, None]
                     == colors_loc[None, :]).astype(jnp.float32)

        def my_slice(full_pad: jnp.ndarray, width_pad: int) -> jnp.ndarray:
            rows = width_pad // self.d_model
            return jax.lax.dynamic_slice_in_dim(full_pad, my_m * rows, rows, 0)

        # all leaves are size-1 sub-templates: same width_pad, same table
        leaf_meta = self.meta[next(
            i for i, nd in enumerate(self.plan.nodes) if nd.is_leaf)]
        pad = jnp.zeros((leaf_meta.width_pad - k, colors_loc.shape[0]),
                        jnp.float32)
        leaf_loc = my_slice(jnp.concatenate([leaf_full, pad], axis=0),
                            leaf_meta.width_pad)

        def passive_op(p_idx, m_p):
            return self._ring_spmm(m_p, src_l, dst_l, msk)

        def combine(idx, m_a_loc, y_p_loc):
            node = self.plan.nodes[idx]
            meta = self.meta[idx]
            ia, ip = split_tabs[idx]
            # adaptive collective choice per node (bytes moved over `model`):
            #  gather-both: move Ca_pad + Cp_pad rows;
            #  scatter-out:  move Cp_pad + S_pad rows (psum of partials).
            a_pad = self.meta[node.active].width_pad
            p_pad = self.meta[node.passive].width_pad
            gather_cost = a_pad + p_pad
            # psum costs ~2x an all-gather of the same rows (ring algebra),
            # unless XLA fuses the trailing slice into a reduce-scatter
            scatter_cost = p_pad + 2 * meta.width_pad
            y_p_full = _allgather_rows(y_p_loc, "model")
            if scatter_cost < gather_cost:
                return self._ema_scatter(m_a_loc, y_p_full, ia, ip,
                                         a_pad // self.d_model)
            m_a_full = _allgather_rows(m_a_loc, "model")
            ia_my = my_slice(ia, meta.width_pad)
            ip_my = my_slice(ip, meta.width_pad)
            return self._ema_local(m_a_full, y_p_full, ia_my, ip_my)

        runner = pexec.PlanExecutor(self.plan, self.exec_schedule)
        root = runner.run(leaf_loc, passive_op=passive_op, combine=combine)
        root_meta = self.meta[-1]
        rows = root_meta.width_pad // self.d_model
        row_ids = my_m * rows + jnp.arange(rows)
        row_mask = (row_ids < root_meta.width).astype(root.dtype)
        local = (root * row_mask[:, None]).sum()
        total = jax.lax.psum(jax.lax.psum(local, "data"), "model")
        return total

    # ------------------------------------------------------------------ api
    def count_step_fn(self):
        """Returns (step_fn, input_arrays, in_shardings) for jit/lower.

        step_fn(seeds, src_l, dst_l, msk) -> per-pod colorful sums (or scalar
        for a single-pod mesh). ``seeds`` is int32 (n_pods,) [or (1,)].
        """
        split_tabs = {
            i: (jnp.asarray(m.ia), jnp.asarray(m.ip))
            for i, m in enumerate(self.meta) if m.ia is not None
        }
        n_pods = self.mesh.shape["pod"] if self.has_pod else 1

        # edge arrays: shard dst-shard dim over data; replicated over
        # pod/model (axes unmentioned in the spec are replicated).
        edge_spec = P("data", None, None)

        def per_pod_count(seed, src_l, dst_l, msk):
            # seed: scalar int32. The coloring is derived *globally* (then
            # sliced per shard) so results are identical across mesh shapes —
            # the basis for elastic-restart determinism.
            colors_full = coloring_for_seed(seed, self.n_pad, self.n_true,
                                            self.k)
            my_d = jax.lax.axis_index("data")
            colors_loc = jax.lax.dynamic_slice_in_dim(
                colors_full, my_d * self.block, self.block)
            return self._count_one(colors_loc, src_l, dst_l, msk, split_tabs)

        def local_step(seeds, src_l, dst_l, msk):
            # inside shard_map: seeds (1,); edge arrays (1, D, e_max)
            total = per_pod_count(seeds[0], src_l[0], dst_l[0], msk[0])
            return jnp.reshape(total, (1,))

        in_specs = (
            P("pod") if self.has_pod else P(None),
            edge_spec, edge_spec, edge_spec,
        )
        out_specs = P("pod") if self.has_pod else P(None)

        step = jax.shard_map(
            local_step, mesh=self.mesh, in_specs=in_specs,
            out_specs=out_specs, check_vma=False,
        )

        if self.abstract:
            src_l = self.edge_arrays["src_local"]
            dst_l = self.edge_arrays["dst_local"]
            msk = self.edge_arrays["mask"]
            seeds = jax.ShapeDtypeStruct((n_pods,), jnp.int32)
        else:
            src_l = jnp.asarray(self.edge_arrays["src_local"])
            dst_l = jnp.asarray(self.edge_arrays["dst_local"])
            msk = jnp.asarray(self.edge_arrays["mask"])
            seeds = jnp.arange(n_pods, dtype=jnp.int32)

        shardings = tuple(NamedSharding(self.mesh, s) for s in in_specs)
        return step, (seeds, src_l, dst_l, msk), shardings

    def _multi_step(self):
        """jit of N pod-rounds scanned inside one device call.

        Built once per DistributedPgbsc (the jit re-traces per distinct
        seed_mat shape but the device-resident edge arrays and the wrapper
        are shared): fn(seed_mat (bs, n_pods), *edges) -> (bs, n_pods)
        colorful sums.
        """
        if not hasattr(self, "_multi"):
            step, (_, src_l, dst_l, msk), _ = self.count_step_fn()

            def multi(seed_mat, a, b, c):
                def body(carry, seeds_row):
                    return carry, step(seeds_row, a, b, c)

                _, outs = jax.lax.scan(body, None, seed_mat)
                return outs  # (bs, n_pods)

            self._multi = (jax.jit(multi), (src_l, dst_l, msk))
        return self._multi

    def default_pod_batch(self) -> int:
        """Budget-derived pod rounds per device call.

        Scanned rounds reuse buffers, so live memory does not grow with the
        round count — but XLA may double-buffer the scan and larger calls
        raise the blast radius of a preemption (the runner loses at most one
        call's work). With a ``memory_budget_bytes`` the rounds scale with
        the headroom over one iteration's modeled per-device peak; without
        one, the historical default of 8 is kept.
        """
        if self.memory_budget_bytes is None:
            return 8
        shards = self.d_data * self.d_model
        per_iter = pexec.simulate_peak_rows(
            self.plan, self.k, self.exec_schedule) * self.n_pad * 4 // shards
        return int(max(1, min(32, self.memory_budget_bytes
                              // max(per_iter, 1))))

    def count_iterations(self, iterations: list[int], seed: int = 0,
                         batch_size: int | None = None) -> tuple[float, dict]:
        """Sum of colorful counts over explicit iteration ids (for the
        fault-tolerant runner; single-process execution on whatever mesh).

        Per-pod work is batched: each device call evaluates up to
        ``batch_size`` coloring iterations per pod (a ``lax.scan`` over pod
        rounds inside the jit), so a checkpoint batch of
        ``batch_size * n_pods`` iterations is one dispatch. ``None`` derives
        the knob from ``memory_budget_bytes`` (:meth:`default_pod_batch`).
        Ragged tails are padded with the last iteration id and discarded;
        per-iteration values are independent of the grouping, preserving
        elastic-restart determinism across mesh shapes AND batch sizes.
        """
        if batch_size is None:
            batch_size = self.default_pod_batch()
        n_pods = self.mesh.shape["pod"] if self.has_pod else 1
        # clamped to the pod-rounds actually needed: lax.scan serializes the
        # rounds, so padding a short checkpoint batch up to the knob would
        # multiply device compute for nothing; one compiled shape per
        # distinct call length is the cheaper side of the tradeoff
        bs = max(1, min(batch_size, -(-len(iterations) // n_pods)))
        multi, (src_l, dst_l, msk) = self._multi_step()
        group = bs * n_pods
        total = 0.0
        per_iter = {}
        for base in range(0, len(iterations), group):
            batch = iterations[base: base + group]
            padded = batch + [batch[-1]] * (group - len(batch))
            seed_mat = jnp.asarray(
                [seed * 1_000_003 + it for it in padded],
                jnp.int32).reshape(bs, n_pods)
            with self.mesh:
                out = np.asarray(multi(seed_mat, src_l, dst_l, msk)
                                 ).reshape(-1)
            for i, it in enumerate(batch):
                per_iter[it] = float(out[i])
                total += float(out[i])
        return total, per_iter


def _allgather_rows(x: jnp.ndarray, axis: str) -> jnp.ndarray:
    g = jax.lax.all_gather(x, axis, axis=0)     # (D, rows, n_loc)
    return g.reshape(-1, x.shape[-1])
