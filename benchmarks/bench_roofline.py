"""Paper Fig. 11 + kernel-level roofline closure (BENCH_roofline.json).

Two sections:

1. Engine placement (paper Fig. 11): achieved GFLOP/s and operational
   intensity per engine; the paper's claim is that PGBSC moves from the
   latency region toward the bandwidth roof.
2. Kernel closure: for every fused-eligible plan-node shape of a template,
   time the unfused Pallas pair (BSR SpMM kernel, then eMA kernel through a
   materialized neighbor-sum table) against the fused SpMM->eMA kernel, and
   place both on the host roofline via the ``analysis.roofline`` traffic
   models. The fused kernel moves strictly fewer modeled HBM bytes (the
   ``(B, C(k,t_p), N)`` y table never leaves VMEM), so achieved bandwidth —
   modeled bytes / measured seconds — rises iff the saved traffic shows up
   as saved wall time. The same budget/batch admission win is recorded from
   the executor's memory model.

Host peaks are measured crudely with a matmul (compute) and a triad
(bandwidth) microbenchmark; kernel wall times on CPU run the kernels in
interpret mode, so absolute numbers are emulation-scale — the fused-vs-
unfused *ratios* are the portable signal.

Three locality/precision sections ride along (PR 8):

- ``reorder``: occupied BSR blocks, block density, and fused-kernel grid
  steps before vs after RCM / degree reordering of the bench graph.
- ``dtype``: the fused kernel timed with float32 vs bfloat16 storage
  (f32 accumulation), per-variant achieved bandwidth on each variant's
  own modeled bytes, and the workload-bandwidth gain — the same logical
  table traffic delivered per second — plus engine-level count error.
- ``shared_passive``: the shared-passive group launch (one SpMM leg for
  N consumers) against per-consumer fused launches on a two-template
  bundle whose plan shares a path2 passive, with the SpMM column-op
  model for both.

    PYTHONPATH=src python -m benchmarks.bench_roofline [--smoke] [--out F]

writes BENCH_roofline.json (repo root by default).
"""

from __future__ import annotations

import argparse
import json
import pathlib
from math import comb

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, timeit
from repro.analysis.roofline import (KernelRoofline, spmm_ema_flops,
                                     spmm_ema_hbm_bytes)
from repro.core import build_engine, colorsets as cs, get_template
from repro.core.templates import TreeTemplate
from repro.graph import rmat
from repro.graph.coloring import coloring_numpy
from repro.graph.reorder import ORDERINGS, apply_order
from repro.kernels.ema import ops as ema_ops
from repro.kernels.fused import ops as fused_ops
from repro.kernels.fused.pallas_fused import pick_group_batch_block
from repro.kernels.spmm import ops as spmm_ops

DEFAULT_OUT = pathlib.Path(__file__).resolve().parents[1] / \
    "BENCH_roofline.json"


def _host_peaks() -> tuple[float, float]:
    a = jnp.asarray(np.random.default_rng(0).random((1024, 1024), np.float32))
    mm = jax.jit(lambda x: x @ x)
    sec = timeit(lambda: mm(a))
    flops = 2 * 1024 ** 3 / sec
    v = jnp.asarray(np.random.default_rng(1).random(1 << 24, np.float32))
    triad = jax.jit(lambda x: x * 2.0 + 1.0)
    sec_b = timeit(lambda: triad(v))
    bw = 3 * v.nbytes / sec_b
    return flops, bw


def _engine_section(g, peaks) -> dict:
    peak_flops, peak_bw = peaks
    t = get_template("u7")
    colors = coloring_numpy(0, 0, g.n, t.k)
    out = {}
    for eng_name in ("fascia", "pfascia", "pgbsc"):
        e = build_engine(g, t, eng_name)
        sec = timeit(lambda: e.count_colorful(colors)[0])
        flops = e.work.total_flops
        bytes_req = e.work.table_bytes * 3  # read a+p, write out (approx)
        gflops = flops / sec / 1e9
        oi = flops / bytes_req
        frac_roof = min(gflops * 1e9 / min(peak_flops, oi * peak_bw), 9.99)
        emit(f"fig11/{eng_name}", sec * 1e6,
             f"{gflops:.2f}GFLOPs|OI={oi:.2f}|roof={frac_roof * 100:.0f}%")
        out[eng_name] = {"gflops": gflops, "oi": oi, "roof_frac": frac_roof}
    return out


def _node_shapes(engine) -> list[tuple[int, int]]:
    """Distinct (t, t_a) of the engine's fused-eligible plan nodes."""
    shapes = []
    for idx in engine.schedule.fused:
        node = engine.plan.nodes[idx]
        key = (node.size, engine.plan.nodes[node.active].size)
        if key not in shapes:
            shapes.append(key)
    return shapes


def _kernel_section(g, tmpl_name: str, peaks, *, batch: int,
                    reps: int) -> dict:
    """Fused vs unfused Pallas timing for every fused-eligible node shape."""
    peak_flops, peak_bw = peaks
    engine = build_engine(g, tmpl_name, "pgbsc", fuse_spmm_ema=True)
    k = engine.k
    fprep = fused_ops.prepare_fused(g)
    bsr_prep = spmm_ops.prepare(g, "pallas_bsr")
    adj_bytes = int(np.asarray(fprep.arrays["blocks"]).nbytes)
    rng = np.random.default_rng(0)
    itemsize = jnp.dtype(jnp.float32).itemsize
    kernels = []
    for t, t_a in _node_shapes(engine):
        c_a, c_p, s = comb(k, t_a), comb(k, t - t_a), comb(k, t)
        ia, ip = cs.split_tables(k, t, t_a)
        ia, ip = jnp.asarray(ia), jnp.asarray(ip)
        length = ia.shape[1]
        m_a = jnp.asarray(rng.random((batch, c_a, g.n), np.float32))
        m_p = jnp.asarray(rng.random((batch, c_p, g.n), np.float32))

        fused = jax.jit(
            lambda a, p: fused_ops.fused_spmm_ema(a, p, ia, ip, fprep))
        unfused = jax.jit(lambda a, p: ema_ops.ema(
            a, spmm_ops.spmm(p, bsr_prep), ia, ip,
            use_pallas=True))

        sec_f = timeit(fused, m_a, m_p, iters=reps)
        sec_u = timeit(unfused, m_a, m_p, iters=reps)
        flops = spmm_ema_flops(batch, g.m, g.n, c_p, s, length)
        s_pad = -(-s // 8) * 8
        bb = pick_group_batch_block(batch, (c_a,), c_p, (s_pad,),
                                    (length,), 128, jnp.float32)
        pair = {}
        for variant, sec in (("fused", sec_f), ("unfused", sec_u)):
            hbm = spmm_ema_hbm_bytes(
                batch, g.n, c_a, c_p, s, adj_bytes, itemsize,
                fused=(variant == "fused"),
                adj_passes=(-(-batch // bb) if variant == "fused" else 1))
            r = KernelRoofline(
                name=f"{tmpl_name}/t{t}a{t_a}/{variant}", flops=flops,
                hbm_bytes=hbm, seconds=sec,
                peak_flops=peak_flops, peak_bw=peak_bw)
            pair[variant] = r.as_dict()
            emit(f"roofline/{r.name}", sec * 1e6,
                 f"{r.achieved_bw / 1e9:.2f}GB/s|OI={r.oi:.2f}"
                 f"|{r.bound}")
        pair["node"] = {"t": t, "t_a": t_a, "c_a": c_a, "c_p": c_p,
                        "s": s, "l": length, "batch": batch}
        pair["speedup"] = pair["unfused"]["seconds"] / \
            pair["fused"]["seconds"]
        pair["bw_gain"] = pair["fused"]["achieved_gbps"] / \
            pair["unfused"]["achieved_gbps"]
        kernels.append(pair)
    return {"kernels": kernels,
            "fused_nodes": list(engine.schedule.fused)}


def _admission_section(g, tmpl_name: str,
                       budget: int | None = None) -> dict:
    """Same memory budget, unfused vs fused: batch the model admits.

    The budget defaults to 32x the unfused per-coloring peak, which keeps
    the comparison below the batch-size cap where admission is actually
    budget-limited.
    """
    if budget is None:
        probe = build_engine(g, tmpl_name, "pgbsc")
        budget = 32 * probe.exec_choice.peak_bytes_per_coloring
    e0 = build_engine(g, tmpl_name, "pgbsc", memory_budget_bytes=budget)
    e1 = build_engine(g, tmpl_name, "pgbsc", memory_budget_bytes=budget,
                      fuse_spmm_ema=True)
    emit(f"roofline/{tmpl_name}/admitted_batch", 0.0,
         f"unfused={e0.batch_size}|fused={e1.batch_size}")
    return {"budget_bytes": budget,
            "unfused_batch": e0.batch_size, "fused_batch": e1.batch_size,
            "unfused_peak_per_coloring": e0.exec_choice.
            peak_bytes_per_coloring,
            "fused_peak_per_coloring": e1.exec_choice.
            peak_bytes_per_coloring}


def _reorder_section(g) -> dict:
    """Occupied BSR blocks / density / fused grid steps, before vs after."""
    before = g.bsr_block_stats()
    grid_before = int(np.asarray(
        fused_ops.prepare_fused(g).arrays["src_tile"]).size)
    out = {"before": {**before, "fused_grid_steps": grid_before}}
    for name, fn in sorted(ORDERINGS.items()):
        gp = apply_order(g, fn(g))
        after = gp.bsr_block_stats()
        grid = int(np.asarray(
            fused_ops.prepare_fused(gp)
            .arrays["src_tile"]).size)
        out[name] = {**after, "fused_grid_steps": grid}
        emit(f"reorder/{name}/occupied_blocks", 0.0,
             f"{before['occupied_blocks']}->{after['occupied_blocks']}"
             f"|grid={grid_before}->{grid}")
    return out


def _dtype_section(g, peaks, *, batch: int, reps: int) -> dict:
    """Fused kernel, float32 vs bfloat16 storage (f32 accumulation).

    Per-variant achieved bandwidth divides each variant's OWN modeled
    bytes (bf16 streams half the physical table/adjacency bytes) by its
    measured seconds. ``workload_bw_gain`` is the portable headline: both
    variants deliver the identical logical table traffic, so the gain in
    logical bytes per second equals the measured speedup.
    """
    peak_flops, peak_bw = peaks
    k, t, t_a = 5, 3, 1
    c_a, c_p, s = comb(k, t_a), comb(k, t - t_a), comb(k, t)
    ia, ip = cs.split_tables(k, t, t_a)
    ia, ip = jnp.asarray(ia), jnp.asarray(ip)
    length = ia.shape[1]
    rng = np.random.default_rng(2)
    m_a32 = jnp.asarray(rng.random((batch, c_a, g.n), np.float32))
    m_p32 = jnp.asarray(rng.random((batch, c_p, g.n), np.float32))
    flops = spmm_ema_flops(batch, g.m, g.n, c_p, s, length)
    s_pad = -(-s // 8) * 8
    out = {}
    for dt in (jnp.float32, jnp.bfloat16):
        dname = np.dtype(dt).name
        prep = fused_ops.prepare_fused(g, dtype=dt)
        m_a, m_p = m_a32.astype(dt), m_p32.astype(dt)
        fn = jax.jit(
            lambda a, p, prep=prep: fused_ops.fused_spmm_ema(
                a, p, ia, ip, prep))
        sec = timeit(fn, m_a, m_p, iters=reps)
        item = jnp.dtype(dt).itemsize
        adj_bytes = int(np.asarray(prep.arrays["blocks"]).nbytes)
        bb = pick_group_batch_block(batch, (c_a,), c_p, (s_pad,), (length,),
                                    128, dt)
        hbm = spmm_ema_hbm_bytes(batch, g.n, c_a, c_p, s, adj_bytes, item,
                                 fused=True, adj_passes=-(-batch // bb))
        r = KernelRoofline(name=f"fused/{dname}", flops=flops,
                           hbm_bytes=hbm, seconds=sec,
                           peak_flops=peak_flops, peak_bw=peak_bw)
        out[dname] = r.as_dict()
        emit(f"roofline/{r.name}", sec * 1e6,
             f"{r.achieved_bw / 1e9:.2f}GB/s|OI={r.oi:.2f}|{r.bound}")
    out["speedup"] = out["float32"]["seconds"] / out["bfloat16"]["seconds"]
    out["workload_bw_gain"] = out["speedup"]
    # engine-level count accuracy: bf16 storage vs the f32 reference
    e32 = build_engine(g, "u5", "pgbsc")
    e16 = build_engine(g, "u5", "pgbsc", dtype=jnp.bfloat16,
                       fuse_spmm_ema=True)
    colors = coloring_numpy(0, 0, g.n, 5)
    want = float(e32.count_colorful(colors)[0])
    got = float(e16.count_colorful(colors)[0])
    out["count_rel_err"] = abs(got - want) / max(abs(want), 1.0)
    emit("roofline/fused/bf16_gain", 0.0,
         f"x{out['workload_bw_gain']:.2f}|relerr="
         f"{out['count_rel_err']:.1e}")
    return out


def _shared_bundle() -> tuple:
    """Two k=5 trees whose dedup plan shares a path2 passive between T1's
    root and an interior node of T2 (see tests/test_kernels_fused.py)."""
    return (TreeTemplate([(0, 1), (1, 2), (0, 3), (0, 4)], root=0,
                         name="sharedp_a"),
            TreeTemplate([(0, 1), (1, 2), (2, 3), (1, 4)], root=0,
                         name="sharedp_b"))


def _shared_section(g, *, batch: int, reps: int) -> dict:
    """Shared-passive group launch vs per-consumer fused launches."""
    shared = build_engine(g, _shared_bundle(), "pgbsc", plan="dedup",
                          fuse_spmm_ema=True)
    assert shared.schedule.fused_groups, "bundle must form a group"
    grp = shared.schedule.fused_groups[0]
    k = shared.k
    c_p = comb(k, shared.plan.nodes[shared.plan.nodes[grp[0]].passive].size)
    cols_grouped = shared.spmm_cols_per_coloring
    cols_per_consumer = cols_grouped + (len(grp) - 1) * c_p
    rng = np.random.default_rng(3)
    fprep = fused_ops.prepare_fused(g)
    m_p = jnp.asarray(rng.random((batch, c_p, g.n), np.float32))
    m_as, ias, ips = [], [], []
    for m in grp:
        node = shared.plan.nodes[m]
        t, t_a = node.size, shared.plan.nodes[node.active].size
        ia, ip = cs.split_tables(k, t, t_a)
        ias.append(jnp.asarray(ia))
        ips.append(jnp.asarray(ip))
        m_as.append(jnp.asarray(
            rng.random((batch, comb(k, t_a), g.n), np.float32)))

    def one_launch(mas, mp):
        return fused_ops.fused_spmm_ema_shared(mas, mp, ias, ips, fprep)

    def per_consumer(mas, mp):
        return tuple(fused_ops.fused_spmm_ema(ma, mp, ia, ip, fprep)
                     for ma, ia, ip in zip(mas, ias, ips))

    sec_s = timeit(jax.jit(one_launch), m_as, m_p, iters=reps)
    sec_p = timeit(jax.jit(per_consumer), m_as, m_p, iters=reps)
    emit("roofline/shared_passive", sec_s * 1e6,
         f"cols={cols_grouped}(vs {cols_per_consumer})"
         f"|x{sec_p / sec_s:.2f}")
    return {"group": list(grp), "consumers": len(grp),
            "spmm_cols_grouped": cols_grouped,
            "spmm_cols_per_consumer_fusion": cols_per_consumer,
            "shared_seconds": sec_s, "per_consumer_seconds": sec_p,
            "speedup": sec_p / sec_s}


def run(smoke: bool = False, out_path: pathlib.Path | None = None) -> dict:
    peak_flops, peak_bw = peaks = _host_peaks()
    emit("fig11/host_peak", 0.0,
         f"{peak_flops / 1e9:.1f}GFLOPs|{peak_bw / 1e9:.1f}GB/s")
    if smoke:
        g = rmat(9, 8, seed=0)
        templates, batch, reps = ("u5",), 4, 2
    else:
        g = rmat(11, 16, seed=0)
        templates, batch, reps = ("u5", "u7"), 8, 3
    result = {
        "smoke": smoke,
        "host": {"peak_gflops": peak_flops / 1e9,
                 "peak_gbps": peak_bw / 1e9,
                 "note": "kernels run in Pallas interpret mode on CPU; "
                         "ratios, not absolutes, are the portable signal"},
        "graph": {"n": g.n, "m": g.m},
        "engines": {} if smoke else _engine_section(g, peaks),
        "templates": {},
    }
    for name in templates:
        result["templates"][name] = _kernel_section(
            g, name, peaks, batch=batch, reps=reps)
        result["templates"][name]["admission"] = _admission_section(g, name)
    result["reorder"] = _reorder_section(g)
    result["dtype"] = _dtype_section(g, peaks, batch=batch, reps=reps)
    result["shared_passive"] = _shared_section(g, batch=batch, reps=reps)
    out_path = pathlib.Path(out_path) if out_path else DEFAULT_OUT
    out_path.write_text(json.dumps(result, indent=2) + "\n")
    emit("roofline/json", 0.0, str(out_path))
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small graph, one template, fewer reps (CI)")
    ap.add_argument("--out", default=None, help="JSON output path")
    args = ap.parse_args(argv)
    run(smoke=args.smoke, out_path=args.out)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
