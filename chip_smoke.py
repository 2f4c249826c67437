#!/usr/bin/env python3
"""Bring-up check of the subgraph-counting service on TPU chips.

    python chip_smoke.py               # one chip: kernels, then served path
    python chip_smoke.py --four-chips  # four chips: sharded counting only

One process holds the chip(s) for the whole run. It fails (non-zero exit,
no result line) when JAX's devices are not TPUs, and never falls back to
the CPU. Phases of the one-chip run:

1. **Kernels at small size.** Every Pallas kernel path of ``CountingEngine``
   (gather SpMM, BSR SpMM, eMA, fused SpMM->eMA, the fused shared-passive
   group, and bf16 fused) counts the same colorings as a reference:
   ``core/oracle.py`` exact counts on ``er(500, 8)``, the XLA segment engine
   on Graph500 RMAT scale 14. Each path must launch its kernel, with no
   kernel fallback and, for the fused paths, admitted plan nodes.
2. **Served path at real size.** Graph500 RMAT scale 20 (1,048,576
   vertices, edge factor 16) behind the async service and HTTP front end
   that ``serve --http`` starts, with a memory budget that lets the count
   tables take several GB of HBM. Four requests are POSTed and polled to
   DONE: ``u5``, ``u7``, ``u7`` with another seed (an engine-cache hit) and
   an edge-list tree, under ``serve``'s default dispatch watchdog.
   Estimates must be finite, every degradation ladder at level 0, every
   breaker closed, no dispatch retried, no kernel fallen back. Then the
   first colorings the service counted for ``u7`` are counted again by
   segment SpMM + Pallas eMA, which must agree to 1e-5.

The four-chip run counts RMAT scale 20 with ``u7`` on a (data=4, model=1)
mesh through ``DistributedPgbsc`` and compares each coloring's count with
a single-chip ``CountingEngine`` on the same coloring.

Lines before the last are informational (timings, memory); the last line
is one JSON object: ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import tempfile
import time
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))

RMAT_SERVED_SCALE = 20
# Per-engine table budget: the executor's model puts u7's tables at
# 2.6 GB for a dispatch of SERVED_ROUND colorings, and a v5e chip peaked
# at 3.0 GB serving them. Compiled ahead of time for the chip, the u7
# program reports 17.3 GB of temporaries at 16 colorings (8.2 GB at 8),
# more than the chip's 16 GB, so the round stays at 8.
SERVED_BUDGET_MB = 3072
SERVED_ROUND = 8              # colorings per dispatch
# The served phase runs its segment SpMM at RMAT-20 at about 0.16 s per
# table row on a v5e chip in 32-row steps (0.27 s a row for an 8-row table
# in one step), so its cost is counted in rows (``spmm_cols_per_coloring``):
# u5 10 and u7 42 per coloring, and 8 for this 4-vertex tree rooted at a
# leaf of its star.
TREE_EDGES = "0-1,1-2,1-3@0"
COMPARED = 2                  # served u7 colorings recounted with Pallas eMA
REL_TOL = 1e-5                # f32 paths vs their reference
BF16_TOL = 1e-2               # bf16 storage vs the f32 reference


class SmokeFailure(AssertionError):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def require_tpus(n: int):
    """The devices as JAX reports them; exits unless they are >= n TPUs."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found: JAX's devices are "
                 f"{devs[0].platform!r} x{len(devs)}; this check runs on "
                 f"TPU chips only")
    if len(devs) < n:
        sys.exit(f"chip_smoke: needs {n} TPU chips, JAX sees {len(devs)}")
    return devs


# ------------------------------------------------------------------ helpers
def counter_total(name: str) -> float:
    from repro.obs import metrics
    return sum(v for k, v in metrics.snapshot()["counters"].items()
               if k.split("{")[0] == name)


def rel_err(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want) / np.maximum(np.abs(want), 1.0)))


# ------------------------------------------------------------ phase 1
def phase_kernels(rmat_scale: int = 14) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.core import build_engine, get_template
    from repro.core.oracle import count_colorful_embeddings
    from repro.core.templates import TreeTemplate
    from repro.graph import erdos_renyi, rmat
    from repro.graph.coloring import coloring_numpy
    from repro.obs import metrics

    graphs = {"er500": erdos_renyi(500, 8.0, seed=0),
              f"rmat{rmat_scale}": rmat(rmat_scale, 16, seed=0)}
    # (path, engine options, kernel launch counter labels, tolerance)
    paths = [
        ("pallas_gather", dict(spmm_method="pallas_gather"),
         dict(kernel="spmm", path="pallas_gather"), REL_TOL),
        ("pallas_bsr", dict(spmm_method="pallas_bsr"),
         dict(kernel="spmm", path="pallas_bsr"), REL_TOL),
        ("pallas_ema", dict(use_pallas_ema=True),
         dict(kernel="ema", path="pallas"), REL_TOL),
        ("fused", dict(fuse_spmm_ema=True),
         dict(kernel="fused", path="pallas"), REL_TOL),
        ("fused_bf16", dict(fuse_spmm_ema=True, dtype=jnp.bfloat16),
         dict(kernel="fused", path="pallas"), BF16_TOL),
    ]
    # two k=5 trees whose dedup plan shares a passive child between two
    # consumers: the fused shared-passive group's shape
    bundle = (TreeTemplate([(0, 1), (1, 2), (0, 3), (0, 4)], root=0,
                           name="bundle_a"),
              TreeTemplate([(0, 1), (1, 2), (2, 3), (1, 4)], root=0,
                           name="bundle_b"))
    cases = [(t, "plain", paths) for t in ("u5", "u7")]
    cases.append((bundle, "dedup",
                  [("fused_shared", dict(fuse_spmm_ema=True),
                    dict(kernel="fused_shared", path="pallas"), REL_TOL)]))
    n_col = 2
    for gname, g in graphs.items():
        for tpl, plan, case_paths in cases:
            trees = list(tpl) if isinstance(tpl, tuple) \
                else [get_template(tpl)]
            tname = "+".join(t.name for t in trees)
            k = trees[0].k
            cols = np.stack([coloring_numpy(0, i, g.n, k)
                             for i in range(n_col)])
            t0 = time.perf_counter()
            if gname == "er500":
                ref_name = "oracle"
                want = np.array([[count_colorful_embeddings(g, t, c)
                                  for t in trees] for c in cols])
            else:
                ref_name = "xla_segment"
                ref = build_engine(g, list(tpl) if len(trees) > 1
                                   else trees[0], "pgbsc", plan=plan)
                want = np.asarray(ref.count_colorful_batch(cols)[0])
            want = want.reshape(n_col, len(trees))
            ref_s = time.perf_counter() - t0
            for name, kw, labels, tol in case_paths:
                launches = metrics.counter("kernel_launches_total",
                                           **labels).value
                fallbacks = counter_total("kernel_fallbacks_total")
                t0 = time.perf_counter()
                eng = build_engine(g, list(tpl) if len(trees) > 1
                                   else trees[0], "pgbsc", plan=plan, **kw)
                got = np.asarray(eng.count_colorful_batch(cols)[0])
                secs = time.perf_counter() - t0
                got = got.reshape(n_col, len(trees))
                err = rel_err(got, want)
                label = f"{gname}/{tname}/{name}"
                check(np.isfinite(got).all(), f"{label}: non-finite counts")
                check(err <= tol, f"{label}: rel err {err:.3g} vs "
                                  f"{ref_name} > {tol:g} ({got} vs {want})")
                check(metrics.counter("kernel_launches_total", **labels)
                      .value > launches, f"{label}: kernel never launched")
                check(counter_total("kernel_fallbacks_total") == fallbacks,
                      f"{label}: a kernel fell back to XLA")
                if kw.get("fuse_spmm_ema"):
                    admitted = sorted(i for i, v in eng.fusion_report.items()
                                      if v.startswith("admitted"))
                    check(bool(admitted),
                          f"{label}: fusion admitted no node "
                          f"({eng.fusion_report})")
                log(f"kernel {label}: rel_err={err:.3g} vs {ref_name} "
                    f"(build+compile+run {secs:.2f}s, reference "
                    f"{ref_s:.2f}s)")
    check(counter_total("kernel_fallbacks_total") == 0,
          "kernel fallbacks during the kernel phase")


# ------------------------------------------------------------ phase 2
def _http(port: int, method: str, path: str, body=None) -> tuple[int, dict]:
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=data, method=method,
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=60) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


def phase_served(scale: int = RMAT_SERVED_SCALE) -> None:
    import gc

    import jax
    import numpy as np

    from repro.core import build_engine
    from repro.launch import serve
    from repro.obs import metrics

    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_ledger_") as ledger:
        args = serve.parse_args([
            "--graph", f"rmat:{scale}", "--templates", "u5,u7",
            "--template-edges", TREE_EDGES, "--http", "0",
            "--ledger", ledger, "--iters", str(SERVED_ROUND),
            "--round-size", str(SERVED_ROUND),
            "--memory-budget-mb", str(SERVED_BUDGET_MB)])
        g = serve.load_graph(args.graph, args.edge_list)
        log(f"served graph rmat:{scale}: n={g.n} edge_slots={g.m} "
            f"max_degree={g.max_degree} (generated in "
            f"{time.perf_counter() - t0:.1f}s)")
        svc, httpd = serve.start_http_service(args, g)
        port = httpd.server_address[1]
        try:
            bodies = [
                ("u5", {"template": "u5"}),
                ("u7", {"template": "u7"}),
                ("u7/seed1", {"template": "u7", "seed": 1}),
                ("tree4", {"template": {
                    "edges": [[int(v) for v in e.split("-")]
                              for e in TREE_EDGES[:-2].split(",")],
                    "root": 0}}),
            ]
            posted = []
            for label, body in bodies:
                body.update(max_iters=SERVED_ROUND, wait=False)
                code, out = _http(port, "POST", "/count", body)
                check(code in (200, 202), f"POST {label}: HTTP {code} {out}")
                posted.append((label, out["requests"][0]["id"],
                               time.perf_counter()))
            results = {}
            deadline = time.monotonic() + 900
            while len(results) < len(posted):
                check(time.monotonic() < deadline,
                      f"requests not DONE in time: {sorted(results)}")
                for label, rid, t_post in posted:
                    if label in results:
                        continue
                    code, out = _http(port, "GET", f"/result/{rid}")
                    check(out.get("status") not in ("failed", "shed",
                                                    "cancelled"),
                          f"{label}: {out}")
                    if out.get("status") != "done":
                        continue
                    res = results[label] = out["result"]
                    check(math.isfinite(res["estimate"])
                          and res["estimate"] > 0,
                          f"{label}: estimate {res['estimate']}")
                    b = res.get("breakdown") or {}
                    log(f"served {label}: estimate={res['estimate']:.6g} "
                        f"rel_stderr={res['rel_stderr']:.3g} "
                        f"iters={res['iterations']} time_to_estimate="
                        f"{time.perf_counter() - t_post:.2f}s compile_s="
                        f"{b.get('compile_s', 0.0):.2f} execute_s="
                        f"{b.get('execute_s', 0.0):.2f}")
                time.sleep(0.2)
            code, health = _http(port, "GET", "/healthz")
            res_state = health["resilience"]
            check(code == 200 and health["ok"], f"healthz: {health}")
            check(not res_state["degraded_ladders"],
                  f"degraded ladders: {res_state['degraded_ladders']}")
            br = res_state["breakers"]
            check(not br["unhealthy"], f"breakers not closed: {br}")
            check(counter_total("dispatch_retries_total") == 0,
                  "a dispatch was retried")
            check(counter_total("engine_rebuilds_total") == 0,
                  "an engine was rebuilt by the degradation ladder")
            check(counter_total("kernel_fallbacks_total") == 0,
                  "a kernel fell back to XLA")
            ec = svc.stats()["engine_cache"]
            check(ec["hits"] >= 1, f"no engine-cache hit: {ec}")
            log(f"served ladders={res_state['ladder_total']} at level 0, "
                f"breakers={br['counts']}, engine cache {ec}")
            engines = [svc.engine_cache._engines[k]
                       for k in svc.engine_cache._engines]
            model_peak = max(e.exec_choice.peak_bytes_per_coloring
                             * min(e.batch_size, SERVED_ROUND)
                             for e in engines)
            # the colorful sums the service's segment engine counted for
            # u7's first colorings (seed 0), from its runner ledger
            (u7,) = [grp for grp in svc._groups.values()
                     if grp.runner.k == 7 and grp.seed == 0]
            served = u7.runner.completed_iterations()
        finally:
            httpd.shutdown()
            svc.close()
    dev = jax.devices()[0]
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    log(f"served device peak_bytes_in_use={peak} executor model peak "
        f"table bytes={model_peak} (largest engine, one dispatch of "
        f"{SERVED_ROUND} colorings)")
    del svc, httpd, engines, u7
    gc.collect()

    # the same colorings at this size through segment SpMM + Pallas eMA.
    # The Pallas gather SpMM cannot run here: its kernel prefetches a tile
    # id pair per edge chunk into the 1 MiB SMEM, and this graph has
    # millions of chunks (see ROADMAP A5).
    ids = list(range(COMPARED))
    launches = metrics.counter("kernel_launches_total", kernel="ema",
                               path="pallas").value
    t0 = time.perf_counter()
    eng = build_engine(g, "u7", "pgbsc", plan="optimized",
                       memory_budget_bytes=SERVED_BUDGET_MB * 2 ** 20,
                       use_pallas_ema=True)
    got = eng.count_iterations_batch(ids, seed=0)
    log(f"real-size segment+pallas_ema: {time.perf_counter() - t0:.2f}s for "
        f"{COMPARED} colorings (build, compile, run)")
    check(metrics.counter("kernel_launches_total", kernel="ema",
                          path="pallas").value > launches,
          "the Pallas eMA never launched at real size")
    got = np.array([got[i] for i in ids])
    want = np.array([served[i] for i in ids])
    err = rel_err(got, want)
    check(np.isfinite(got).all(), "non-finite real-size counts")
    check(err <= REL_TOL, f"real-size Pallas eMA rel err {err:.3g} "
                          f"({got} vs {want})")
    check(counter_total("kernel_fallbacks_total") == 0,
          "a kernel fell back to XLA at real size")
    log(f"real-size u7 served segment vs segment+pallas_ema on "
        f"{COMPARED} colorings: rel_err={err:.3g}")


# ------------------------------------------------------------ four chips
def phase_four_chips(scale: int = RMAT_SERVED_SCALE, n_seeds: int = 2):
    import jax
    import numpy as np

    from repro.core import CountingEngine, get_template
    from repro.core.distributed import DistributedPgbsc, coloring_for_seed
    from repro.graph import rmat
    from repro.launch.mesh import make_mesh

    devs = jax.devices()[:4]
    t0 = time.perf_counter()
    g = rmat(scale, 16, seed=0)
    t = get_template("u7")
    mesh = make_mesh((4, 1), ("data", "model"), devices=devs)
    dist = DistributedPgbsc(g, t, mesh)
    step, args, shardings = dist.count_step_fn()
    edges = tuple(jax.device_put(a, s)
                  for a, s in zip(args[1:], shardings[1:]))
    for name, a in zip(("src_local", "dst_local", "mask"), edges):
        check(len(a.sharding.device_set) == 4,
              f"{name} is on {len(a.sharding.device_set)} devices")
    log(f"four-chip rmat:{scale}: n={g.n} edge_slots={g.m}; graph, ring "
        f"edges and upload {time.perf_counter() - t0:.1f}s")
    fn = jax.jit(step, in_shardings=shardings)
    one = CountingEngine(g, t)
    for seed in range(n_seeds):
        t1 = time.perf_counter()
        seeds = jax.device_put(np.asarray([seed], np.int32), shardings[0])
        got = float(np.asarray(fn(seeds, *edges))[0])
        t2 = time.perf_counter()
        colors = coloring_for_seed(seed, dist.n_pad, g.n, t.k)[:g.n]
        want = float(one.count_colorful(jax.device_put(colors, devs[0]))[0])
        err = abs(got - want) / max(abs(want), 1.0)
        log(f"four-chip u7 rmat:{scale} coloring seed={seed}: "
            f"sharded={got:.9g} ({t2 - t1:.2f}s) single-chip={want:.9g} "
            f"({time.perf_counter() - t2:.2f}s) rel_err={err:.3g}")
        check(math.isfinite(got) and err <= REL_TOL,
              f"seed {seed}: sharded {got} vs single-chip {want}")
    per_dev = [(d.id, (d.memory_stats() or {}).get("peak_bytes_in_use"))
               for d in devs]
    log(f"four-chip placement: edge arrays on "
        f"{sorted(d.id for d in edges[0].sharding.device_set)}; per-device "
        f"peak_bytes_in_use={per_dev}; {time.perf_counter() - t0:.1f}s")
    check(all(p for _, p in per_dev), "a chip held no memory")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded four-chip comparison")
    args = ap.parse_args(argv)
    devs = require_tpus(4 if args.four_chips else 1)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    log(f"devices: {len(devs)} x {devs[0].device_kind}; compile cache "
        f"{enable_compile_cache()}")
    t0 = time.perf_counter()
    if args.four_chips:
        phase_four_chips()
    else:
        phase_kernels()
        log(f"phase kernels done in {time.perf_counter() - t0:.1f}s")
        phase_served()
    log(f"all phases done in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
