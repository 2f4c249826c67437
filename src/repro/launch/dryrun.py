"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes, record memory/cost analysis + collective bytes.
The meshes are 512 host devices of the CPU backend, which ``main`` asks
for before the backend starts.

    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b \
        --cell train_4k --mesh single --out results/dryrun

One JSON per (arch, cell, mesh) so independent processes can split the grid.
``--arch pgbsc`` runs the paper's own distributed counting step (RMAT-1M).
"""

import argparse
import json
import os
import sys
import time
import traceback

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis.hlo import collective_summary, count_ops
from repro.analysis.roofline import model_flops, roofline_from_compiled
from repro.configs import get_config, input_specs, resolve_for_mesh, ARCH_IDS
from repro.launch.mesh import make_production_mesh
from repro.train import sharding as shd
from repro.train.step import (abstract_train_state, build_serve_step,
                              build_train_step, param_specs_for)

PGBSC_CELLS = {
    # paper workloads: (graph n, directed edge slots, template)
    "gs20_u5": {"n": 600_000, "e": 62_000_000, "template": "u5"},
    "rmat1m_u7": {"n": 1_000_000, "e": 400_000_000, "template": "u7"},
    "rmat1m_u10": {"n": 1_000_000, "e": 400_000_000, "template": "u10"},
    "rmat1m_u12": {"n": 1_000_000, "e": 400_000_000, "template": "u12"},
}


def _spec_shardings(mesh, tree_specs):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), tree_specs,
        is_leaf=lambda x: isinstance(x, P))


def run_cell(arch_id: str, cell_name: str, mesh_kind: str) -> dict:
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    chips = len(mesh.devices.ravel())
    t0 = time.time()

    if arch_id == "pgbsc":
        rec = _run_pgbsc(cell_name, mesh, chips)
    elif arch_id == "pgbsc-opt":
        rec = _run_pgbsc(cell_name, mesh, chips, plan="optimized")
    else:
        rec = _run_arch(arch_id, cell_name, mesh, chips)

    rec.update(arch=arch_id, cell=cell_name, mesh=mesh_kind, chips=chips,
               wall_s=round(time.time() - t0, 1))
    return rec


def _microbatches_for(arch, cell) -> int:
    """Gradient-accumulation factor per train cell (activation memory).

    Extrapolation variants (scan_layers=False) skip microbatching: the total
    per-step work is identical and their job is exact cost counting, not
    memory footprint."""
    if arch.family == "lm" and cell.kind == "train" and \
            getattr(arch.model, "scan_layers", True):
        return 8
    return 1


def _lower_cell(arch, cell_name, mesh):
    """Lower one (arch-variant, cell) on the mesh; returns the Lowered."""
    cell = arch.cell(cell_name)
    batch, bspecs, statics = input_specs(arch, cell_name)
    bspecs = resolve_for_mesh(bspecs, mesh)
    d_in = cell.dims.get("d_feat")
    state = abstract_train_state(arch, d_in=d_in)
    if cell.kind == "train":
        pspecs = param_specs_for(arch, state["params"], mesh)
        state_specs = {"params": pspecs,
                       "opt": shd.opt_state_specs(pspecs, state["params"],
                                                  mesh)}
        mb = _microbatches_for(arch, cell)
        step = build_train_step(arch, statics=statics, microbatches=mb)
        in_sh = (_spec_shardings(mesh, state_specs),
                 _spec_shardings(mesh, bspecs))
        return jax.jit(step, in_shardings=in_sh,
                       donate_argnums=0).lower(state, batch)
    params = state["params"]
    pspecs = param_specs_for(arch, params, mesh)
    hints = None
    if arch.family == "lm" and cell.kind == "decode":
        from repro.configs.shapes import decode_hint_specs
        hspecs = resolve_for_mesh(decode_hint_specs(arch, cell), mesh)
        hints = {k: NamedSharding(mesh, v) for k, v in hspecs.items()}
    serve = build_serve_step(
        arch, cell.kind if cell.kind in ("prefill", "decode",
                                         "retrieval") else "serve",
        statics=statics, shard_hints=hints)
    in_sh = (_spec_shardings(mesh, pspecs), _spec_shardings(mesh, bspecs))
    donate = (1,) if cell.kind == "decode" else ()
    return jax.jit(serve, in_shardings=in_sh,
                   donate_argnums=donate).lower(params, batch)


def _lm_variant(arch, n_scan: int):
    """Arch with a reduced, *unrolled* layer count + HLO-visible attention
    chunks, for the layer-linear cost extrapolation (see _run_arch)."""
    import dataclasses
    m = arch.model
    front = m.first_dense_layers if m.moe else 0
    return dataclasses.replace(
        arch, model=dataclasses.replace(
            m, n_layers=front + n_scan, attn_unroll=True,
            scan_layers=False))


def _moe_grouped(arch, mesh):
    """Set MoE dispatch groups = data-shard count (GShard grouping)."""
    import dataclasses
    m = arch.model
    if getattr(m, "moe", None) is None:
        return arch
    g = 1
    for ax in ("pod", "data"):
        if ax in mesh.axis_names:
            g *= mesh.shape[ax]
    return dataclasses.replace(
        arch, model=dataclasses.replace(
            m, moe=dataclasses.replace(m.moe, groups=g)))


def _run_arch(arch_id, cell_name, mesh, chips):
    arch = _moe_grouped(get_config(arch_id), mesh)
    cell = arch.cell(cell_name)

    # full-size compile: memory analysis + collective structure
    lowered = _lower_cell(arch, cell_name, mesh)
    rec = _finalize(lowered, chips, mf=model_flops(arch, cell))

    if arch.family == "lm":
        # XLA HloCostAnalysis counts while (scan) bodies once; flops/bytes/
        # collective-bytes are exactly linear in the scanned layer count, so
        # two small compiles with unrolled attention chunks give the exact
        # totals: f(L) = f(2) + (L-2)/2 * (f(4) - f(2)).
        m = arch.model
        front = m.first_dense_layers if m.moe else 0
        l_full = m.n_layers - front
        roof2 = _roof_only(_lm_variant(arch, 2), cell_name, mesh, chips)
        roof4 = _roof_only(_lm_variant(arch, 4), cell_name, mesh, chips)

        def extrap(k):
            return roof2[k] + (l_full - 2) / 2.0 * (roof4[k] - roof2[k])

        from repro.analysis.roofline import RooflineTerms
        corrected = RooflineTerms(
            flops=extrap("flops"), bytes_accessed=extrap("bytes"),
            collective_bytes=extrap("collective_bytes"), chips=chips)
        rec["roofline_raw_scan_body"] = rec["roofline"]
        rec["roofline"] = corrected.as_dict()
        rec["useful_flops_ratio"] = (
            rec["model_flops_per_device"] / corrected.flops
            if corrected.flops else None)
        rec["extrapolation"] = {"l2": roof2, "l4": roof4,
                                "l_full_scanned": l_full}
    return rec


def _roof_only(arch_variant, cell_name, mesh, chips) -> dict:
    lowered = _lower_cell(arch_variant, cell_name, mesh)
    compiled = lowered.compile()
    text = compiled.as_text()
    roof = roofline_from_compiled(compiled, chips, hlo_text=text)
    return {"flops": roof.flops, "bytes": roof.bytes_accessed,
            "collective_bytes": roof.collective_bytes}


def _run_pgbsc(cell_name, mesh, chips, plan: str = "dedup"):
    from repro.core.distributed import DistributedPgbsc
    from repro.core.templates import get_template
    spec = PGBSC_CELLS[cell_name]
    dist = DistributedPgbsc(
        None, get_template(spec["template"]), mesh, plan=plan,
        abstract_dims={"n": spec["n"], "e": spec["e"]})
    step, args, shardings = dist.count_step_fn()
    jitted = jax.jit(step, in_shardings=shardings)
    lowered = jitted.lower(*args)
    mf = 2.0 * (dist.plan.n_nodes * spec["e"])  # order-of-magnitude useful work
    return _finalize(lowered, chips, mf=mf)


def _finalize(lowered, chips, mf: float) -> dict:
    t0 = time.time()
    compiled = lowered.compile()
    compile_s = time.time() - t0
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    roof = roofline_from_compiled(compiled, chips, hlo_text=text)
    colls = collective_summary(text)
    per_dev_model_flops = mf / chips
    rec = {
        "ok": True,
        "compile_s": round(compile_s, 1),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(
                mem, "generated_code_size_in_bytes", None),
        },
        "roofline": roof.as_dict(),
        "collectives": colls,
        "hlo_ops": count_ops(text),
        "model_flops_global": mf,
        "model_flops_per_device": per_dev_model_flops,
        "useful_flops_ratio": (per_dev_model_flops / roof.flops
                               if roof.flops else None),
    }
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--cell", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi",
                                                       "both"])
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args(argv)
    # fake host devices exist on the CPU backend only; the flag is added
    # to the caller's XLA_FLAGS, which must not have started a backend yet
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " "
                               "--xla_force_host_platform_device_count=512"
                               ).strip()
    jax.config.update("jax_platforms", "cpu")

    archs = list(ARCH_IDS) + ["pgbsc"] if args.arch == "all" \
        else args.arch.split(",")
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    os.makedirs(args.out, exist_ok=True)

    n_fail = 0
    for arch_id in archs:
        if arch_id in ("pgbsc", "pgbsc-opt"):
            cells = list(PGBSC_CELLS)
        else:
            cells = [c.name for c in get_config(arch_id).cells]
        if args.cell != "all":
            cells = [c for c in cells if c in args.cell.split(",")]
        for cell in cells:
            for mesh_kind in meshes:
                tag = f"{arch_id}__{cell}__{mesh_kind}"
                path = os.path.join(args.out, tag + ".json")
                if args.skip_existing and os.path.exists(path):
                    print(f"[skip] {tag}")
                    continue
                print(f"[run ] {tag}", flush=True)
                try:
                    rec = run_cell(arch_id, cell, mesh_kind)
                except Exception as e:  # noqa: BLE001 — record the failure
                    rec = {"ok": False, "arch": arch_id, "cell": cell,
                           "mesh": mesh_kind, "error": repr(e),
                           "traceback": traceback.format_exc()[-4000:]}
                    n_fail += 1
                    print(f"[FAIL] {tag}: {e!r}", flush=True)
                with open(path + ".tmp", "w") as f:
                    json.dump(rec, f, indent=1)
                os.replace(path + ".tmp", path)
                if rec.get("ok"):
                    r = rec["roofline"]
                    print(f"[ ok ] {tag} compile={rec['compile_s']}s "
                          f"flops={r['flops']:.3e} bytes={r['bytes']:.3e} "
                          f"coll={r['collective_bytes']:.3e} "
                          f"dom={r['dominant']}", flush=True)
    return 1 if n_fail else 0


if __name__ == "__main__":
    sys.exit(main())
