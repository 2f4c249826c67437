"""End-to-end driver: fault-tolerant distributed subgraph counting service.

Runs the paper's workload (PGBSC on an Erdos-Renyi graph) across every
device JAX sees, with per-iteration checkpointing — kill it mid-run and
rerun: it resumes from the ledger. Eight or more devices form a
(pod=2, data=n/4, model=2) mesh, fewer a (data=n, model=1) one. On a host
without accelerators, simulate eight devices:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        PYTHONPATH=src python examples/distributed_counting.py [--iters 32]
"""

import argparse

import jax

from repro.core import count_subgraphs_exact, get_template
from repro.core.distributed import DistributedPgbsc
from repro.core.runner import EstimatorRunner, distributed_counter
from repro.graph import erdos_renyi
from repro.launch.mesh import make_mesh

ap = argparse.ArgumentParser()
ap.add_argument("--iters", type=int, default=32)
ap.add_argument("--ledger", default="/tmp/pgbsc_ledger")
args = ap.parse_args()

g = erdos_renyi(200, 6.0, seed=4)
t = get_template("u5")
n_dev = len(jax.devices())
mesh = (make_mesh((2, n_dev // 4, 2), ("pod", "data", "model"))
        if n_dev >= 8 and n_dev % 4 == 0
        else make_mesh((n_dev, 1), ("data", "model")))
print(f"mesh: {dict(mesh.shape)}  graph: n={g.n} m={g.m}  template: {t}")

dist = DistributedPgbsc(g, t, mesh)
runner = EstimatorRunner(
    distributed_counter(dist, seed=3), k=t.k,
    automorphisms=t.automorphisms, n_iterations=args.iters,
    ledger_dir=args.ledger, checkpoint_every=4, seed=3)
res = runner.run()

print(f"estimate={res.count:.5g}  colorful_sum={res.colorful_sum:.4g}")
print(f"iterations done={len(res.completed)}  restarts={res.restarts}  "
      f"elapsed={res.elapsed_s:.1f}s")
exact = count_subgraphs_exact(g, t)
print(f"exact={exact}  rel_err={abs(res.count - exact) / exact:.3%}")
