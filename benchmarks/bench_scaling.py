"""Paper Fig. 13 analog: scaling of the distributed engine with device count.

Thread scaling on the paper's Skylake node becomes device scaling of the
shard_map ring engine here (1 real core under the hood, so this measures
partitioning overhead, not true speedup — the trend of interest is that the
ring decomposition stays correct and the per-device work shrinks).

Each device count runs in a child process with that many fake host
devices of the CPU backend. The bench therefore refuses to run where the
default backend is an accelerator: a parent that has touched the chip holds
it, and fake host devices would measure nothing about the chip anyway. A
child that fails fails the bench.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.common import emit

_WORKER = """
import os, sys, json, time
import jax
from repro.core import get_template
from repro.core.distributed import DistributedPgbsc
from repro.graph import rmat
from repro.launch.mesh import make_mesh

d = %d
g = rmat(10, 16, seed=7)
t = get_template("u5")
mesh = make_mesh((d, 1), ("data", "model"))
dist = DistributedPgbsc(g, t, mesh)
step, args, _ = dist.count_step_fn()
f = jax.jit(step)
out = f(*args); out.block_until_ready()
t0 = time.time()
for _ in range(3):
    out = f(*args)
out.block_until_ready()
rec = {"devices": d, "sec": (time.time() - t0) / 3, "count": float(out[0]),
       "batch": {}}

# batched per-pod dispatch: iterations/sec vs batch size (one scanned
# device call per batch; warm cache first so jit cost is excluded)
n_iters = 8
for bs in (1, 4, 8):
    dist.count_iterations(list(range(n_iters)), seed=0, batch_size=bs)
    t0 = time.time()
    dist.count_iterations(list(range(n_iters)), seed=0, batch_size=bs)
    rec["batch"]["bs%%d" %% bs] = n_iters / (time.time() - t0)
print(json.dumps(rec))
"""


def run() -> dict:
    import jax
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            f"fig13_scaling fakes host devices on the CPU backend; the "
            f"default backend here is {jax.default_backend()!r}")
    out = {}
    counts = {}
    for d in (1, 2, 4, 8):
        env = dict(os.environ)
        env["PYTHONPATH"] = "src"
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_"
                            f"platform_device_count={d}").strip()
        proc = subprocess.run(
            [sys.executable, "-c", _WORKER % d], env=env,
            capture_output=True, text=True, timeout=600)
        if proc.returncode != 0:
            raise RuntimeError(f"fig13 worker with {d} devices failed:\n"
                               f"{proc.stderr[-4000:]}")
        rec = json.loads(proc.stdout.strip().splitlines()[-1])
        emit(f"fig13/devices{d}", rec["sec"] * 1e6,
             f"count={rec['count']:.6g}")
        for bs, ips in rec["batch"].items():
            emit(f"fig13/devices{d}/batch/{bs}", 1e6 / ips,
                 f"{ips:.1f} iters/s")
        out[d] = rec["sec"]
        counts[d] = rec["count"]
    # ring decomposition must be device-count invariant up to f32
    # reassociation (counts here exceed 2^24, so exactness doesn't apply)
    vals = list(counts.values())
    if vals:
        spread = (max(vals) - min(vals)) / max(abs(max(vals)), 1e-30)
        assert spread < 1e-6, counts
    return out
