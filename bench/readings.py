"""Readings that set the limits of ``correct``: the program as configured
and the control (the program's own bfloat16 storage path) on many seeds,
in one process so that set-up and compiles are paid once.

    python3 bench/readings.py --workload g500-s18.u7-closed \\
        --seeds 101-112 --control-seeds 201-203 --seconds 30

Prints one JSON line per run (``kind``, ``seed``, ``correct`` and every
number compared) and a summary: the largest sound reading and the smallest
control reading of each number.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def seed_list(spec: str) -> list[int]:
    out = []
    for part in filter(None, spec.split(",")):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--control-dtype", default="bfloat16")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench.harness.env import jax_env

    jax_env(str(ROOT), args.rehearsal)
    from bench.harness import runner
    runs = [("program", s) for s in seed_list(args.seeds)]
    runs += [("control", s) for s in seed_list(args.control_seeds)]
    worst: dict = {}
    for kind, seed in runs:
        out = runner.run(args.workload, seed, args.seconds, False,
                         time.perf_counter(), rehearsal=args.rehearsal,
                         dtype=args.control_dtype if kind == "control"
                         else None, log=lambda *a: None)
        vals = {k: c["value"] for k, c in out["checks"].items()}
        print(json.dumps({"kind": kind, "seed": seed,
                          "correct": out["correct"],
                          "attempted": out["attempted"], **vals}),
              flush=True)
        for k, v in vals.items():
            agg = max if kind == "program" else min
            key = (kind, k)
            worst[key] = v if key not in worst else agg(worst[key], v)
    print(json.dumps({f"{kind}:{k}": v for (kind, k), v in worst.items()}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
