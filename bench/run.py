"""Benchmark entry point: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload g500-s18.u7-closed --seed 7 \\
        --seconds 30 --trace 0

Prints progress lines, then the numbers compared for ``correct`` on
standard error, then one JSON result line as the last line of standard
output. Exits non-zero with no result line when JAX finds no TPU, or fewer
chips than the cell asks for. ``--rehearsal`` runs the cell on the CPU at a
tiny scale to check the harness; its result line says ``"rehearsal": true``
and measures nothing.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU, tiny scale, harness check only")
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench.harness.env import jax_env

    jax_env(str(ROOT), args.rehearsal)
    from bench.harness import runner
    from bench.harness.device import NoChip
    try:
        out = runner.run(args.workload, args.seed, args.seconds,
                         bool(args.trace), T_START,
                         rehearsal=args.rehearsal)
    except NoChip as exc:
        print(f"no chip: {exc}", file=sys.stderr, flush=True)
        return 2
    runner.print_checks(out["checks"])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
