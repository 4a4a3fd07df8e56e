"""Run one benchmark cell once and print its result as the last line.

    python3 perfbench/run.py --workload qwen3-8b.chat --seed 7 \\
        --seconds 40 --trace 0

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of the same window.  Both compare
what the window served with the plain float32 reference and print each
number compared beside its limit, last on standard error and last in the
result line.  Exits non-zero with no result line where JAX finds no
accelerator or fewer chips than the cell needs.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from perfbench import env  # noqa: E402

env.prepare()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax

    env.configure_jax(jax)

    from perfbench.harness import run_cell

    out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                   T_PROCESS, t_jax=time.perf_counter())
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
