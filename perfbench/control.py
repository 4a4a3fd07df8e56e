"""Readings behind the limits of ``correct``: the program's, on many seeds,
and the float8 control's, at the cell's own size and load.

    python3 perfbench/control.py --workload qwen3-8b.chat --seconds 10 \\
        --seeds 101,102,...  --control-seeds 101,102,103

One process, one set-up.  For each seed it serves that seed's weights and
one window of the cell's traffic (the whole queue for a closed cell), and
prints one JSON line: the numbers the run's comparison reads
(:func:`perfbench.check.compare`), each beside the cell's limit, and the
``correct`` they give (``program``).  For a control seed, the same numbers
with the tokens the float8 reference puts first at the same positions in
place of the served ones, and their ``correct`` (``control``).  An
``--int8-seeds`` seed is served again by the program with its weight-only
int8 path switched on and goes through the same comparison
(``int8_program``).  The program must come out correct and each control
not; ``PERF.md`` lists the readings.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from perfbench import env  # noqa: E402

env.prepare()

from perfbench import check  # noqa: E402  (no JAX: numpy alone)


def _reading(nums, gap):
    return {"numbers": {k: v["value"] for k, v in nums.items()},
            "correct": check.verdict(nums),
            "per_request": [float(x.max()) for x in gap]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--int8-seeds", default="")
    args = ap.parse_args(argv)

    import jax

    env.configure_jax(jax)

    from perfbench import harness, spec

    cell = spec.cell(args.workload)
    clock = harness.CompileClock()
    harness.device_info(cell.chips)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    ctl = {int(s) for s in args.control_seeds.split(",") if s}
    print(json.dumps({"limits": cell.cell["limits"]}), flush=True)
    server = None
    for seed in seeds:
        key, rng = harness.seed_key(seed)
        if server is None:
            server = harness.Server(cell, key, clock)
        else:
            server.set_weights(key)
        raw = harness.window(server, args.seconds, rng, clock)
        server.engine.params = None                  # room for the reference
        t = time.perf_counter()
        reqs = check.sample(raw["requests"],
                            int(cell.cell["check"]["requests"]), seed)
        g = check.gaps(cell, reqs, key, control=seed in ctl)
        line = {"seed": seed, "requests": len(raw["requests"]),
                "compared_tokens": int(sum(len(x) for x in g["gap"])),
                "program": _reading(check.numbers(cell, g["gap"],
                                                  raw["requests"]), g["gap"]),
                "reference_s": time.perf_counter() - t}
        if seed in ctl:
            line["control"] = _reading(check.numbers(
                cell, g["control_gap"], raw["requests"]), g["control_gap"])
        print(json.dumps(line), flush=True)
        del g
    if server is not None:
        server.close()
    # the program's own lower-precision path: weight-only int8, served on
    # the same weights and requests as the seed's bf16 run, and judged by
    # the run's own comparison
    for seed in (int(s) for s in args.int8_seeds.split(",") if s):
        key, rng = harness.seed_key(seed)
        server = harness.Server(cell, key, clock, quant="int8")
        raw = harness.window(server, args.seconds, rng, clock)
        server.close()
        run = harness.make_run(cell, raw, args.seconds, 0.0)
        nums = check.compare(cell, run, key, seed)
        print(json.dumps({"seed": seed, "int8_program": {
            "numbers": {k: v["value"] for k, v in nums.items()},
            "correct": check.verdict(nums)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
