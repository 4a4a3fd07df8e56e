"""Find the knee of an open-loop cell: the highest rate at which the queue
does not grow over the window.

    python3 perfbench/sweep.py --workload qwen3-8b.chat --seed 5 \\
        --seconds 30 --rates 2,3,4,5

One process, one set-up; then one window per rate, each a fresh trace from
the seed at that rate.  Prints one JSON line per rate: TTFT percentiles, the
mean TTFT of the first and the last quarter of requests by due time (a
queue that grows makes the last quarter wait longer), how many requests
had not had their first token when the window closed, and the ITL tail.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from perfbench import env  # noqa: E402

env.prepare()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    env.configure_jax(jax)

    from perfbench import harness, spec, stats

    cell = spec.cell(args.workload)
    clock = harness.CompileClock()
    harness.device_info(cell.chips)
    key, rng = harness.seed_key(args.seed)
    server = harness.Server(cell, key, clock)
    for rate in (float(r) for r in args.rates.split(",")):
        cell.cell["rate_hz"] = rate
        raw = harness.window(server, args.seconds, rng, clock)
        reqs = sorted(raw["requests"], key=lambda r: r["due"])
        tt = stats.ttfts(reqs)
        q = max(1, len(reqs) // 4)
        close = raw["t_start"] + args.seconds
        ends = sorted(e for _, e, _ in raw["steps"])
        gaps = stats.inter_token_gaps(reqs, ends, close)
        print(json.dumps({
            "rate_hz": rate, "requests": len(reqs),
            "ttft_p50_ms": stats.percentile(tt, 50) * 1e3,
            "ttft_p90_ms": stats.percentile(tt, 90) * 1e3,
            "ttft_first_quarter_ms": 1e3 * float(np.mean(tt[:q])),
            "ttft_last_quarter_ms": 1e3 * float(np.mean(tt[-q:])),
            "waiting_at_close": sum(r["t_first"] > close for r in reqs),
            "itl_p95_ms": stats.percentile(gaps, 95) * 1e3,
            "decode_step_ms": 1e3 * raw["stats"]["decode_s"]
            / max(1, raw["stats"]["decode_steps"]),
            "occupancy": float(np.mean([l for _, _, l in raw["steps"]])),
            "window_s": raw["t_end"] - raw["t_start"],
            "compiles": raw["compiles"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
