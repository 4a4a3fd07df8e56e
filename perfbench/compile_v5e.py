"""Compile each cell's decode step and largest prefill for a described
TPU v5e, with no chip attached, and print what the compiler says of memory.

    JAX_PLATFORMS=cpu python3 perfbench/compile_v5e.py [workload ...]

For every workload of ``BENCHMARK.json`` (or those named) it builds the
program's prefill and decode runners at the cell's lanes and ``max_ctx``,
from shapes alone, and compiles them for one chip of a ``v5e:2x2``
topology.  The program picks its generated kernels from the backend, which
here is the CPU, so the script tells it the backend is a TPU.  A program
that does not fit one chip's memory, or a kernel Mosaic refuses, fails
here at no chip time.  Prints one JSON line per program.
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv) -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    import repro.ops
    from repro.launch.serving import paged
    from repro.launch.serving.runners import DecodeRunner, PrefillRunner
    from repro.models.api import get_api
    from repro.search import serving_phase

    from perfbench import spec, traffic

    repro.ops._use_pallas = lambda: True          # the described chip's path
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=chip), tree)

    names = argv or [w["name"] for w in spec.benchmark()["workloads"]]
    for name in names:
        cell = spec.cell(name)
        arch, serve = cell.arch(), cell.serve()
        cfg = serve.program_config(cell.config)
        c = cell.cell
        ps, lanes = int(c["page_size"]), int(c["lanes"])
        max_pages = -(-int(c["max_ctx"]) // ps)
        n_pages = 1 + lanes * max_pages
        params = on_chip(jax.eval_shape(
            lambda k: serve.program_params(arch.make_weights(cell.config, k)),
            jax.random.key(0)))
        pools = on_chip(jax.eval_shape(
            lambda: paged.pool_init(cfg, n_pages, ps)))
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=chip)
        api = get_api(cfg)
        dec = DecodeRunner(cfg, api, ps, lanes, max_pages)
        pre = PrefillRunner(cfg, api, ps)
        longest = max(traffic.allowed_lengths(cell.traffic["prompt"]))
        n = -(-longest // ps)
        progs = []
        with serving_phase("decode"):
            progs.append(("decode", dec._step.lower(
                params, pools, i32(lanes, max_pages), i32(lanes),
                i32(lanes)).compile()))
        with serving_phase("prefill"):
            progs.append((f"prefill_{n * ps}", pre._run.lower(
                params, i32(1, n * ps), i32(1)).compile()))
            caches = on_chip(jax.eval_shape(
                lambda p, t, l: pre._run(p, t, l)[1],
                params, i32(1, n * ps), i32(1)))
            progs.append((f"store_{n * ps}", pre._store.lower(
                pools, caches, i32(n)).compile()))
        for prog, compiled in progs:
            m = compiled.memory_analysis()
            text = compiled.as_text()
            print(json.dumps({
                "workload": name, "program": prog,
                "argument_bytes": m.argument_size_in_bytes,
                "output_bytes": m.output_size_in_bytes,
                "alias_bytes": m.alias_size_in_bytes,
                "temp_bytes": m.temp_size_in_bytes,
                "generated_kernel_calls": text.count("tpu_custom_call"),
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
