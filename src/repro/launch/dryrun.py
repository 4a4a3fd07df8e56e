import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS_EXTRA", "")
)

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

MUST set XLA_FLAGS before any other import (jax locks the device count on
first init) — hence the lines above.

For each cell this:
  1. builds the StepBundle (train/prefill/serve) with full shardings,
  2. ``jax.jit(fn, in_shardings=..., out_shardings=...).lower(...)``,
  3. ``.compile()`` — proving the distribution config is coherent,
  4. records memory_analysis / cost_analysis / per-collective bytes parsed
     from the compiled HLO into a JSON blob for EXPERIMENTS.md.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b \
      --shape train_4k --mesh pod --out results/
  PYTHONPATH=src python -m repro.launch.dryrun --all --out results/
"""

import argparse
import json
import re
import time
import traceback
from typing import Dict

import jax

from ..configs import ARCH_IDS, SHAPES, cell_is_applicable, get_config
from .mesh import make_production_mesh
from .steps import prefill_bundle, serve_bundle, train_bundle

_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s32": 4, "u32": 4,
    "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1, "s64": 8, "u64": 8,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(type_str: str) -> int:
    """Bytes of an HLO type string like 'f32[128,1024]' or a tuple thereof."""
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.groups()
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum output-shape bytes of every collective op in the compiled HLO."""
    out = {k: 0 for k in _COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        s = line.lstrip()
        # match '%name = TYPE op-name(' with op possibly suffixed (-start)
        for coll in _COLLECTIVES:
            started = f" {coll}-start(" in s
            if not (f" {coll}(" in s or started):
                continue
            eq = s.find("=")
            if eq < 0:
                continue
            op_tok = f" {coll}-start(" if started else f" {coll}("
            idx = s.find(op_tok)
            type_str = s[eq + 1: idx]
            b = _shape_bytes(type_str)
            # async -start ops have tuple types aliasing (operand, result):
            # count the payload once
            if started and type_str.strip().startswith("("):
                b //= 2
            out[coll] += b
            out["count"] += 1
            break
    return out


def run_cell(
    arch: str, shape_name: str, multi_pod: bool,
    hlo_dir: str | None = None,
) -> Dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = cell_is_applicable(cfg, shape)
    rec: Dict = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": 512 if multi_pod else 256,
    }
    if not ok:
        rec.update(status="skipped", reason=why)
        return rec
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            bundle = train_bundle(mesh, cfg, shape)
        elif shape.kind == "prefill":
            bundle = prefill_bundle(mesh, cfg, shape)
        else:
            bundle = serve_bundle(mesh, cfg, shape)
        # REPRO_DONATE=1 (§Perf knob): donate params/opt-state buffers so the
        # updated trees alias the inputs — halves the peak for the
        # weight-dominated cells
        donate = (
            (0, 1)
            if os.environ.get("REPRO_DONATE") == "1"
            and bundle.static_name == "train_step"
            else ()
        )
        jitted = jax.jit(
            bundle.fn, out_shardings=bundle.out_shardings,
            donate_argnums=donate,
        )
        lowered = jitted.lower(*bundle.in_shapes)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    colls = collective_bytes(hlo)
    # trip-count-aware accounting: XLA's cost_analysis counts while bodies
    # once; the parser multiplies by scan trip counts (roofline/hlo_parse)
    from ..roofline.hlo_parse import analyze_hlo

    parsed = analyze_hlo(hlo)
    if hlo_dir:
        import gzip

        os.makedirs(hlo_dir, exist_ok=True)
        tag = f"{arch}__{shape_name}__{'mp' if multi_pod else 'sp'}"
        with gzip.open(os.path.join(hlo_dir, tag + ".hlo.gz"), "wt") as f:
            f.write(hlo)
    rec.update(
        status="ok",
        step=bundle.static_name,
        lower_s=round(t_lower, 2),
        compile_s=round(t_compile, 2),
        flops=cost.get("flops", 0.0),
        bytes_accessed=cost.get("bytes accessed", 0.0),
        memory=_mem_dict(mem),
        collectives=colls,
        parsed=parsed,
        hlo_lines=hlo.count("\n"),
    )
    return rec


def _mem_dict(mem) -> Dict:
    keys = (
        "generated_code_size_in_bytes", "argument_size_in_bytes",
        "output_size_in_bytes", "temp_size_in_bytes", "alias_size_in_bytes",
        "peak_memory_in_bytes",
    )
    out = {}
    for k in keys:
        v = getattr(mem, k, None)
        if v is not None:
            out[k] = int(v)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["pod", "multipod", "both"],
                    default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    cells = []
    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = {"pod": [False], "multipod": [True], "both": [False, True]}[
        args.mesh
    ]
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                cells.append((arch, shape, mp))

    for arch, shape, mp in cells:
        tag = f"{arch}__{shape}__{'mp' if mp else 'sp'}"
        path = os.path.join(args.out, tag + ".json")
        if os.path.exists(path):
            print(f"[skip-existing] {tag}")
            continue
        print(f"[dryrun] {tag} ...", flush=True)
        try:
            import signal

            def _alarm(sig, frm):
                raise TimeoutError(
                    f"cell exceeded {os.environ.get('DRYRUN_TIMEOUT', '1800')}s"
                )

            signal.signal(signal.SIGALRM, _alarm)
            signal.alarm(int(os.environ.get("DRYRUN_TIMEOUT", "1800")))
            try:
                rec = run_cell(
                    arch, shape, mp,
                    hlo_dir=os.path.join(args.out, "hlo"),
                )
            finally:
                signal.alarm(0)
        except Exception as e:
            rec = {
                "arch": arch, "shape": shape,
                "mesh": "2x16x16" if mp else "16x16",
                "status": "error",
                "error": f"{type(e).__name__}: {e}",
                "trace": traceback.format_exc()[-3000:],
            }
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
        print(f"[done] {tag}: {rec['status']}", flush=True)


if __name__ == "__main__":
    main()
