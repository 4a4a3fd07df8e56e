"""Analytic cost models for HoF-nest variants — the paper's missing early-cut.

The paper enumerates variants and *measures* them all; its Future Work notes
an early-cut rule is needed for this to scale.  We implement two flavours:

* ``cpu_cost``  — a hierarchical cache-traffic model (classic reuse-level /
  working-set analysis) used to rank the paper's Table-1/2 permutations
  without running them;
* ``tpu_cost``  — a VMEM/HBM/MXU roofline flavour used to pick Pallas block
  shapes and loop orders for the kernels, with explicit penalties for
  MXU-misaligned innermost extents (multiples of (8, 128) wanted).

Both consume a ``ContractionSpec`` + loop order, i.e. they work on the same
objects the rewrite rules produce, so "enumerate -> cut -> lower" is a single
pipeline (see autotune.py).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Sequence, Tuple

from .enumerate import ContractionSpec


@dataclasses.dataclass(frozen=True)
class CacheLevel:
    name: str
    capacity: int  # elements (we model in elements, not bytes)
    miss_cost: float  # relative cost per line fetched from beyond this level


#: a Core-i5-7300HQ-ish hierarchy, in 8-byte elements
CPU_HIERARCHY = (
    CacheLevel("L1", 32 * 1024 // 8, 1.0),
    CacheLevel("L2", 256 * 1024 // 8, 4.0),
    CacheLevel("L3", 3 * 1024 * 1024 // 8, 20.0),
    CacheLevel("DRAM", 1 << 62, 120.0),
)

LINE_ELEMS = 8  # 64-byte lines of float64


def _operand_views(spec: ContractionSpec) -> Dict[str, Tuple[str, ...]]:
    """Operands plus the output array 'OUT' (store traffic counts too)."""
    views = dict(spec.operands)
    views["OUT"] = spec.output
    return views


def _footprint(
    axes: Tuple[str, ...], resident: set, extents: Dict[str, int]
) -> int:
    return math.prod(extents[a] for a in axes if a in resident) or 1


def _lines(
    name: str,
    axes: Tuple[str, ...],
    resident: set,
    extents: Dict[str, int],
    canonical: Dict[str, Tuple[str, ...]],
    line: int,
) -> float:
    """Footprint in cache lines: contiguous innermost axis amortizes fetches."""
    fp = _footprint(axes, resident, extents)
    if not axes:
        return 1.0
    inner = canonical[name][-1]  # stride-1 axis in canonical storage
    if inner in resident:
        inner_e = min(extents[inner], fp)
        return fp / min(line, inner_e)
    return float(fp)


def cpu_cost(
    spec: ContractionSpec,
    order: Sequence[str],
    hierarchy: Sequence[CacheLevel] = CPU_HIERARCHY,
    line: int = LINE_ELEMS,
) -> float:
    """Total weighted line traffic across the cache hierarchy."""
    views = _operand_views(spec)
    canonical = dict(views)
    extents = spec.extents
    depth = {idx: k for k, idx in enumerate(order)}
    total = 0.0
    for lvl in hierarchy:
        # deepest loop level t such that the working set below t fits
        best_t = len(order)  # innermost only
        for t in range(len(order) + 1):
            resident = set(order[t:])
            ws = sum(
                _footprint(axes, resident, extents) for axes in views.values()
            )
            if ws <= lvl.capacity:
                best_t = t
                break
        resident = set(order[best_t:])
        miss_lines = 0.0
        for name, axes in views.items():
            trips = math.prod(
                extents[i]
                for i in order[:best_t]
                if i in axes
            ) or 1
            miss_lines += trips * _lines(
                name, axes, resident, extents, canonical, line
            )
        total += miss_lines * lvl.miss_cost
    return total


def rank_variants(
    spec: ContractionSpec,
    orders: Sequence[Sequence[str]],
    cost_fn=cpu_cost,
) -> List[Tuple[float, Tuple[str, ...]]]:
    scored = sorted(
        (cost_fn(spec, tuple(o)), tuple(o)) for o in orders
    )
    return scored


def early_cut(
    spec: ContractionSpec,
    orders: Sequence[Sequence[str]],
    keep: int = 4,
    cost_fn=cpu_cost,
) -> List[Tuple[str, ...]]:
    """The paper's future-work pruning rule: keep only the cheapest variants."""
    return [o for _, o in rank_variants(spec, orders, cost_fn)[:keep]]


# ---------------------------------------------------------------------------
# TPU flavour
# ---------------------------------------------------------------------------

#: Scoped VMEM (bytes) every Pallas kernel of this repo is compiled with
#: (``vmem_limit_bytes``), and the budget ``kernel_vmem_bytes`` holds every
#: block choice to (``codegen.tune``, ``search.beam``,
#: ``core.autotune.choose_matmul_blocks``).  A TPU v5e core has 128 MiB of
#: VMEM; without the explicit limit Mosaic allows a kernel only its 16 MiB
#: default, and refuses the blocks the selectors picked.
VMEM_LIMIT_BYTES = 64 * 1024 * 1024

#: v5e-like hardware model (see DESIGN.md §6)
TPU = dict(
    peak_flops=197e12,  # bf16
    hbm_bw=819e9,
    vmem_bytes=VMEM_LIMIT_BYTES,
    ici_bw=50e9,  # per link
    mxu=(128, 128),
    sublane=8,
)


def pipeline_vmem_bytes(in_blocks, out_blocks, scratch: int = 0) -> int:
    """VMEM a Pallas TPU kernel holds, in bytes.

    The pipeline double-buffers every input and output block (``in_blocks``
    / ``out_blocks``, bytes each) so the next block's DMA overlaps this
    step's compute; ``scratch`` (accumulators and the body's temporaries)
    is held once.
    """
    return 2 * (sum(in_blocks) + sum(out_blocks)) + scratch


def kernel_vmem_bytes(
    spec: ContractionSpec,
    blocks: Dict[str, int],
    elem_bytes: int,
    *,
    extents: Dict[str, int] = None,
    out_bytes: int = None,
) -> int:
    """VMEM of the generated kernel for ``spec`` under per-index ``blocks``.

    ``blocks`` maps each root index to its grid block (map indices) or its
    per-step chunk (reduce indices); omitted indices are whole.
    ``extents`` are the per-shard extents (default: the root's).  Operands
    are stored at ``elem_bytes``, the output at ``out_bytes`` (default: the
    operands', or the 4-byte accumulator of a quantized spec).  Mirrors the
    layouts ``codegen.pallas_gen`` and ``codegen.fused_gen`` build:

    * contraction: an operand block holds its map indices at their block
      and its reduce indices at full extent (the reduction is resident and
      the body loops over chunks); scratch is the f32 accumulator, the
      step's dot result and one f32 chunk of every operand;
    * attention: Q, K and V blocks stream the KV axis through the grid;
      scratch is the running max/sum, the accumulator, the score and
      probability tiles and the f32 copies of the blocks;
    * grouped matmul: the whole row set stays resident per column block.
    """
    f32 = 4
    spec = spec.root()
    ext = dict(extents or spec.extents)
    if out_bytes is None:
        out_bytes = f32 if getattr(spec, "quant", None) else elem_bytes
    b = {i: int(blocks.get(i, ext[i])) for i in spec.indices}
    kind = getattr(spec, "fused_kind", "")
    if kind == "attention":
        bh, bs, bt = b["h"], b["s"], b["t"]
        ins = [bh * bs * ext["d"], bh * bt * ext["d"], bh * bt * ext["e"]]
        out = bh * bs * ext["e"]
        scratch = 2 * bh * bs + out + 2 * bh * bs * bt + sum(ins)
        return pipeline_vmem_bytes(
            [n * elem_bytes for n in ins], [out * out_bytes], scratch * f32
        )
    if kind == "grouped_matmul":
        names = tuple(spec.operands)
        if "g" in spec.output:  # dW: (n, o1) x (n, o2) -> (g, o1, o2)
            _, o1, o2 = spec.output
            n = ext[spec.operands[names[0]][0]]
            ins = [n * ext[o1], n * b[o2]]
            out = ext[o1] * b[o2]
            scratch = sum(ins) + out
        else:  # row mode: x (n, c) x w (g, c, oc) -> (n, oc)
            n_ax, c_ax = spec.operands[names[0]]
            oc = spec.output[1]
            n, c = ext[n_ax], ext[c_ax]
            ins = [n * c, c * b[oc]]
            out = n * b[oc]
            scratch = out + b[n_ax] * (c + b[oc])
        return pipeline_vmem_bytes(
            [m * elem_bytes for m in ins], [out * out_bytes], scratch * f32
        )
    ins, chunks = [], 0
    for axes in spec.operands.values():
        ins.append(math.prod(
            b[a] if a in spec.output else ext[a] for a in axes
        ))
        chunks += math.prod(b[a] for a in axes)
    out = math.prod(b[i] for i in spec.output)
    return pipeline_vmem_bytes(
        [n * elem_bytes for n in ins], [out * out_bytes],
        (2 * out + chunks) * f32,
    )


def tpu_cost(
    spec: ContractionSpec,
    order: Sequence[str],
    elem_bytes: int = 2,
    hw: dict = TPU,
) -> float:
    """Estimated step time (s): max(compute, HBM traffic) + alignment penalty.

    The resident set is the deepest loop suffix whose working set fits VMEM
    (the Pallas block); everything outside streams from HBM.
    """
    views = _operand_views(spec)
    extents = spec.extents
    cap = hw["vmem_bytes"] // elem_bytes
    best_t = len(order)
    for t in range(len(order) + 1):
        resident = set(order[t:])
        ws = sum(_footprint(a, resident, extents) for a in views.values())
        if ws <= cap:
            best_t = t
            break
    resident = set(order[best_t:])
    hbm_elems = 0.0
    for name, axes in views.items():
        trips = math.prod(e for i in order[:best_t] if i in axes for e in (extents[i],)) or 1
        hbm_elems += trips * _footprint(axes, resident, extents)
    hbm_time = hbm_elems * elem_bytes / hw["hbm_bw"]
    compute_time = spec.flops() / hw["peak_flops"]

    # alignment: the innermost map/rnz extents feed the MXU; penalize extents
    # that are not multiples of the (sublane, lane) tile.
    penalty = 1.0
    inner = [i for i in order[best_t:]]
    if inner:
        lane = extents[inner[-1]]
        if lane % hw["mxu"][1]:
            penalty *= 1.5
        if len(inner) >= 2 and extents[inner[-2]] % hw["sublane"]:
            penalty *= 1.2
    return max(compute_time, hbm_time) * penalty


def roofline_terms(
    flops: float,
    hbm_bytes: float,
    collective_bytes: float,
    chips: int,
    hw: dict = TPU,
) -> Dict[str, float]:
    """The three §Roofline terms, in seconds (see EXPERIMENTS.md)."""
    return dict(
        compute_s=flops / (chips * hw["peak_flops"]),
        memory_s=hbm_bytes / (chips * hw["hbm_bw"]),
        collective_s=collective_bytes / (chips * hw["ici_bw"]),
    )
