"""Schedule selection for generated kernels, with the persistent cache.

This generalizes ``core.autotune.choose_matmul_blocks`` from the one
hand-written matmul to any ContractionSpec: enumerate per-index block
candidates (pow2 divisors, MXU-flavoured), rank with the napkin HBM
traffic model under the VMEM budget, optionally measure the analytic
top-k in Pallas interpreter mode, and persist the winner keyed by
spec+shapes+dtype+hardware (``codegen.cache``).  A second process — or a
serving replica warming up — gets the tuned schedule back without paying
enumeration or measurement again.
"""

from __future__ import annotations

import itertools
import math
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core.cost import TPU, kernel_vmem_bytes
from ..core.enumerate import ContractionSpec
from ..core.schedule import Schedule
from .cache import (
    AutotuneCache,
    cache_key,
    default_cache,
    schedule_from_dict,
    schedule_to_dict,
)
from .schedules import default_schedule

#: bumped when candidate generation or scoring changes
TUNER_VERSION = 2


def _pow2_divisors(extent: int, cap: int = 512, limit: int = 6) -> List[int]:
    """Pow2 divisors of extent up to cap, largest first, plus extent itself."""
    out = [extent]
    c = 1
    while c <= min(extent, cap):
        if extent % c == 0 and c != extent:
            out.append(c)
        c *= 2
    out.sort(reverse=True)
    return out[:limit]


def _score(
    spec: ContractionSpec,
    blocks: Dict[str, int],
    elem_bytes: int,
    hw: dict,
) -> Optional[float]:
    """HBM traffic (elements) of the generated kernel, or None if its VMEM
    footprint (``core.cost.kernel_vmem_bytes``) exceeds the budget.

    Each operand is re-fetched once per grid block of every *output* index
    it does not carry.
    """
    if kernel_vmem_bytes(spec, blocks, elem_bytes) > hw["vmem_bytes"]:
        return None
    extents = spec.extents
    n_blocks = {
        i: extents[i] // blocks[i] for i in spec.output
    }
    traffic = 0.0
    for name, axes in spec.operands.items():
        elems = math.prod(extents[a] for a in axes)
        trips = math.prod(
            n_blocks[i] for i in spec.output if i not in axes
        )
        traffic += elems * trips
    traffic += math.prod(extents[i] for i in spec.output)
    # MXU alignment nudges: innermost output axis wants multiples of lanes
    penalty = 1.0
    last = spec.output[-1]
    if blocks[last] % hw["mxu"][1] and blocks[last] != extents[last]:
        penalty *= 1.25
    if len(spec.output) >= 2:
        sub = spec.output[-2]
        if blocks[sub] % hw["sublane"] and blocks[sub] != extents[sub]:
            penalty *= 1.1
    return traffic * penalty


def _reduce_chunk(extent: int, cap: int = 512) -> int:
    """Seq-loop chunk for a reduce axis: largest pow2 divisor <= cap.

    Reduce blocks don't change HBM traffic in the generated kernel (the
    axis is VMEM-resident either way), so they are not enumerated — one
    heuristic chunk bounds the per-dot depth; extent itself (no seq
    level) when it is small or has no pow2 divisor under the cap.
    """
    if extent <= cap:
        return extent
    best = 0
    c = 1
    while c <= cap:
        if extent % c == 0:
            best = c
        c *= 2
    return best or extent


def candidate_blocks(
    spec: ContractionSpec, hw: dict = TPU, per_index: int = 6
) -> List[Dict[str, int]]:
    """Cross-product of pow2 MAP-index block candidates; batch-like dims
    pinned near 1, reduce indices fixed to their heuristic chunk."""
    choices: List[Tuple[str, List[int]]] = []
    whole = getattr(spec.root(), "whole_indices", ())
    for i in spec.indices:
        e = spec.extents[i]
        if i in whole:
            cands = [e]  # fused families keep these axes unblocked
        elif i not in spec.output:
            cands = [_reduce_chunk(e)]
        elif e <= hw["sublane"]:
            cands = [1, e] if e > 1 else [1]  # batch-like tiny dims
        else:
            cands = _pow2_divisors(e, limit=per_index)
        choices.append((i, cands))
    out = []
    for combo in itertools.product(*(c for _, c in choices)):
        out.append({i: b for (i, _), b in zip(choices, combo)})
    return out


def tune_schedule(
    spec: ContractionSpec,
    *,
    dtype=np.float32,
    hw: dict = TPU,
    cache: Optional[AutotuneCache] = None,
    measure_with: Optional[Dict[str, np.ndarray]] = None,
    keep: int = 3,
    use_default_cache: bool = True,
) -> Schedule:
    """Pick (and persist) a Schedule for ``spec``.

    Cache hit -> deserialize, no enumeration, no measurement.  Miss ->
    analytic search; if ``measure_with`` provides operand arrays the
    analytic top-``keep`` are timed through the generated kernel (the
    Pallas interpreter off a TPU) before the winner is stored.  Raises
    ``ValueError`` when no candidate fits the VMEM budget.
    """
    from ..obs import span

    spec = spec.root()
    if cache is None and use_default_cache:
        cache = default_cache()
    elem = np.dtype(dtype).itemsize
    key = cache_key(
        spec,
        dtype=np.dtype(dtype),
        extra={
            "tuner": TUNER_VERSION,
            "keep": keep,
            # custom cost-model dicts must not hit the default's entries
            "hw": sorted(
                (k, v) for k, v in hw.items()
                if isinstance(v, (int, float))
            ),
            # an analytic-only winner must not satisfy a measured request
            "measured": measure_with is not None,
        },
    )
    if cache is not None:
        hit = cache.get(key)
        if hit is not None:
            return schedule_from_dict(hit["schedule"], spec)

    with span("codegen.tune", spec=spec.name,
              measured=measure_with is not None):
        scored = []
        for blocks in candidate_blocks(spec, hw):
            s = _score(spec, blocks, elem, hw)
            if s is not None:
                steps = sum(  # tie-break: fewer seq steps win
                    spec.extents[i] // blocks[i]
                    for i in spec.indices
                    if i not in spec.output
                )
                scored.append((s, steps, tuple(sorted(blocks.items()))))
        if not scored:
            raise ValueError(
                f"no block choice for {spec.name} {spec.extents} fits the "
                f"{hw['vmem_bytes']} B VMEM budget"
            )
        scored.sort()
        top = [dict(b) for _, _, b in scored[:keep]]

        best = top[0]
        if measure_with is not None and len(top) > 1:
            from ..ops import default_interpret
            from .pallas_gen import compile_kernel

            timings = []
            for blocks in top:
                sched = default_schedule(spec, blocks)
                kern = compile_kernel(
                    spec, sched, interpret=default_interpret()
                )
                args = tuple(measure_with[n] for n in spec.operands)
                t0 = time.perf_counter()
                np.asarray(kern(*args))
                timings.append((time.perf_counter() - t0, blocks))
            timings.sort(key=lambda t: t[0])
            best = timings[0][1]

    schedule = default_schedule(spec, best)
    if cache is not None:
        cache.put(
            key,
            {
                "schedule": schedule_to_dict(schedule),
                "blocks": {k: int(v) for k, v in best.items()},
                "measured": measure_with is not None,
            },
        )
    return schedule
