"""Autotuning: enumerate variants, early-cut with the cost model, pick one.

This is the paper's §4 pipeline made automatic:
  1. enumerate HoF orderings (SJT) and subdivision factors,
  2. rank with the analytic cost model (the early-cut rule the paper's
     Future Work calls for),
  3. (optionally) measure the survivors,
  4. emit the winner as a Schedule for ops/kernels.
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .cost import TPU, cpu_cost, pipeline_vmem_bytes, rank_variants, tpu_cost
from .enumerate import ContractionSpec, variant_orders
from .execute import execute_variant


@dataclasses.dataclass
class TunedVariant:
    order: Tuple[str, ...]
    spec: ContractionSpec
    predicted_cost: float
    measured_s: Optional[float] = None


def enumerate_subdivided(
    spec: ContractionSpec,
    subdiv_candidates: Dict[str, Sequence[int]],
) -> List[ContractionSpec]:
    """spec plus every single- and double-index subdivision combination."""
    specs = [spec]
    idxs = list(subdiv_candidates)
    for i, idx in enumerate(idxs):
        for b in subdiv_candidates[idx]:
            if spec.extents[idx] % b:
                continue
            s1 = spec.subdivide(idx, b)
            specs.append(s1)
            for idx2 in idxs[i + 1 :]:
                for b2 in subdiv_candidates[idx2]:
                    if s1.extents[idx2] % b2:
                        continue
                    specs.append(s1.subdivide(idx2, b2))
    return specs


def _tune_cache_key(spec, subdiv_candidates, cost_fn, keep, measure_with):
    """NB: cost_fn is identified by module+qualname — pass a NAMED function
    when caching; two lambdas defined at the same spot would collide."""
    from ..codegen.cache import cache_key

    return cache_key(
        spec,
        extra={
            "what": "tune.variants",
            "subdiv": {
                k: sorted(int(b) for b in v)
                for k, v in (subdiv_candidates or {}).items()
            },
            "cost_fn": (
                getattr(cost_fn, "__module__", "")
                + ":"
                + getattr(
                    cost_fn, "__qualname__",
                    getattr(cost_fn, "__name__", repr(cost_fn)),
                )
            ),
            "keep": keep,
            "measured": measure_with is not None
            and {
                k: [list(np.shape(a)), str(np.asarray(a).dtype)]
                for k, a in measure_with.items()
            },
        },
    )


def _variants_to_json(survivors: List[TunedVariant]) -> list:
    return [
        {
            "order": list(tv.order),
            "splits": [[i, int(b)] for i, b in tv.spec.split_chain()],
            "predicted": float(tv.predicted_cost),
            "measured": tv.measured_s,
        }
        for tv in survivors
    ]


def _variants_from_json(data: list, root: ContractionSpec) -> List[TunedVariant]:
    out = []
    for d in data:
        s = root.root()
        for index, b in d["splits"]:
            s = s.subdivide(index, b)
        out.append(
            TunedVariant(
                tuple(d["order"]), s, d["predicted"], d.get("measured")
            )
        )
    return out


def tune(
    spec: ContractionSpec,
    subdiv_candidates: Optional[Dict[str, Sequence[int]]] = None,
    cost_fn: Callable = cpu_cost,
    keep: int = 4,
    measure_with: Optional[Dict[str, np.ndarray]] = None,
    repeats: int = 3,
    cache=None,
) -> List[TunedVariant]:
    """Full enumerate -> cut -> (measure) pipeline; best variant first.

    ``cache`` (a ``codegen.cache.AutotuneCache``) persists the survivor
    list keyed by spec + subdiv candidates + cost model + measurement
    shapes: a repeated call — in this process or any later one — returns
    the stored ranking without re-enumerating or re-measuring.
    """
    if cache is not None:
        key = _tune_cache_key(spec, subdiv_candidates, cost_fn, keep, measure_with)
        hit = cache.get(key)
        if hit is not None:
            return _variants_from_json(hit, spec)
    specs = (
        enumerate_subdivided(spec, subdiv_candidates)
        if subdiv_candidates
        else [spec]
    )
    pool: List[TunedVariant] = []
    for s in specs:
        for cost, order in rank_variants(s, variant_orders(s), cost_fn):
            pool.append(TunedVariant(order, s, cost))
    pool.sort(key=lambda tv: tv.predicted_cost)
    survivors = pool[:keep]
    if measure_with is not None:
        for tv in survivors:
            best = math.inf
            for _ in range(repeats):
                t0 = time.perf_counter()
                execute_variant(tv.spec, tv.order, measure_with)
                best = min(best, time.perf_counter() - t0)
            tv.measured_s = best
        survivors.sort(key=lambda tv: tv.measured_s)
    if cache is not None:
        cache.put(key, _variants_to_json(survivors))
    return survivors


# ---------------------------------------------------------------------------
# TPU block-shape selection for the Pallas matmul
# ---------------------------------------------------------------------------


def choose_matmul_blocks(
    m: int,
    n: int,
    k: int,
    elem_bytes: int = 2,
    hw: dict = TPU,
) -> Tuple[int, int, int]:
    """(block_m, block_n, block_k) minimizing HBM traffic under VMEM.

    Napkin model (the TPU analogue of the paper's cache reasoning):
      traffic = M*K * (N/bn)  +  K*N * (M/bm)  +  M*N
    so we maximize bm, bn subject to the k-streamed kernel's footprint —
    double-buffered (bm, bk), (bk, bn) and (bm, bn) blocks plus the f32
    (bm, bn) accumulator (``cost.pipeline_vmem_bytes``) — fitting VMEM,
    with every extent a multiple of the MXU tile where possible.
    """

    def fits(bm: int, bn: int, bk: int) -> bool:
        return pipeline_vmem_bytes(
            [bm * bk * elem_bytes, bk * bn * elem_bytes],
            [bm * bn * elem_bytes],
            bm * bn * 4,
        ) <= hw["vmem_bytes"]

    def aligned(align: int, size: int, cap: int = 1024) -> List[int]:
        """Divisors of ``size`` that are pow2 multiples of ``align``."""
        outs, c = [], align
        while c <= min(size, cap):
            if size % c == 0:
                outs.append(c)
            c *= 2
        return outs or [size]

    best, best_score = None, None
    for bm in aligned(8, m):
        for bn in aligned(128, n):
            for bk in aligned(128, k):
                if not fits(bm, bn, bk):
                    continue
                traffic = m * k * (n / bn) + k * n * (m / bm) + m * n
                # prefer deeper k-blocks on ties (fewer grid steps)
                score = (traffic, -bk, -(bm * bn))
                if best is None or score < best_score:
                    best, best_score = (bm, bn, bk), score
    if best is None:  # tiny problem: single block
        best = (m, n, k)
    return best
