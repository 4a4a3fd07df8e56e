"""Public wrapper for the blocked matmul: generated kernel first.

Routing policy (see DESIGN.md): the schedule-driven generator
(``repro.codegen``) compiles the matmul's Schedule into a Pallas kernel;
block shapes come from the persistent autotune cache when available, else
``choose_matmul_blocks``.  The hand-written ``matmul_pallas`` is kept as
the verification baseline (``use_generated=False`` and the equivalence
tests in tests/test_codegen.py).  On non-TPU backends without
``interpret`` we fall back to ``lax.dot_general`` so the surrounding
program still lowers/compiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core.autotune import choose_matmul_blocks
from ...core.enumerate import matmul_spec
from ...ops import _use_pallas
from .matmul import matmul_pallas
from .ref import matmul_ref


def _generated_matmul(a, b, block_m, block_n, block_k, interpret):
    from ... import codegen

    m, k = a.shape
    _, n = b.shape
    spec = matmul_spec(m, k, n)  # extents: i=m, j=k, k=n
    if block_m is None:
        # No caller-pinned blocks: let the generator's tuner pick.  Its
        # VMEM budget accounts for the generated kernel's resident reduce
        # axis (choose_matmul_blocks budgets for the k-STREAMED hand-
        # written kernel, which would overflow VMEM here at large K).
        schedule = codegen.tune_schedule(spec, dtype=a.dtype)
    else:
        schedule = codegen.default_schedule(
            spec, {"i": block_m, "k": block_n, "j": block_k}
        )
    kern = codegen.cached_compile(spec, schedule, interpret=interpret)
    return kern(a, b)


@functools.partial(
    jax.jit,
    static_argnames=(
        "block_m", "block_n", "block_k", "interpret", "force_pallas",
        "use_generated",
    ),
)
def matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    block_m: int | None = None,
    block_n: int | None = None,
    block_k: int | None = None,
    interpret: bool = False,
    force_pallas: bool = False,
    use_generated: bool = True,
) -> jax.Array:
    m, k = a.shape
    _, n = b.shape
    use_pallas = force_pallas or interpret or _use_pallas()
    if not use_pallas:
        return matmul_ref(a, b)
    if use_generated and block_m is None and block_n is None and block_k is None:
        return _generated_matmul(a, b, None, None, None, interpret)
    if block_m is None or block_n is None or block_k is None:
        bm, bn, bk = choose_matmul_blocks(
            m, n, k, elem_bytes=a.dtype.itemsize
        )
        block_m, block_n, block_k = (
            block_m or bm, block_n or bn, block_k or bk
        )
    if use_generated:
        return _generated_matmul(
            a, b, block_m, block_n, block_k, interpret
        )
    return matmul_pallas(
        a, b,
        block_m=block_m, block_n=block_n, block_k=block_k,
        interpret=interpret,
    )
