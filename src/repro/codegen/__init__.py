"""repro.codegen — schedule-driven Pallas kernel generation.

The paper's claim is that HoF rewrite rules can distribute a contraction
"over the entire hierarchy of modern hardware".  This package makes the
claim executable for *any* ``ContractionSpec``: a ``Schedule`` (the tier
assignment produced by enumeration + the cost model, ``core.schedule``)
is compiled into a runnable JAX/Pallas kernel instead of being pattern-
matched against a fixed set of hand-written kernels.

Tier -> Pallas mapping (see ``plan.py`` for the derivation):

  =============  ==========================================================
  Schedule tier  Generated realization
  =============  ==========================================================
  ``mesh:*``     ``shard_map`` over the named mesh axis; operand
                 PartitionSpecs from ``Schedule.mesh_axes_for``; reduce
                 indices sharded on a mesh axis get a ``lax.psum`` epilogue
  ``grid``       one parallel Pallas grid dimension per level; BlockSpec
                 index maps route block ``program_id`` to the operand axes
                 (block shapes folded from ``Schedule.block_shape_for``)
  ``seq``        in-kernel ``lax.fori_loop`` over reduction chunks,
                 accumulating into a float32 VMEM scratch tile
  ``mxu``        the innermost tile, contracted with ``lax.dot_general``
                 (f32 ``preferred_element_type``) so the MXU sees a matmul
  =============  ==========================================================

Everything runs (and is tested) on CPU via Pallas interpreter mode.
``tune.py`` chooses schedules with the analytic cost model and persists
winners in a disk-backed cache (``cache.py``) keyed by
spec+shapes+dtype+hardware, so tuning cost is paid once per fleet.

Entry point::

    from repro import codegen
    kernel = codegen.compile(spec, schedule, interpret=True)
    out = kernel(A, B)                      # matches jnp.einsum
"""

from .cache import AutotuneCache, cache_key, default_cache, hardware_fingerprint
from .collectives import (
    all_reduce,
    naive_gather_matmul,
    ring_gather_matmul,
    ring_psum,
)
from .epilogue import Epilogue
from .mesh_gen import (
    MeshBoundKernel,
    bind_mesh,
    operand_partition_spec,
    output_partition_spec,
)
from .pallas_gen import (
    CompiledKernel,
    cached_compile,
    compile_kernel,
    compiled_kernels,
)
from .plan import KernelPlan, build_plan
from .schedules import (
    batched_matmul_schedule,
    chain_matmul_schedule,
    default_schedule,
    transposed_matmul_schedule,
)
from .tune import tune_schedule

#: public name per the design doc: ``codegen.compile(spec, schedule)``.
compile = compile_kernel

__all__ = [
    "AutotuneCache",
    "CompiledKernel",
    "Epilogue",
    "KernelPlan",
    "MeshBoundKernel",
    "all_reduce",
    "batched_matmul_schedule",
    "bind_mesh",
    "build_plan",
    "cache_key",
    "cached_compile",
    "chain_matmul_schedule",
    "compile",
    "compile_kernel",
    "compiled_kernels",
    "default_cache",
    "default_schedule",
    "hardware_fingerprint",
    "naive_gather_matmul",
    "operand_partition_spec",
    "output_partition_spec",
    "ring_gather_matmul",
    "ring_psum",
    "transposed_matmul_schedule",
    "tune_schedule",
]
