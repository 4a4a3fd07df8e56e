"""Main-path kernels at qwen3-8b's published widths compile for a TPU v5e.

Interpret mode never sees the chip's compiler, which refuses a kernel that
asks for more VMEM than it was compiled with.  These tests describe a
``v5e:2x2`` topology (no chip attached) and compile, for one of its chips,
the generated kernels the serving path dispatches at d_model 4096, 32 query
/ 8 KV heads of 128 and d_ff 12288:

* the matmul of every projection (q/o 4096->4096, k/v 4096->1024, gate/up
  4096->12288, down 12288->4096) at prefill M 512 and decode M 128, with
  the schedule ``codegen.tune_schedule`` picks;
* the derived backward GEMMs ``matmul.dA`` / ``matmul.dB`` of 4096->12288;
* the fused causal attention over 32 heads x 512 x 512 x 128;
* every schedule the beam search ranks for the down projection, in bf16
  and f32, since the search lowers each of them on the chip.

Each compile must emit the Mosaic kernel (``tpu_custom_call``).  The
topology is described inside a fixture, never at import: only one process
may hold the TPU library, and the suite runs under several workers.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro import codegen
from repro.core.enumerate import attention_spec, matmul_spec
from repro.grad import derived_specs
from repro.search import beam_search

D_MODEL, KV_DIM, D_FF = 4096, 8 * 128, 12288

PROJECTIONS = {
    "q_o": (D_MODEL, D_MODEL),
    "k_v": (D_MODEL, KV_DIM),
    "gate_up": (D_MODEL, D_FF),
    "down": (D_FF, D_MODEL),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile_for_chip(spec, schedule, one_chip, dtype=jnp.bfloat16) -> str:
    kern = codegen.compile_kernel(spec, schedule)
    args = [
        jax.ShapeDtypeStruct(
            tuple(spec.root().extents[i] for i in axes), dtype,
            sharding=one_chip,
        )
        for axes in spec.root().operands.values()
    ]
    return jax.jit(lambda *a: kern(*a)).lower(*args).compile().as_text()


def _tuned(spec, dtype=jnp.bfloat16):
    return codegen.tune_schedule(
        spec, dtype=np.dtype(dtype), use_default_cache=False
    )


@pytest.mark.parametrize("m", [512, 128])
@pytest.mark.parametrize("proj", sorted(PROJECTIONS))
def test_projection_matmul_compiles(one_chip, proj, m):
    k, n = PROJECTIONS[proj]
    spec = matmul_spec(m, k, n)
    assert "tpu_custom_call" in _compile_for_chip(spec, _tuned(spec), one_chip)


@pytest.mark.parametrize("wrt", ["A", "B"])
def test_derived_backward_matmul_compiles(one_chip, wrt):
    spec = derived_specs(matmul_spec(512, D_MODEL, D_FF))[wrt]
    assert spec.name == f"matmul.d{wrt}"
    assert "tpu_custom_call" in _compile_for_chip(spec, _tuned(spec), one_chip)


def test_fused_causal_attention_compiles(one_chip):
    spec = attention_spec(32, 512, 512, 128, causal=True)
    assert "tpu_custom_call" in _compile_for_chip(spec, _tuned(spec), one_chip)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_beam_ranked_schedules_compile(one_chip, dtype):
    spec = matmul_spec(512, D_FF, D_MODEL)
    ranked, _ = beam_search(
        spec, beam_width=4, topk=3, elem_bytes=np.dtype(dtype).itemsize
    )
    assert ranked
    for sc in ranked:
        assert sc.cost.fits_vmem
        hlo = _compile_for_chip(
            spec, sc.candidate.to_schedule(), one_chip, dtype
        )
        assert "tpu_custom_call" in hlo
