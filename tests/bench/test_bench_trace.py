"""The reduction from a profiler trace to busy and idle time, per-op time
and GEMM device time."""

from __future__ import annotations

import os

import pytest

from perfbench import xtrace
from perfbench.xtrace import Device, Event

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

WHILE = ("%while = (s32[], bf16[8,64]) while((s32[], bf16[8,64]) %t), "
         "condition=%c, body=%b")
DOT = "%fusion.16 = bf16[8,64]{1,0} fusion(bf16[8,64] %a), kind=kOutput"
GATHER = "%fusion.3 = bf16[8,64]{1,0} fusion(bf16[8,64] %a), kind=kLoop"
KERNEL = ('%fn.1 = bf16[8,64]{1,0} custom-call(bf16[8,64] %x), '
          'custom_call_target="tpu_custom_call"')


def _device():
    """Two executions of jit_step and one of jit_run over [0, 100) ns:
    step at [10, 30) with a while [12, 28) holding two dots and a gather,
    run at [40, 50) with a kernel, step again at [60, 70)."""
    modules = [Event("jit_step(11)", 10, 20), Event("jit_run(12)", 40, 10),
               Event("jit_step(11)", 60, 10)]
    ops = [Event(WHILE, 12, 16), Event(DOT, 12, 5), Event(GATHER, 17, 3),
           Event(DOT, 20, 8), Event(KERNEL, 40, 10), Event(DOT, 60, 6),
           Event(GATHER, 66, 2)]
    return Device("/device:TPU:0", modules, ops)


def test_busy_is_the_union_of_op_intervals_nesting_counted_once():
    d = _device()
    # [12, 28) + [40, 50) + [60, 68)
    assert xtrace.busy_ns(d, 0, 100) == 16 + 10 + 8
    assert xtrace.busy_ns(d, 20, 45) == 8 + 5


def test_idle_gaps_cover_the_rest_of_the_window():
    d = _device()
    gaps = xtrace.idle_gaps(d, 0, 100)
    assert gaps == [(0, 12), (28, 40), (50, 60), (68, 100)]
    assert sum(t - s for s, t in gaps) + xtrace.busy_ns(d, 0, 100) == 100


def test_op_seconds_by_module_without_containers():
    d = _device()
    top = dict(xtrace.op_seconds(d, 0, 100))
    assert top == pytest.approx({"jit_step/fusion.16": 19e-9,
                                 "jit_step/fusion.3": 5e-9,
                                 "jit_run/fn.1": 10e-9})


def test_module_time_and_named_ops_stay_inside_the_module():
    d = _device()
    assert len(xtrace.module_runs(d, "jit_step", 0, 100)) == 2
    assert xtrace.module_busy_ns(d, "jit_step", 0, 100) == 16 + 8
    assert xtrace.ops_ns(d, "jit_step", {"fusion.16", "fn.1"}, 0, 100) == {
        "fusion.16": 19}
    assert xtrace.ops_ns(d, "jit_run", {"fusion.16", "fn.1"}, 0, 100) == {
        "fn.1": 10}


def test_instruction_names_and_opcodes_from_event_text():
    assert xtrace.op_name(DOT) == "fusion.16"
    assert xtrace.opcode(DOT) == "fusion"
    assert xtrace.opcode(WHILE) == "while"
    assert xtrace.opcode(KERNEL) == "custom-call"
    assert xtrace.module_name("jit_step(3122197152331374719)") == "jit_step"


def test_gemm_instructions_of_a_compiled_tpu_module():
    """A v5e-compiled program: a generated kernel (x 128x512 @ w 512x512),
    then a scan of three 128x512 @ 512x512 dots (one output fusion holding
    the weight's dynamic slice and the convolution), then a 128x512 @
    (128x512)^T product that is no weight GEMM."""
    with open(os.path.join(DATA, "scan_dots.hlo.txt")) as f:
        hlo = f.read()
    assert xtrace.gemm_instructions(hlo, [(128, 512, 512)]) == {
        "fn.1", "fusion.16"}
    assert xtrace.gemm_instructions(hlo, [(128, 512, 1024)]) == set()


def test_recorded_tpu_trace():
    """The trace of that program, run five times on one v5e."""
    path = os.path.join(DATA, "scan_dots.xplane.pb")
    from jax.profiler import ProfileData

    tr = xtrace.from_profile(ProfileData.from_file(path))
    assert tr.start_wall_ns > 0
    (d,) = tr.devices
    mods = xtrace.module_runs(d, "jit_f", 0, float("inf"))
    assert len(mods) == 5
    lo, hi = mods[0].start_ns, mods[-1].end_ns
    busy = xtrace.busy_ns(d, lo, hi)
    assert 0 < busy < hi - lo
    with open(os.path.join(DATA, "scan_dots.hlo.txt")) as f:
        gemms = xtrace.gemm_instructions(f.read(), [(128, 512, 512)])
    per_op = xtrace.ops_ns(d, "jit_f", gemms, lo, hi)
    assert set(per_op) == {"fn.1", "fusion.16"}
    # per run: one kernel and three scanned dots, all inside the busy time
    gemm = sum(per_op.values())
    assert 0 < gemm < xtrace.module_busy_ns(d, "jit_f", lo, hi) <= busy
    n = sum(1 for _, e in xtrace._in_module(d, "jit_f", lo, hi)
            if xtrace.op_name(e.name) in gemms)
    assert n == 5 * 4


MULTI_OUTPUT_HLO = """\
HloModule jit_step, is_scheduled=true

%fused_computation.7 (param_0.1: bf16[64,256], param_1.1: bf16[8,64]) -> (f32[8], s32[8]) {
  %param_1.1 = bf16[8,64]{1,0:T(8,128)(2,1)} parameter(1)
  %param_0.1 = bf16[64,256]{1,0:T(8,128)(2,1)} parameter(0)
  %convolution.3 = f32[8,256]{1,0:T(8,128)} convolution(%param_1.1, %param_0.1), dim_labels=bf_io->bf
  %iota.1 = s32[8,256]{1,0:T(8,128)} iota(), iota_dimension=1
  ROOT %reduce.1 = (f32[8]{0:T(128)}, s32[8]{0:T(128)}) reduce(%convolution.3, %iota.1), dimensions={1}
}

ENTRY %main.2 (w.1: bf16[64,256], x.1: bf16[8,64]) -> (f32[8], s32[8]) {
  %w.1 = bf16[64,256]{1,0:T(8,128)(2,1)} parameter(0)
  %x.1 = bf16[8,64]{1,0:T(8,128)(2,1)} parameter(1)
  ROOT %convolution_reduce_fusion = (f32[8]{0:T(128)}, s32[8]{0:T(128)}) fusion(%w.1, %x.1), kind=kOutput, calls=%fused_computation.7
}
"""


def test_a_multi_output_fusion_holding_the_lm_head_is_a_gemm():
    """An LM head fused with the argmax over its logits returns a tuple;
    the op that runs is the fusion, not the convolution inside it."""
    assert xtrace.gemm_instructions(MULTI_OUTPUT_HLO, [(8, 64, 256)]) == {
        "convolution_reduce_fusion"}
