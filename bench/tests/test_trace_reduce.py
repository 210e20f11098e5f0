"""The reduction from trace events to per-layer numbers, on hand-made
events whose answers are known."""
import json

import pytest

from bench import trace_reduce
from bench.trace_reduce import Op
from conftest import BENCH

PEAKS = json.loads((BENCH / "peaks.json").read_text())["devices"][
    "TPU v5 lite"]

HLO = ('%custom-call.3 = (f32[8,128]{1,0}, f32[4]{0}) custom-call('
       'f32[4]{0} %p0, f32[3,1024,256]{2,1,0} %p1, f32[3,1,256]{2,1,0} %p2, '
       'f32[8,128]{1,0} %p3, f32[8,128]{1,0} %p4, f32[8,128]{1,0} %p5), '
       'custom_call_target="tpu_custom_call", '
       'metadata={op_name="jit(margin_ls_pallas)/pallas_call" '
       'source_file="/x/src/repro/kernels/superstep_tile.py" '
       'source_line=243}')


def test_kernel_module_and_operands_from_hlo_text():
    assert trace_reduce.kernel_module(HLO) == "superstep_tile"
    assert trace_reduce.parse_operands(HLO) == [
        ("f32", (4,)), ("f32", (3, 1024, 256)), ("f32", (3, 1, 256)),
        ("f32", (8, 128)), ("f32", (8, 128)), ("f32", (8, 128))]
    assert trace_reduce.kernel_module("%fusion.2 = f32[4] fusion(...)") \
        is None


def test_reader_of_a_kernel_output_is_no_kernel():
    # a fusion that reads a Pallas call's output names the call among its
    # operands; only the call's own instruction is attributed
    reader = ("%fusion.9 = f32[8,128]{1,0} fusion(f32[8,128]{1,0} "
              "%get-tuple-element.2), kind=kLoop, calls=%fused.9 "
              "stats_gram_solve_pallas.1")
    assert trace_reduce._attribute("fusion.9", reader, None) == (None, None)
    assert trace_reduce._attribute("custom-call.3", HLO, None)[0] == \
        "superstep_tile"
    entry = ("superstep_tile", [("f32", (4,))])
    assert trace_reduce._attribute("margin_ls_pallas.1", "x", entry) == \
        entry


def test_busy_and_idle(small):
    # window [0, 10]; device 0: [1, 3], [2.5, 4] and [5, 6]; device 1: [0, 2]
    ops = [Op(0, "fusion.1", 1.0, 3.0), Op(0, "fusion.2", 5.0, 6.0),
           Op(0, "copy.7", 2.5, 4.0), Op(1, "fusion.1", 0.0, 2.0)]
    spans = [("bench/fit_path", 0.0, 10.0),
             ("solver/superstep", 4.5, 5.5)]
    out = trace_reduce.reduce_events(ops, spans, small, peaks=PEAKS)
    assert out.window_s == 10.0
    assert out.busy_by_device == {0: 4.0, 1: 2.0}
    assert out.busy_s == 3.0
    # idle gaps of the fullest device, each named by the innermost span
    # around its middle: [0,1] and [6,10] in the path driver, [4,5] in the
    # superstep's dispatch
    assert out.idle_by_span == {"bench/fit_path": pytest.approx(5.0),
                                "solver/superstep": pytest.approx(1.0)}
    top = out.breakdown()
    assert top["device_ops"][0] == ["fusion", pytest.approx(5.0)]
    assert top["idle_gaps"][0][0] == "bench/fit_path"


def test_roofline_of_a_kernel_call(small):
    # one margin_ls call of 2 ms over 3 tiles of 1024 x 256 and 4
    # candidate steps
    op = Op(0, "custom-call.3", 0.0, 0.002, kernel="superstep_tile",
            operands=trace_reduce.parse_operands(HLO))
    out = trace_reduce.reduce_events([op], [("bench/fit_path", 0, 1.0)],
                                     small, peaks=PEAKS)
    flops = 2 * 3 * 1024 * 256 + 7 * 4 * 1024
    nbytes = 4 * (3 * 1024 * 256 + 4 * 1024 + 3 * 256 + 2 * 4)
    assert out.kernel_s == pytest.approx(0.002)
    assert out.roofline_s == pytest.approx(
        max(flops / PEAKS["flops_bf16"], nbytes / PEAKS["hbm_bytes_per_s"]))


def test_instruction_names_resolve_within_their_program():
    # two programs share the instruction name custom-call.3; the program
    # running at the op's start (its ``XLA Modules`` event) decides
    hlo = {("jit_counted", "custom-call.3"): ("superstep_tile", []),
           ("jit_grad", "custom-call.3"): ("glm_stats", [])}
    modules = [(0, 10, "jit_counted(7)"), (20, 30, "jit_grad(9)")]
    at = trace_reduce._program_at
    assert at(modules, 5) == "jit_counted(7)"
    assert at(modules, 25) == "jit_grad(9)"
    assert at(modules, 15) == ""
    pick = trace_reduce._in_program
    assert pick(hlo, "jit_grad(9)", "custom-call.3")[0] == "glm_stats"
    assert pick(hlo, "jit_counted(7)", "custom-call.3")[0] == \
        "superstep_tile"
    assert pick(hlo, "", "custom-call.3") is None
    assert pick(hlo, "", "fusion.1") is None
