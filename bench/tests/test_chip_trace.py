"""The reduction on a profiler trace recorded on one TPU v5e: one λ-path of
the dense cell at test size, with the compiled programs' HLO and the
session's counters over the traced path (``bench/tests/v5e_trace/``).

On a TPU an ``XLA Ops`` event is named by its instruction's whole text and
carries no program name, so the reduction finds the program by the
``XLA Modules`` event running at its start."""
import json

import pytest

from bench import trace_reduce
from conftest import BENCH

FIXTURE = BENCH / "tests" / "v5e_trace"
PEAKS = json.loads((BENCH / "peaks.json").read_text())["devices"][
    "TPU v5 lite"]


def programs():
    return [p.read_text() for p in sorted(FIXTURE.glob("*.hlo.txt"))]


def test_pallas_calls_attributed_by_program_and_by_name():
    hlo = {}
    for text in programs():
        hlo.update(trace_reduce.hlo_kernels(text))
    ops, spans = trace_reduce.load_events(FIXTURE, hlo)
    kernels = {(o.name, o.kernel) for o in ops if o.kernel}
    assert {k for _, k in kernels} == {"glm_stats", "superstep_tile"}
    # the instruction name alone attributes every call the same way
    bare, _ = trace_reduce.load_events(FIXTURE, {})
    assert {(o.name, o.kernel) for o in bare if o.kernel} == kernels
    # each call's operands are those its program states
    assert all(o.operands for o in ops if o.kernel)
    assert any(n == "bench/fit_path" for n, _, _ in spans)
    assert any(n == "solver/superstep" for n, _, _ in spans)


def test_reduced_numbers(small):
    counters = json.loads((FIXTURE / "counters.json").read_text())
    out = trace_reduce.reduce_dir(FIXTURE, small, counters=counters,
                                  peaks=PEAKS, programs=programs())
    assert 0 < out.busy_s <= out.window_s
    assert 0 < out.kernel_s <= out.busy_s
    # a roofline share is a share: above 0 and not over the whole
    assert 0 < out.roofline_s < out.kernel_s
    top = out.breakdown()
    assert top["device_ops"][0][0].startswith("kernel superstep_tile/")
    assert top["idle_gaps"] and all(s > 0 for _, s in top["idle_gaps"])
