"""The readers of the outer loop's and the λ-path loop's idle time, on
hand-made device ops and program spans whose answers are known."""
import json

import pytest

from bench import harness, trace_reduce
from bench.trace_reduce import Op
from conftest import BENCH

PEAKS = json.loads((BENCH / "peaks.json").read_text())["devices"][
    "TPU v5 lite"]


def _layer(reg, name, reduced, counters):
    ctx = harness.LayerContext({}, 1, None, counters, {}, reduced, PEAKS)
    return reg.module("metrics", name).read(ctx)


def test_idle_readers_split_turnaround_from_the_lambda_path(small):
    # device 0 busy over [0, 20] but for six gaps; each gap's middle lies
    # in one innermost program span
    busy = [(0, 1), (2, 4), (5, 8), (9, 10), (10.5, 12), (14, 15), (16, 20)]
    ops = [Op(0, "fusion.1", a, b) for a, b in busy]
    spans = [("bench/fit_path", 0.0, 20.0), ("solver/path", 0.1, 19.9),
             ("solver/lambda", 0.2, 19.8),
             ("solver/screen", 1.0, 2.0),         # gap [1, 2]
             ("solver/run", 2.0, 12.0),           # gap [10, 10.5]
             ("solver/superstep", 2.0, 5.0),
             ("solver/sync", 3.0, 5.0),           # gap [4, 5]
             ("solver/superstep", 5.0, 9.5),      # gap [8, 9]
             ("solver/sync", 6.0, 8.0),
             ("solver/kkt", 12.0, 14.5)]          # gap [12, 14]
    # and gap [15, 16] in the λ's own time
    out = trace_reduce.reduce_events(ops, spans, small, peaks=PEAKS)
    counters = {"supersteps": 2, "lambdas": 1, "kkt_rounds": 1, "paths": 1}
    outer = _layer(small, "outer.idle_ms_per_superstep", out, counters)
    path = _layer(small, "path.idle_ms_per_lambda", out, counters)
    assert outer == pytest.approx(1e3 * (1.0 + 1.0 + 0.5) / 2)
    assert path == pytest.approx(1e3 * (1.0 + 2.0 + 1.0))
    # the two layers hold every idle gap once
    assert outer * 2 + path * 1 == pytest.approx(
        1e3 * (20.0 - out.busy_s))
    assert _layer(small, "path.kkt_rounds_per_lambda", out,
                  dict(counters, kkt_rounds=3)) == 3.0


def test_idle_readers_read_nothing_without_a_trace_or_counters(small):
    counters = {"supersteps": 2, "lambdas": 1, "kkt_rounds": 1, "paths": 1}
    empty = trace_reduce.reduce_events([], [], small, peaks=PEAKS)
    for name in ("outer.idle_ms_per_superstep", "path.idle_ms_per_lambda"):
        assert _layer(small, name, empty, counters) is None
    # a program without the λ-path loop's counters (and spans)
    busy = trace_reduce.reduce_events(
        [Op(0, "fusion.1", 1.0, 2.0)],
        [("bench/fit_path", 0.0, 3.0), ("solver/superstep", 2.0, 2.5)],
        small, peaks=PEAKS)
    old = {"supersteps": 2, "sweep_tile_launches": 2, "paths": 1}
    for name in ("outer.idle_ms_per_superstep", "path.idle_ms_per_lambda",
                 "path.kkt_rounds_per_lambda"):
        assert _layer(small, name, busy, old) is None
    assert _layer(small, "path.kkt_rounds_per_lambda", busy,
                  dict(counters, lambdas=0)) is None
