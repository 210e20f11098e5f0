"""The harness finds a new configuration, traffic mix, cell, per-layer
metric and kernel formula from files added to a copy of the benchmark's
directory alone, with no edit to harness code."""
import json
import os

import pytest

from bench import harness, trace_reduce
from conftest import BENCH, args, benchmark_json, small_registry

PEAKS = json.loads((BENCH / "peaks.json").read_text())["devices"][
    "TPU v5 lite"]


def added_files():
    config = json.loads((BENCH / "configs" / "epsilon_dense.json")
                        .read_text())
    config.update(name="fixture_dense")
    config["data"].update(rows=8000, features=300)
    config["solver"]["tile_size"] = 128
    config["path"] = {"n_lambdas": 3, "lam_ratio": 0.1}
    bench = benchmark_json()
    bench["configs"].append({"name": "fixture_dense", "source": "test",
                             "file": "bench/configs/fixture_dense.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "fixture_dense.short",
                               "config": "fixture_dense",
                               "traffic": "short", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({
        "name": "fixture.paths_traced", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "outer loop",
        "moves": "path_s", "workloads": ["fixture_dense.short"]})
    return {
        "BENCHMARK.json": bench,
        "configs/fixture_dense.json": config,
        "traffic/short.json": {"mesh": None, "warmup_lambdas": 1,
                               "grid_scale": [0.95, 1.05], "grid_points": 2,
                               "check_paths": 1, "trace_paths": 1},
        "workloads/fixture_dense.short.json": {
            "config": "fixture_dense", "traffic": "short", "chips": 1,
            "limits": {"kkt": 1.0, "objective_gap": 1e-4, "margin_gap": 1e-3,
                       "compiles_in_window": 0}},
        "metrics/fixture.paths_traced.py":
            "def read(ctx):\n    return ctx.counters['paths']\n",
        "kernels/fixture_kernel.py":
            "def cost(operands, ctx):\n    return 2.0e9, 8.19e8\n",
    }


def test_new_cell_and_metric_from_files_alone(tmp_path):
    reg = small_registry(tmp_path, extra=added_files())
    result, lines = harness.run_cell(args("fixture_dense.short", trace=1),
                                     reg=reg, require_tpu=False)
    assert result["correct"], lines
    assert result["metrics"]["fixture.paths_traced"]["value"] == 1.0
    # the metric is declared for the new cell only
    other, _ = harness.run_cell(args("epsilon_dense.path", trace=1),
                                reg=reg, require_tpu=False)
    assert "fixture.paths_traced" not in other["metrics"]


def test_new_kernel_formula_from_its_file_alone(tmp_path):
    reg = small_registry(tmp_path, extra=added_files())
    op = trace_reduce.Op(0, "custom-call.7", 0.2, 0.7,
                         kernel="fixture_kernel", operands=[])
    out = trace_reduce.reduce_events(
        [op], [("bench/fit_path", 0.0, 1.0)], reg, peaks=PEAKS)
    # 2e9 operations at 197 TFLOP/s, 8.19e8 bytes at 819 GB/s: 1 ms
    assert out.kernel_s == pytest.approx(0.5)
    assert out.roofline_s == pytest.approx(1e-3)
    assert out.op_seconds == {
        "kernel fixture_kernel/custom-call": pytest.approx(0.5)}


def test_kernel_without_formula_is_an_error(small):
    op = trace_reduce.Op(0, "custom-call.7", 0.2, 0.7,
                         kernel="unknown_kernel", operands=[])
    with pytest.raises(trace_reduce.NoFormula):
        trace_reduce.reduce_events(
            [op], [("bench/fit_path", 0.0, 1.0)], small, peaks=PEAKS)


def test_cell_environment_from_its_file(tmp_path, monkeypatch):
    reg = small_registry(tmp_path, extra={
        "workloads/env_cell.json": {"environment": {"BENCH_TEST_A": "1",
                                                    "BENCH_TEST_B": 2}}})
    monkeypatch.delenv("BENCH_TEST_A", raising=False)
    monkeypatch.setenv("BENCH_TEST_B", "kept")
    argv = ["run.py", "--workload", "env_cell", "--seed", "1"]
    assert harness.cell_environment(argv, reg.root) == {
        "BENCH_TEST_A": "1", "BENCH_TEST_B": 2}
    assert os.environ["BENCH_TEST_A"] == "1"
    assert os.environ["BENCH_TEST_B"] == "kept"
    assert harness.cell_environment(["run.py", "--workload", "nothing"],
                                    reg.root) == {}
