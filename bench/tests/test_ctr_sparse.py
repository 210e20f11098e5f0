"""The hashed CTR cell (``ctr_sparse.path_full``): its files are found by
name, its generator's float64 products and planted click rate, its
operation count, its two per-layer readers, and a whole run at test size
on the CPU."""
import dataclasses
import json

import numpy as np
import pytest

from bench import harness, trace_reduce
from conftest import BENCH, args, small_registry

CELL = "ctr_sparse.path_full"
PEAKS = json.loads((BENCH / "peaks.json").read_text())["devices"][
    "TPU v5 lite"]


@pytest.fixture(scope="module")
def config():
    return json.loads((BENCH / "configs" / "ctr_sparse.json").read_text())


def _problem(config, rows=4096, features=20000):
    d = dict(config["data"], rows=rows, features=features)
    return harness.load_module(BENCH / "gen" / "criteo_fields.py", "gen") \
        .generate(dict(config, data=d), {}, 11, (1, 1))


@pytest.fixture(scope="module")
def problem(config):
    return _problem(config)


def test_registry_finds_every_file_of_the_cell():
    reg = harness.Registry()
    cell = reg.data("workloads", CELL)
    config = reg.data("configs", cell["config"])
    assert reg.data("traffic", cell["traffic"])["warmup_lambdas"] == 2
    assert reg.has_module("gen", config["generator"])
    assert reg.has_module("reference", config["reference"])
    names = [m["name"] for m in reg.metrics("per_layer", CELL)]
    for name in names:
        assert reg.has_module("metrics", name), name
    assert {"superstep.xla_ms", "tail.entries_per_superstep",
            "superstep_mfu", "kernels_roofline"} <= set(names)
    assert {m["name"] for m in reg.metrics("end_to_end", CELL)} == {
        "path_s", "peak_hbm_gib", "setup_s"}
    entry = next(c for c in reg.benchmark["configs"]
                 if c["name"] == "ctr_sparse")
    assert set(entry["reduced"]) == set(config["reduced"])
    assert entry["source"] == config["source"]


def test_config_keeps_the_published_shape(config):
    d = config["data"]
    fields = d["integer_fields"] + len(d["categorical_cardinalities"])
    assert fields == d["fields"] == 39
    assert d["features"] == 1_000_000
    assert config["solver"]["head_features"] % config["solver"][
        "tile_size"] == 0


def test_products_match_the_densified_rows(problem):
    ids, vals = problem.pairs()
    n, p = problem.shape
    X = np.zeros((n, p))
    np.add.at(X, (np.repeat(np.arange(n), ids.shape[1]), ids.ravel()),
              vals.ravel())
    np.testing.assert_array_equal(ids, np.asarray(problem.X.ids))
    rng = np.random.default_rng(0)
    B = np.where(rng.random((p, 3)) < 0.01, rng.normal(size=(p, 3)), 0.0)
    S = rng.normal(size=(n, 2))
    M = X @ B
    b = problem.profile_intercepts(M)
    np.testing.assert_allclose(problem.margins(B), M + b, rtol=1e-12,
                               atol=1e-12)
    plain = dataclasses.replace(problem, fit_intercept=False)
    np.testing.assert_allclose(plain.margins(B), M, rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(problem.rmatvec(S), X.T @ S, rtol=1e-12,
                               atol=1e-9)


def test_profiled_intercept_minimizes_the_loss(problem):
    """The intercept the reference adds zeroes the loss's derivative in
    it, from margins near and far from the labels."""
    assert problem.fit_intercept
    n = problem.shape[0]
    t = (problem.y > 0).astype(np.float64)
    rng = np.random.default_rng(4)
    M = np.stack([np.zeros(n), rng.normal(size=n), 8.0 + rng.normal(size=n),
                  -20.0 * np.abs(rng.normal(size=n))], axis=1)
    b = problem.profile_intercepts(M)
    q = 1.0 / (1.0 + np.exp(-(M + b)))
    np.testing.assert_allclose(np.sum(q - t[:, None], axis=0), 0.0,
                               atol=1e-8)
    # the intercept-only model's intercept is the log-odds of a click
    assert b[0] == pytest.approx(np.log(t.mean() / (1.0 - t.mean())),
                                 rel=1e-12)


def test_rows_carry_one_value_of_each_field(problem):
    ids, vals = problem.pairs()
    assert ids.shape[1] == 39 and (vals == 1.0).all()
    assert ids.min() >= 0 and ids.max() < problem.shape[1]


@pytest.mark.parametrize("rows", [4096, 65536])
def test_planted_click_rate(config, rows):
    pr = _problem(config, rows=rows)
    assert abs(float(np.mean(pr.y > 0)) - 0.256) <= 0.005
    assert set(np.unique(pr.y)) == {-1.0, 1.0}


def test_superstep_flops_by_hand():
    """Two rows, a head of one 2-wide tile: row 0 has 2 head nonzeros and
    1 tail nonzero, row 1 has 1 and 2 — 2·(2² + 1²) for the live tile,
    4·3 for the tail, 4·n·H for the head's gradient and margin delta."""
    gen = harness.load_module(BENCH / "gen" / "criteo_fields.py", "gen")
    pr = gen.SparseRowsProblem(None, np.ones(2), (2, 6), None, 2, 0.0)
    # features 0 and 1 are the most frequent: the head
    pr._host = (np.array([[0, 1, 4], [0, 3, 5]]), np.ones((2, 3)))
    assert pr.superstep_flops(2, 1.0) == 2.0 * 5 + 4.0 * 3 + 4.0 * 2 * 2
    # a fraction of a live tile counts its share; more than the head's
    # tiles count the head's
    assert pr.superstep_flops(2, 0.5) == 5.0 + 12.0 + 16.0
    assert pr.superstep_flops(2, 3.0) == pr.superstep_flops(2, 1.0)


def _reader(name):
    return harness.load_module(BENCH / "metrics" / f"{name}.py", "metrics")


def _ctx(reduced, counters):
    return harness.LayerContext({}, 1, None, counters, {}, reduced, PEAKS)


def test_xla_ms_reader_on_synthetic_trace():
    red = trace_reduce.Reduced(window_s=10.0, busy_by_device={0: 6.0},
                               kernel_s=3.0)
    read = _reader("superstep.xla_ms").read
    # (busy 6 s - kernels 3 s) over 4 supersteps
    assert read(_ctx(red, {"supersteps": 4})) == pytest.approx(750.0)
    assert read(_ctx(red, {"supersteps": 0})) is None
    assert read(_ctx(None, {"supersteps": 4})) is None


def test_tail_entries_reader_on_synthetic_counters():
    read = _reader("tail.entries_per_superstep").read
    assert read(_ctx(None, {"supersteps": 4, "tail_entries": 10})) == 2.5
    # a program that does not count tail entries reads nothing
    assert read(_ctx(None, {"supersteps": 4})) is None


def test_cell_runs_at_test_size(tmp_path, monkeypatch):
    import conftest
    monkeypatch.setitem(conftest.SMALL, "ctr_sparse",
                        {"rows": 16384, "features": 50000})
    reg = small_registry(tmp_path)
    result, lines = harness.run_cell(args(CELL, seconds=0.1, trace=1),
                                     reg=reg, require_tpu=False)
    assert result["correct"], lines
    m = result["metrics"]
    assert m["tail.entries_per_superstep"]["value"] >= 0.0
    assert m["outer.supersteps_per_path"]["value"] > 0
    assert m["design.device_gib"]["value"] > 0
