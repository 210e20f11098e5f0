"""A whole run of each cell at test size on the CPU, the harness's look for
a chip skipped: the answers come out correct and the result line has the
shape the contract asks for."""
import json

import pytest

from bench import harness
from conftest import args


@pytest.mark.parametrize("cell", ["epsilon_dense.path"])
def test_small_cell_runs_correct(small, cell):
    result, lines = harness.run_cell(args(cell), reg=small, require_tpu=False)
    assert result["correct"], lines
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == {"path_s", "peak_hbm_gib", "setup_s"}
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert result["checks"]["compiles_in_window"]["value"] == 0
    json.dumps(result)


def test_small_traced_run(small):
    result, lines = harness.run_cell(args("epsilon_dense.path", trace=1),
                                     reg=small, require_tpu=False)
    assert result["correct"], lines
    assert "outer.supersteps_per_path" in result["metrics"]
    assert "design.device_gib" in result["metrics"]
    assert {"busy_s", "window_s"} <= set(result["device"])
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
