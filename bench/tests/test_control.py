"""The control, at test size on the CPU: each configuration's
lower-precision setting (``control`` in its file) in the program's place
comes out not correct under the cell's own limits, on three seeds.

Dense: bfloat16 inputs to the fused Gram and margin products
(``precision``), which only the Pallas kernels apply, so the control runs
them in interpret mode."""
import json

import pytest

from bench import harness
from conftest import BENCH, args, small_registry

DENSE = json.loads((BENCH / "configs" / "epsilon_dense.json").read_text())
DENSE["data"].update(rows=8000, features=512)
DENSE["control"]["solver"]["kernel_backend"] = "pallas"


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_is_not_correct(tmp_path, seed):
    reg = small_registry(tmp_path,
                         extra={"configs/epsilon_dense.json": DENSE})
    result, lines = harness.run_cell(args("epsilon_dense.path", seed=seed),
                                     reg=reg,
                                     require_tpu=False, control=True)
    assert result["correct"] is False, lines
