"""Shared set-up of the benchmark's own tests: the checkout and the
package on the path, and fixture registries at test sizes."""
import argparse
import copy
import json
import pathlib
import shutil
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
CHECKOUT = BENCH.parent
for p in (str(CHECKOUT / "src"), str(CHECKOUT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402

# test sizes of the configurations: every width (tile size, correlation)
# as in the cells, fewer rows and features
SMALL = {
    "epsilon_dense": {"rows": 16000, "features": 600},
}


def small_registry(tmp_path, *, extra=None) -> harness.Registry:
    """A copy of the benchmark's files under ``tmp_path`` with every
    configuration cut to its test size.  ``extra`` maps relative paths to
    file contents (str, or a dict written as JSON) added to the copy."""
    root = tmp_path / "bench"
    shutil.copytree(BENCH, root, ignore=shutil.ignore_patterns(
        "tests", "__pycache__", "*.md"))
    for name, sizes in SMALL.items():
        path = root / "configs" / f"{name}.json"
        cfg = json.loads(path.read_text())
        cfg["data"].update(sizes)
        path.write_text(json.dumps(cfg))
    bench = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    for rel, content in (extra or {}).items():
        if rel == "BENCHMARK.json":
            bench = content
            continue
        path = root / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(content if isinstance(content, str)
                        else json.dumps(content))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return harness.Registry(root, tmp_path / "BENCHMARK.json")


def benchmark_json():
    return copy.deepcopy(json.loads((CHECKOUT / "BENCHMARK.json")
                                    .read_text()))


def args(workload, *, seed=7, seconds=0.1, trace=0):
    return argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=trace)


@pytest.fixture
def small(tmp_path):
    return small_registry(tmp_path)
