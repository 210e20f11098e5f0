"""The readings a check limit is set from: the numbers compared with the
reference, for many seeds in one process (set-up paid once per seed,
compilation once), for the program as the configuration states it or for
its control (the configuration's lower-precision setting, ``--control``).

    python bench/readings.py --workload <cell> --seconds <s> [--control] \\
        --seeds 1 2 3 ...

Prints one JSON line per seed: the seed, the paths run and checked, and
each compared number beside the cell's limit.
"""
import argparse
import json
import pathlib
import sys
import time

CHECKOUT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]

from bench import harness  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    a = ap.parse_args(argv)
    harness.cell_environment(["--workload", a.workload])
    from repro import compile_cache
    compile_cache.init()
    for seed in a.seeds:
        t0 = time.perf_counter()
        args = argparse.Namespace(workload=a.workload, seed=seed,
                                  seconds=a.seconds, trace=0)
        result, _ = harness.run_cell(args, control=a.control)
        print(json.dumps({"seed": seed, "control": a.control,
                          "paths": result["attempted"],
                          "failed": result["failed"],
                          "correct": result["correct"],
                          "checks": result["checks"],
                          "metrics": result["metrics"],
                          "wall_s": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
