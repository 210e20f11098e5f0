"""Run one benchmark cell:

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``: each number compared with the
reference beside its limit).  Without a TPU, or with fewer chips than the
cell asks for, it exits non-zero and prints no result.
"""
import pathlib
import sys
import time

T_START = time.perf_counter()
CHECKOUT = pathlib.Path(__file__).resolve().parents[1]

if __name__ == "__main__":
    sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]
    from bench import harness
    harness.cell_environment(sys.argv)
    from repro import compile_cache
    compile_cache.init()
    sys.exit(harness.main(t_start=T_START))
