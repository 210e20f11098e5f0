"""End-to-end driver (the paper's kind of workload at the largest size this
CPU box sustains): train a wide sparse logistic regression — ~1M features —
with distributed d-GLMNET over 8 simulated feature-split nodes, with
checkpointing every 10 supersteps and automatic resume.

Scale knobs: N_EXAMPLES / N_FEATURES / devices.

    python examples/train_glm_large.py [--features 1048576] [--steps 200]
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import sys
import pathlib
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

import argparse
import time

import jax
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.core.dglmnet import DGLMNETConfig
from repro.core.solver import GLMSolver
from repro.data import synthetic
from repro.sharding import compat


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--examples", type=int, default=10_000)
    ap.add_argument("--features", type=int, default=1 << 16,
                    help="feature count (default 65k; raise to 1<<20 with "
                         "enough RAM — the algorithm/IO path is identical)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--ckpt-dir", default="/tmp/dglmnet_large_ckpt")
    args = ap.parse_args()

    print(f"generating sparse data: n={args.examples} p={args.features}")
    ds = synthetic.make_sparse(n=args.examples, p=args.features,
                               avg_nnz=40, k_true=500, seed=11)
    X = ds.train.X                      # SparseCOO — the dense (n, p) matrix
    print(f"nnz={X.nnz/1e6:.1f}M")      # is never materialized on host

    mesh = compat.make_mesh((1, 8), ("data", "model"))
    cfg = DGLMNETConfig(lam1=2.0, lam2=0.1, tile_size=256,
                        coupling="jacobi", alb=True,
                        max_outer=args.steps, tol=1e-9)
    mgr = CheckpointManager(args.ckpt_dir, keep_last=2, async_save=True)
    if mgr.latest_step():
        print(f"resuming from superstep {mgr.latest_step()}")

    t0 = time.time()
    solver = GLMSolver(X, ds.train.y, config=cfg, mesh=mesh)
    res = solver.fit(ckpt_manager=mgr, ckpt_every=10, verbose=True)
    dt = time.time() - t0
    print(f"\ndone in {dt:.1f}s  ({res.n_iter} supersteps, "
          f"converged={res.converged})")
    print(f"nnz={(res.beta != 0).sum()} of {len(res.beta)}")
    # beta comes back in the original feature order
    scores = ds.test.X.matvec(res.beta)
    print(f"test auPRC = {synthetic.au_prc(ds.test.y, scores):.4f}")


if __name__ == "__main__":
    main()
