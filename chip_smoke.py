#!/usr/bin/env python3
"""Smoke run of the d-GLMNET fit and scoring path on TPU, through the
package's own entry points (``GLMSolver``, ``repro.serve``).

    python chip_smoke.py              # phases (a), (b), (c) on one chip
    python chip_smoke.py --chips 4    # the feature-sharded phase only

One chip (no arguments):

  (a) sparse λ-path, the paper's regime: logistic regression on a
      ``BlockSparseDesign`` with the ``glm_sparse`` shape
      (``configs/glm_webscale.py``) at one chip's share of its 16×16 mesh —
      32,768 rows × 65,536 features, T = 512, row_block = 256, about 5 %
      brick occupancy, Zipf feature popularity — fitted over a short
      warm-started λ-path with the default (Gauss-Seidel) configuration:
      ``tile_gram``, ``cd_tile_solve``, ``glm_stats``, ``alpha_search``.
  (b) dense control: the shape of the paper's epsilon set, 400,000 × 2,000
      correlated features, T = 256, ``coupling="jacobi"`` with the fused
      superstep (``stats_gram_solve``, ``margin_ls``).
  (c) scoring: (a)'s fit exported as an int8 artifact, a few hundred sparse
      requests through a ``MicroBatcher`` in front of a ``ScoringEngine``
      (``predict_tile``).

Four chips (``--chips 4``): the ``glm_sparse`` shape at four shards' worth
(65,536 × 131,072) fitted on a (1, 4) mesh (the paper's 1-D feature split)
and on (2, 2), each against the same problem fitted on one device in this
process.  Its features are numbered so that consecutive popularity ranks
fall in the tiles of different feature shards, and the fit keeps that
order (``reorder=False``): each shard holds a quarter of the hot head, the
active set spans every shard, and the sharded fits must couple their
blocks to match the one-device fit.

Every one-chip fit is repeated with ``kernel_backend="ref"`` (the jnp
oracles of ``kernels/ref.py``) on the same chip and must agree within the
tolerances below.  The oracle runs use float32 matrix products
(``jax.default_matmul_precision("highest")``), the semantics the oracles
define; the Pallas runs use the package defaults, as a user's fit does.
A phase fails if its compiled program holds no Pallas kernel
(``tpu_custom_call``).  The printed timings are those of one smoke run,
compilation stated separately — not benchmark numbers.

The last line of standard output is ``{"ok": true, "device": {...}}`` only
when every phase passed; without a TPU, or without the package next to
this script, the script exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import pathlib
import sys
import tempfile
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

OBJ_RTOL = 1e-4     # |f - f_ref| / max(1, |f_ref|) at every λ of the path
BETA_TOL = 1e-2     # max |β - β_ref| / max(1, max |β_ref|)
SCORE_ATOL = 1e-5   # |score - score_ref| on response probabilities
N_LAMBDAS = 4       # points of each λ-path
LAM_RATIO = 0.1     # λ_min / λ_max
# the four-chip path goes down to glmnet's default λ_min / λ_max for n < p,
# far enough for its active set to reach every feature shard
SHARDED_LAM_RATIO = 0.01
MAX_OUTER = 100     # supersteps per λ at most

# (a) and the four-chip phase: one chip's share of glm_sparse, and four's
SPARSE_ROWS, SPARSE_FEATURES = 32768, 65536
SPARSE_TILE, ROW_BLOCK, AVG_NNZ = 512, 256, 16
# (b): the epsilon set's shape
DENSE_ROWS, DENSE_FEATURES, DENSE_TILE = 400_000, 2000, 256
# (c)
N_REQUESTS = 512


class SmokeFailure(Exception):
    pass


def check(ok, msg):
    if not ok:
        raise SmokeFailure(msg)


def log(msg):
    print(msg, flush=True)


def sparse_problem(rows, features, seed):
    """Training split with ``rows`` rows (make_sparse keeps 80 %).  The
    popularity law is concentrated on a hot head of ~p/2730 features with a
    thin Zipf tail: about 5 % of the (row block × tile) bricks carry
    nonzeros, the occupancy of configs/glm_webscale.py "glm_sparse"."""
    from repro.data import synthetic
    return synthetic.make_sparse(
        n=rows * 5 // 4, p=features, avg_nnz=AVG_NNZ, k_true=200,
        seed=seed, zipf_scale=features / 2730.0)


def deal_over_shards(X, shards, tile):
    """Renumber the features of SparseCOO ``X`` so that popularity rank r
    lands in tile ``shards·(r // (shards·tile)) + r % shards``: with the
    layout's round-robin deal of tiles, consecutive ranks go to different
    feature shards and each shard's first tile holds a quarter of the
    hot head."""
    p = X.shape[1]
    assert p % (shards * tile) == 0
    r = np.arange(p)
    new_id = ((r // (shards * tile)) * shards + r % shards) * tile \
        + (r // shards) % tile
    perm = np.empty(p, np.int64)
    perm[new_id] = X.col_frequency_order()
    return X.permute_cols(perm)


def fit_once(X, y, cfg, lambdas, *, lam_ratio=LAM_RATIO, mesh=None,
             reorder=True, lower_first=True):
    """One session + λ-path; returns (session, path, report dict).  Oracle
    fits run under float32 matrix products, kernel fits at the defaults."""
    import jax
    from repro.core.solver import GLMSolver
    from repro.kernels import ops

    precision = (jax.default_matmul_precision("highest")
                 if cfg.kernel_backend == "ref" else contextlib.nullcontext())
    with ops.oracle_trace() as oracle, precision:
        t0 = time.perf_counter()
        s = GLMSolver(X, y, config=cfg, mesh=mesh, row_block=ROW_BLOCK,
                      reorder=reorder)
        rep = {"setup_s": time.perf_counter() - t0}
        if lower_first:
            t0 = time.perf_counter()
            compiled = s.lower_superstep().compile()
            rep["compile_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        if lambdas is None:
            path = s.fit_path(n_lambdas=N_LAMBDAS, lam_ratio=lam_ratio)
        else:
            path = s.fit_path(lambdas=lambdas)
        rep["run_s"] = time.perf_counter() - t0
        rep["compile_count"] = s.compile_count
        if not lower_first:
            compiled = s.lower_superstep().compile()
    rep["tpu_custom_calls"] = compiled.as_text().count("tpu_custom_call")
    rep["supersteps"] = int(s.launch_stats["supersteps"])
    rep["path_supersteps"] = [int(k) for k in path.n_iters]
    rep["device_bytes"] = s.device_bytes()
    rep["ref_dispatches"] = {f"{op}: {why}": n for (op, why), n
                             in sorted(oracle.items())}
    stats = jax.devices()[0].memory_stats() or {}
    rep["peak_bytes_in_use_dev0"] = stats.get("peak_bytes_in_use")
    return s, path, rep


def report(tag, rep):
    byts = rep["device_bytes"]
    log(f"[{tag}] setup {rep['setup_s']:.1f}s"
        + (f", compile {rep['compile_s']:.1f}s" if "compile_s" in rep
           else "")
        + f", run {rep['run_s']:.1f}s (smoke-run timings, not a benchmark)")
    log(f"[{tag}] supersteps {rep['supersteps']} (per λ "
        f"{rep['path_supersteps']}), superstep compiles "
        f"{rep['compile_count']}, tpu_custom_call ops in the compiled "
        f"superstep {rep['tpu_custom_calls']}")
    log(f"[{tag}] device bytes {sum(byts.values())} "
        f"(per device {byts}), peak_bytes_in_use (device 0, process) "
        f"{rep['peak_bytes_in_use_dev0']}")
    log(f"[{tag}] dispatches on the jnp oracle: "
        f"{rep['ref_dispatches'] or 'none'}")


def compare_paths(tag, path, ref):
    f, f_ref = np.asarray(path.f), np.asarray(ref.f)
    gap = float(np.max(np.abs(f - f_ref) / np.maximum(1.0, np.abs(f_ref))))
    scale = max(1.0, float(np.abs(ref.betas).max()))
    dbeta = float(np.abs(path.betas - ref.betas).max()) / scale
    log(f"[{tag}] objective per λ {f.tolist()} vs ref {f_ref.tolist()}")
    log(f"[{tag}] max relative objective gap {gap:.3e} (tol {OBJ_RTOL}), "
        f"max |Δβ|/max(1,|β|) {dbeta:.3e} (tol {BETA_TOL}), nnz "
        f"{path.nnz.tolist()} vs ref {ref.nnz.tolist()}")
    check(np.all(np.isfinite(f)) and np.all(np.isfinite(path.betas)),
          f"{tag}: non-finite objective or coefficients")
    check(gap <= OBJ_RTOL, f"{tag}: objective gap {gap:.3e} > {OBJ_RTOL}")
    check(dbeta <= BETA_TOL, f"{tag}: coefficient gap {dbeta:.3e} > "
          f"{BETA_TOL}")
    return gap


def pallas_vs_ref(tag, X, y, cfg):
    """Fit the path with the Pallas kernels, then with the oracles on the
    same λ grid; returns (pallas session, pallas path, objective gap)."""
    s, path, rep = fit_once(X, y, cfg, None)
    report(f"{tag} pallas", rep)
    check(rep["tpu_custom_calls"] > 0,
          f"{tag}: the compiled superstep holds no Pallas kernel")
    cfg_ref = dataclasses.replace(cfg, kernel_backend="ref")
    s_ref, ref, rep_ref = fit_once(X, y, cfg_ref, path.lambdas)
    report(f"{tag} ref", rep_ref)
    del s_ref
    gc.collect()
    return s, path, compare_paths(tag, path, ref)


def phase_sparse(seed, artifact_dir):
    from repro.core.dglmnet import DGLMNETConfig
    ds = sparse_problem(SPARSE_ROWS, SPARSE_FEATURES, seed)
    X, y = ds.train.X, ds.train.y
    cfg = DGLMNETConfig(tile_size=SPARSE_TILE, max_outer=MAX_OUTER)
    log(f"[a] sparse logistic λ-path: {X.shape[0]} rows × {X.shape[1]} "
        f"features, {len(X.vals)} nonzeros, T={SPARSE_TILE}, "
        f"row_block={ROW_BLOCK}, coupling={cfg.coupling}, {N_LAMBDAS} λ")
    s, path, gap = pallas_vs_ref("a", X, y, cfg)
    info = s.info
    log(f"[a] brick occupancy {info.occupancy:.4f}, {info.n_bricks} bricks")
    s.save(artifact_dir, quantize="int8")
    del s
    gc.collect()
    return ds.test.X, gap


def phase_dense(seed):
    from repro.core.dglmnet import DGLMNETConfig
    from repro.data import synthetic
    ds = synthetic.make_dense(n=DENSE_ROWS * 5 // 4, p=DENSE_FEATURES,
                              k_true=50, rho=0.5, seed=seed)
    X, y = ds.train.X, ds.train.y
    del ds
    cfg = DGLMNETConfig(tile_size=DENSE_TILE, coupling="jacobi",
                        fuse_superstep=True, max_outer=MAX_OUTER)
    log(f"[b] dense logistic λ-path: {X.shape[0]} rows × {X.shape[1]} "
        f"features (AR(1) ρ=0.5), T={DENSE_TILE}, jacobi, fused "
        f"superstep, {N_LAMBDAS} λ")
    s, path, gap = pallas_vs_ref("b", X, y, cfg)
    del s
    gc.collect()
    return gap


def phase_scoring(X_test, artifact_dir):
    import jax
    from repro.kernels import ops
    from repro.serve.artifact import load_artifact
    from repro.serve.batcher import MicroBatcher
    from repro.serve.engine import ScoringEngine, coo_to_requests

    model = load_artifact(artifact_dir)
    reqs = [r for r in coo_to_requests(X_test) if len(r[0])]
    reqs = reqs[:N_REQUESTS]
    eng = ScoringEngine(model)
    log(f"[c] int8 artifact: {model.n_features} features, "
        f"{eng.n_active} active; {len(reqs)} sparse requests "
        f"(max nnz {max(len(i) for i, _ in reqs)})")
    t0 = time.perf_counter()
    with ops.oracle_trace() as oracle, MicroBatcher(eng) as mb:
        handles = [mb.submit(idx, val) for idx, val in reqs]
        got = np.stack([h.get(timeout=600) for h in handles])
        stats = mb.stats()
    run_s = time.perf_counter() - t0
    log(f"[c] dispatches on the jnp oracle: {dict(oracle) or 'none'}")
    log(f"[c] batcher: {stats['n_requests']} requests, {stats['n_failed']} "
        f"failed, {stats['n_batches']} batches, {stats['compiled_shapes']} "
        f"compiled shapes, {run_s:.1f}s including compiles (smoke-run "
        "timing, not a benchmark)")
    check(stats["n_failed"] == 0 and stats["n_requests"] == len(reqs),
          f"c: batcher stats {stats}")
    max_nnz = max(len(i) for i, _ in reqs)
    nnz = min([b for b in mb.nnz_buckets if b >= max_nnz] or [max_nnz])
    n_kernels = eng.lower_packed(
        mb.max_batch, nnz).compile().as_text().count("tpu_custom_call")
    log(f"[c] tpu_custom_call ops in the compiled scoring program "
        f"({mb.max_batch}×{nnz}): {n_kernels}")
    check(n_kernels > 0, "c: the scoring program holds no Pallas kernel")
    with jax.default_matmul_precision("highest"):
        want = ScoringEngine(model, backend="ref").score_sparse(reqs)
    err = float(np.abs(got - want).max())
    log(f"[c] max |score - ref| {err:.3e} (tol {SCORE_ATOL}), scores in "
        f"[{float(got.min()):.4f}, {float(got.max()):.4f}]")
    check(np.all(np.isfinite(got)), "c: non-finite scores")
    check(err <= SCORE_ATOL, f"c: score gap {err:.3e} > {SCORE_ATOL}")
    return err


def shard_spread(s, path, M):
    """Nonzero coefficients per feature shard of session ``s`` (sharded
    ``M`` ways over features), per λ."""
    p = s.info.shape[1]
    p_loc = (p + (-p) % (M * SPARSE_TILE)) // M
    shard = s.info.col_of_feature // p_loc
    return np.stack([np.bincount(shard[b != 0], minlength=M)
                     for b in np.asarray(path.betas)])


def phase_sharded(seed):
    """The paper's feature-sharded layout on four chips vs one device."""
    import jax
    from repro.core.dglmnet import DGLMNETConfig
    from repro.sharding import compat

    ds = sparse_problem(2 * SPARSE_ROWS, 2 * SPARSE_FEATURES, seed)
    X = deal_over_shards(ds.train.X, 4, SPARSE_TILE)
    y = ds.train.y
    cfg = DGLMNETConfig(tile_size=SPARSE_TILE, max_outer=MAX_OUTER)
    log(f"[4] sparse logistic λ-path: {X.shape[0]} rows × {X.shape[1]} "
        f"features, {len(X.vals)} nonzeros, T={SPARSE_TILE}, popularity "
        f"ranks dealt over 4 feature shards, {len(jax.devices())} devices")
    s, one, rep = fit_once(X, y, cfg, None, lam_ratio=SHARDED_LAM_RATIO,
                           reorder=False, lower_first=False)
    report("4 one-device", rep)
    log(f"[4] brick occupancy {s.info.occupancy:.4f}, "
        f"{s.info.n_bricks} bricks")
    del s
    gc.collect()
    gaps = {}
    for shape in ((1, 4), (2, 2)):
        tag = f"4 mesh{shape[0]}x{shape[1]}"
        mesh = compat.make_mesh(shape, ("data", "model"))
        s, path, rep = fit_once(X, y, cfg, one.lambdas, mesh=mesh,
                                reorder=False, lower_first=False)
        report(tag, rep)
        held = rep["device_bytes"]
        check(len(held) == 4 and min(held.values()) > 0,
              f"{tag}: data is not spread over four devices: {held}")
        check(rep["compile_count"] == 1,
              f"{tag}: the superstep compiled {rep['compile_count']} times")
        check(rep["tpu_custom_calls"] > 0,
              f"{tag}: the compiled superstep holds no Pallas kernel")
        spread = shard_spread(s, path, shape[1])
        log(f"[{tag}] nonzero coefficients per feature shard, per λ: "
            f"{spread.tolist()}")
        check((spread > 0).any(axis=0).all(),
              f"{tag}: a feature shard never holds a nonzero coefficient, "
              "so the blocks were never coupled")
        f, f_one = np.asarray(path.f), np.asarray(one.f)
        gap = float(np.max(np.abs(f - f_one)
                           / np.maximum(1.0, np.abs(f_one))))
        dbeta = float(np.abs(path.betas - one.betas).max()) \
            / max(1.0, float(np.abs(one.betas).max()))
        log(f"[{tag}] objective per λ {f.tolist()} vs one device "
            f"{f_one.tolist()}: max relative gap {gap:.3e} "
            f"(tol {OBJ_RTOL}), max |Δβ|/max(1,|β|) {dbeta:.3e}")
        check(np.all(np.isfinite(f)), f"{tag}: non-finite objective")
        check(gap <= OBJ_RTOL, f"{tag}: objective gap {gap:.3e}")
        gaps[shape] = gap
        del s
        gc.collect()
    return gaps


def run(chips, seed):
    """Every phase for ``chips``; raises SmokeFailure on the first miss."""
    if chips == 4:
        phase_sharded(seed)
        return
    with tempfile.TemporaryDirectory() as td:
        X_test, _ = phase_sparse(seed, td)
        phase_dense(seed)
        phase_scoring(X_test, td)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"chip_smoke: the repro package is not next to this script "
              f"({e})", file=sys.stderr)
        return 2
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (jax platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 3
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 4
    d0 = devices[0]
    log(f"chip_smoke: {len(devices)} × {d0.device_kind} ({d0.platform}), "
        f"jax {jax.__version__}, phases for --chips {args.chips}")
    t0 = time.perf_counter()
    run(args.chips, args.seed)
    log(f"chip_smoke: all phases passed in {time.perf_counter() - t0:.1f}s "
        "(smoke-run wall time)")
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
