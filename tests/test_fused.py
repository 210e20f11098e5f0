"""Fused superstep fast path (DESIGN.md §8): fused-vs-unfused β parity
across families/designs/observation features, Pallas-kernel-vs-oracle
interpret parity, active-set-shaped launch bookkeeping, mixed-precision
accumulation, and the cross-process compilation cache."""
import os
import pathlib
import shutil
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import repro.core  # noqa: F401  (design↔ops import cycle: core first)
import jax.numpy as jnp

from repro.core.dglmnet import DGLMNETConfig
from repro.core.solver import GLMSolver
from repro.data import synthetic
from repro.data import design as design_lib
from repro.kernels import ops

FAMILIES = ["logistic", "squared", "probit", "poisson"]


def _cfg(family, fused, tile_size=16, **kw):
    return DGLMNETConfig(family=family, tile_size=tile_size,
                         coupling="jacobi", max_outer=60, tol=1e-10,
                         fuse_superstep=fused, **kw)


def _obs_features(n, p, seed):
    """weights + offset + penalty factors with an unpenalized coordinate."""
    rng = np.random.default_rng(seed)
    w = rng.uniform(0.5, 1.5, n).astype(np.float32)
    off = (0.1 * rng.normal(size=n)).astype(np.float32)
    pf = rng.uniform(0.5, 2.0, p).astype(np.float32)
    pf[0] = 0.0
    return w, off, pf


@pytest.mark.parametrize("family", FAMILIES)
def test_fused_matches_unfused_dense(family):
    """β parity ≤ 1e-5 on a dense design under sample weights + offset +
    penalty factors — the fused two-launch superstep must be numerically
    interchangeable with the historical 5-launch pipeline."""
    ds = synthetic.make_dense(n=300, p=48, k_true=8, family=family, seed=5)
    X, y = ds.train.X, ds.train.y
    w, off, pf = _obs_features(*X.shape, seed=6)
    betas = {}
    for fused in (False, True):
        s = GLMSolver(X, y, config=_cfg(family, fused), sample_weight=w,
                      offset=off, penalty_factor=pf)
        betas[fused] = s.fit(lam1=0.1 * s.lambda_max(), lam2=0.05).beta
    err = float(np.abs(betas[True] - betas[False]).max())
    assert err <= 1e-5, err
    assert np.abs(betas[True]).max() > 0  # non-degenerate fit


@pytest.mark.parametrize("family", ["logistic", "squared"])
def test_fused_matches_unfused_block_sparse(family):
    ds = synthetic.make_sparse(n=400, p=256, avg_nnz=12, k_true=20,
                               family=family, seed=7)
    X, y = ds.train.X, ds.train.y
    betas = {}
    for fused in (False, True):
        s = GLMSolver(X, y, config=_cfg(family, fused, tile_size=32))
        betas[fused] = s.fit(lam1=0.1 * s.lambda_max(), lam2=0.0).beta
    err = float(np.abs(betas[True] - betas[False]).max())
    assert err <= 1e-5, err


def test_fused_path_parity_with_screening():
    """fit_path exercises the strong-rule partial active mask: the fused
    sweep must zero screened coordinates exactly like the unfused one."""
    ds = synthetic.make_dense(n=400, p=96, k_true=10, seed=8)
    paths = {}
    for fused in (False, True):
        s = GLMSolver(ds.train.X, ds.train.y, config=_cfg("logistic", fused))
        paths[fused] = s.fit_path(n_lambdas=8, lam_ratio=1e-2)
    err = float(np.abs(paths[True].betas - paths[False].betas).max())
    assert err <= 1e-5, err
    assert (paths[True].nnz == paths[False].nnz).all()


@pytest.mark.parametrize("family", ["logistic", "squared"])
def test_fused_pallas_kernels_match_oracle(family):
    """Interpret-mode Pallas fused kernels vs the jnp oracle path, moderate
    margins (the ref/pallas stats formulas only diverge in the |m|≳12
    tails, which real line-searched iterates never visit)."""
    rng = np.random.default_rng(9)
    n, p, T = 256, 256, 128
    X = (0.2 * rng.normal(size=(n, p))).astype(np.float32)
    design, _ = design_lib.dense_design(jnp.asarray(X), T)
    y = jnp.asarray(rng.choice([-1.0, 1.0], n).astype(np.float32)
                    if family == "logistic"
                    else rng.normal(size=n).astype(np.float32))
    beta = jnp.asarray(
        (0.5 * rng.normal(size=p) * (rng.random(p) < 0.3)).astype(
            np.float32))
    xb = design.matvec(beta)
    live = jnp.asarray(np.array([True, False]))  # tile 1 screened out
    kw = dict(mu=1.0, nu=1e-6, lam1=0.1, lam2=0.05, tile_live=live)
    out_r = ops.fused_stats_sweep(design, y, xb, beta, family,
                                  backend="ref", **kw)
    out_p = ops.fused_stats_sweep(design, y, xb, beta, family,
                                  backend="pallas", **kw)
    for a, b, name in zip(out_r[:4], out_p[:4],
                          ("loss", "s", "w", "dbeta")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4,
                                   err_msg=name)
    # dead tile contributes exactly nothing in both backends
    assert not np.asarray(out_p[3][T:]).any()
    alphas = jnp.asarray(np.logspace(-2, 0, 14), jnp.float32)
    dbeta = out_r[3]
    xdb_r, ls_r = ops.fused_ls(design, y, xb, dbeta, alphas, family,
                               backend="ref")
    xdb_p, ls_p = ops.fused_ls(design, y, xb, dbeta, alphas, family,
                               backend="pallas")
    np.testing.assert_allclose(np.asarray(xdb_r), np.asarray(xdb_p),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(ls_r), np.asarray(ls_p),
                               rtol=1e-5, atol=1e-3)


@pytest.mark.parametrize("precision", ["fp32", "bf16"])
@pytest.mark.parametrize("T", [128, 256, 512])
def test_stats_gram_solve_blocked_gram(T, precision):
    """``stats_gram_solve_pallas`` (interpret mode) contracts only the
    upper 128-blocks of each tile's Gram and mirrors the rest: ``G_all``
    matches the oracle's full Gram and is bitwise symmetric, each live
    tile's Δβ is the oracle's chain on the oracle's Gram, and a dead tile
    returns zeros — over two 1024-row blocks with a masked tail."""
    from repro.kernels import ref, superstep_tile
    rng = np.random.default_rng(T)
    nt, n_pad, n = 3, 2048, 2048 - 300
    Xt3 = (0.2 * rng.normal(size=(nt, n_pad, T))).astype(np.float32)
    Xt3[:, n:] = 0.0
    y = rng.choice([-1.0, 1.0], n_pad).astype(np.float32)
    xb = (0.5 * rng.normal(size=n_pad)).astype(np.float32)
    mask = (np.arange(n_pad) < n).astype(np.float32)
    beta = (0.3 * rng.normal(size=(nt, T))
            * (rng.random((nt, T)) < 0.3)).astype(np.float32)
    penf = rng.uniform(0.5, 2.0, (nt, T)).astype(np.float32)
    mu, nu, lam1, lam2 = 1.0, 1e-6, 0.1, 0.05
    sel = jnp.asarray([0, 2, 1, 2], jnp.int32)     # tile 1 dead
    pack = lambda v: jnp.asarray(v.reshape(-1, 128))
    _, _, _, G, g, dbeta = superstep_tile.stats_gram_solve_pallas(
        sel, jnp.asarray(Xt3), pack(y), pack(xb), pack(mask),
        jnp.asarray(beta), jnp.asarray(penf),
        jnp.asarray([mu, nu, lam1, lam2], jnp.float32),
        family="logistic", precision=precision, interpret=True)
    G, g, dbeta = np.asarray(G), np.asarray(g), np.asarray(dbeta)
    _, _, _, G_ref, g_ref = ref.fused_stats_gram_dense(
        jnp.asarray(Xt3), jnp.asarray(y), jnp.asarray(xb), jnp.asarray(mask),
        "logistic", precision=precision)
    # the kernel's Gram is the oracle's upper triangle, mirrored: with
    # bf16 inputs the oracle's own two triangles differ by bf16 rounding
    # (row weights round with the left operand)
    upper = np.triu(np.ones((T, T), bool))
    G_ref = jnp.where(upper, G_ref, jnp.swapaxes(G_ref, 1, 2))
    assert (G == np.swapaxes(G, 1, 2)).all()
    assert not G[1].any() and not g[1].any() and not dbeta[1].any()
    for t in (0, 2):
        np.testing.assert_allclose(G[t], np.asarray(G_ref[t]), rtol=1e-5,
                                   atol=1e-4)
        np.testing.assert_allclose(g[t], np.asarray(g_ref[t]), rtol=1e-5,
                                   atol=1e-4)
        want = ref.cd_tile_solve(G_ref[t], g_ref[t], jnp.diagonal(G_ref[t]),
                                 jnp.asarray(beta[t]), jnp.zeros((T,)), mu,
                                 nu, lam1, lam2, penf=jnp.asarray(penf[t]))
        np.testing.assert_allclose(dbeta[t], np.asarray(want), atol=1e-4)
        assert np.abs(dbeta[t]).max() > 0
    assert superstep_tile.gram_blocks_mirrored(T) == {
        128: 0, 256: 1, 512: 6}[T]


def _ls_parity_problem(family, n, p, T, seed):
    """A dense design and a line-search direction from the oracle's tile
    solves, under sample weights and an offset, with moderate margins."""
    rng = np.random.default_rng(seed)
    X = (0.2 * rng.normal(size=(n, p))).astype(np.float32)
    design, _ = design_lib.dense_design(jnp.asarray(X), T)
    beta = (0.3 * rng.normal(size=p) * (rng.random(p) < 0.3)).astype(
        np.float32)
    m = X @ beta
    y = {"logistic": rng.choice([-1.0, 1.0], n),
         "probit": rng.choice([-1.0, 1.0], n),
         "squared": m + rng.normal(size=n),
         "poisson": rng.poisson(np.exp(m))}[family].astype(np.float32)
    w, off, pf = _obs_features(n, p, seed + 1)
    return design, jnp.asarray(y), jnp.asarray(beta), jnp.asarray(w), \
        jnp.asarray(off), jnp.asarray(pf)


@pytest.mark.parametrize("family", FAMILIES)
def test_margin_ls_matches_oracle_at_full_candidates(family):
    """``margin_ls_pallas`` (interpret mode, through ``ops.fused_ls``)
    against ``ref.fused_ls_dense`` at the benchmark's candidate count: the
    294 step sizes of ``full_candidates``, three 1024-row blocks with a
    ragged, masked tail, sample weights and an offset.  The losses agree to
    f32 summation order, and ``select_precomputed`` picks the same α."""
    from repro.core import linesearch
    cfg = DGLMNETConfig(family=family)
    n, p, T = 2 * 1024 + 700, 256, 128
    design, y, beta, w, off, pf = _ls_parity_problem(family, n, p, T, 13)
    xb = design.matvec(beta)
    lam1, lam2 = 0.02, 0.01
    loss_i, s, wt, dbeta, _, _ = ops.fused_stats_sweep(
        design, y, xb, beta, family, mu=1.0, nu=1e-6, lam1=lam1, lam2=lam2,
        weights=w, offset=off, penf=pf, backend="ref")
    assert np.abs(np.asarray(dbeta)).max() > 0
    cand = linesearch.full_candidates(cfg.ls_delta, cfg.ls_grid_size,
                                      cfg.backtrack_b, cfg.max_backtracks)
    assert cand.shape == (294,)
    out = {b: ops.fused_ls(design, y, xb, dbeta, cand, family, weights=w,
                           offset=off, backend=b)
           for b in ("ref", "pallas")}
    (xdb_r, ls_r), (xdb_p, ls_p) = out["ref"], out["pallas"]
    np.testing.assert_allclose(np.asarray(xdb_p), np.asarray(xdb_r),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(ls_p), np.asarray(ls_r), rtol=1e-5)
    R0 = linesearch.penalty_terms(beta, jnp.zeros_like(beta),
                                  jnp.zeros((1,)), lam1, lam2, None, pf)[0]
    grad_dot_dir = -jnp.sum(s * xdb_r)
    quad_form = jnp.sum(wt * xdb_r * xdb_r) + 1e-6 * jnp.sum(dbeta * dbeta)
    alphas = [
        float(linesearch.select_precomputed(
            losses, cand, beta, dbeta, lam1, lam2,
            f_current=jnp.sum(loss_i) + R0, grad_dot_dir=grad_dot_dir,
            quad_form=quad_form, sigma=cfg.sigma, gamma=cfg.gamma,
            grid_size=cfg.ls_grid_size, max_backtracks=cfg.max_backtracks,
            penf=pf).alpha)
        for losses in (ls_r, ls_p)]
    assert alphas[0] == alphas[1], alphas


def test_margin_ls_sublane_reduced_accumulator(monkeypatch):
    """A candidate set too large for the whole-tile accumulator falls back
    to one sublane-reduced row per candidate, with the same losses."""
    from repro.core import linesearch
    from repro.kernels import ref, superstep_tile
    assert superstep_tile._ls_acc_rows(294, 8) == 8
    assert superstep_tile._ls_acc_rows(1 << 20, 8) == 1
    n, p, T = 1024 + 300, 256, 128
    design, y, beta, w, off, _ = _ls_parity_problem("logistic", n, p, T, 17)
    dbeta = jnp.asarray(np.random.default_rng(18).normal(size=p) * 0.1,
                        jnp.float32)
    xb = design.matvec(beta) + off
    cand = linesearch.full_candidates(1e-3, 13, 0.5, 20)
    Xt3 = design.tiles3()
    (y2, xb2, w2), pad = ops._pack_rows(Xt3, y, xb, w)
    monkeypatch.setattr(superstep_tile, "_LS_ACC_BUDGET", 0)
    # unjitted, so the patched budget is read when the kernel is traced
    _, losses = superstep_tile.margin_ls_pallas.__wrapped__(
        Xt3, dbeta.reshape(-1, T), y2, xb2, w2 * pad, cand,
        family="logistic", interpret=True)
    pad_to = lambda v: jnp.pad(v, (0, Xt3.shape[1] - n))
    _, want = ref.fused_ls_dense(Xt3, pad_to(y), pad_to(xb), dbeta,
                                 pad_to(w), cand, "logistic")
    np.testing.assert_allclose(np.asarray(losses), np.asarray(want),
                               rtol=1e-5)


def test_screened_tiles_cost_zero_sweep_launches():
    """Host-side launch bookkeeping: along a screened λ-path, fully
    screened-out tiles are skipped by the active-set-shaped launch and the
    counters must balance exactly (live + skipped = supersteps × tiles)."""
    ds = synthetic.make_dense(n=400, p=128, k_true=6, seed=10)
    s = GLMSolver(ds.train.X, ds.train.y, config=_cfg("logistic", True,
                                                      tile_size=16))
    s.fit_path(n_lambdas=8, lam_ratio=1e-2)
    st = s.launch_stats
    n_tiles = 128 // 16
    assert st["supersteps"] > 0
    assert st["sweep_tiles_skipped"] > 0, st
    assert st["sweep_tile_launches"] + st["sweep_tiles_skipped"] \
        == st["supersteps"] * n_tiles, st
    # the unfused jacobi superstep has no shaped launch: nothing skipped
    s2 = GLMSolver(ds.train.X, ds.train.y, config=_cfg("logistic", False,
                                                       tile_size=16))
    s2.fit_path(n_lambdas=8, lam_ratio=1e-2)
    assert s2.launch_stats["sweep_tiles_skipped"] == 0


@pytest.mark.parametrize("backend,per_tile", [("pallas", 1), ("ref", 0)])
def test_gram_blocks_mirrored_counts_live_tiles(backend, per_tile):
    """At T = 256 the fused Pallas kernel mirrors one 128-block of each
    live tile's Gram a superstep: along a screened path the counter is
    the tiles it launched.  The oracle contracts every block."""
    ds = synthetic.make_dense(n=300, p=768, k_true=6, seed=14)
    s = GLMSolver(ds.train.X, ds.train.y, config=DGLMNETConfig(
        tile_size=256, coupling="jacobi", max_outer=8,
        kernel_backend=backend))
    s.fit_path(n_lambdas=3, lam_ratio=0.1)
    st = s.launch_stats
    assert st["sweep_tiles_skipped"] > 0, st
    assert st["gram_blocks_mirrored"] == \
        per_tile * st["sweep_tile_launches"], st


def test_runtime_active_changes_do_not_recompile():
    """The active mask is a runtime argument of the ONE compiled fused
    superstep — a whole screened path must stay at ≤1 superstep compile."""
    ds = synthetic.make_dense(n=300, p=64, k_true=6, seed=11)
    s = GLMSolver(ds.train.X, ds.train.y, config=_cfg("logistic", True))
    s.fit_path(n_lambdas=6, lam_ratio=1e-2)
    first = s.compile_count
    s.fit(lam1=0.05 * s.lambda_max())
    assert s.compile_count == first  # warm re-fit: zero new compiles


def test_bf16_tracks_fp32_alpha_sequence():
    """precision='bf16' (bf16 Gram/margin inputs, fp32 accumulation and
    Armijo sums): the accepted-α sequence must track fp32 — the line
    search decides from fp32 sums, so discrete α choices only flip on
    near-ties — and β must land within bf16-resolution of the fp32 fit."""
    ds = synthetic.make_dense(n=300, p=48, k_true=8, seed=12)
    fits = {}
    for prec in ("fp32", "bf16"):
        s = GLMSolver(ds.train.X, ds.train.y,
                      config=_cfg("logistic", True, precision=prec))
        fits[prec] = s.fit(lam1=0.1 * s.lambda_max(), lam2=0.05)
    a32 = np.asarray(fits["fp32"].history["alpha"])
    a16 = np.asarray(fits["bf16"].history["alpha"])
    k = min(len(a32), len(a16))
    assert k > 5
    match = float(np.mean(np.isclose(a32[:k], a16[:k], rtol=1e-6)))
    assert match >= 0.8, (match, a32[:k], a16[:k])
    err = float(np.abs(fits["bf16"].beta - fits["fp32"].beta).max())
    scale = float(np.abs(fits["fp32"].beta).max())
    assert err <= 0.05 * max(scale, 1.0), (err, scale)


@pytest.mark.parametrize("where", ["env", "checkout"])
def test_compilation_cache_populates_and_hits(tmp_path, where):
    """A child process populates the persistent cache; an identical second
    child must add no new entries (pure cache hits on the deserialized
    executables).  ``env``: the cache is where JAX_COMPILATION_CACHE_DIR
    says; ``checkout``: with the variable unset it is the fixed
    ``<checkout>/.jax_cache`` (here a copy of the package, so the run
    touches only its own directory)."""
    script = textwrap.dedent("""
        import numpy as np
        from repro import compile_cache
        from repro.core.dglmnet import DGLMNETConfig
        from repro.core.solver import GLMSolver
        rng = np.random.default_rng(0)
        X = rng.normal(size=(64, 32)).astype(np.float32)
        y = rng.choice([-1.0, 1.0], 64).astype(np.float32)
        s = GLMSolver(X, y, config=DGLMNETConfig(tile_size=16, max_outer=3))
        s.fit(lam1=0.3 * s.lambda_max())
        print("FIT_OK", compile_cache.cache_dir())
    """)
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    src = pathlib.Path(__file__).resolve().parents[1] / "src"
    if where == "env":
        cache = tmp_path / "cache"
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    else:
        shutil.copytree(src / "repro", tmp_path / "src" / "repro",
                        ignore=shutil.ignore_patterns("__pycache__"))
        src = tmp_path / "src"
        cache = tmp_path / ".jax_cache"
    env["PYTHONPATH"] = str(src)

    def run():
        r = subprocess.run([sys.executable, "-c", script], env=env,
                           capture_output=True, text=True, timeout=300)
        assert r.returncode == 0 and "FIT_OK" in r.stdout, r.stderr[-2000:]
        assert r.stdout.split()[-1] == str(cache), r.stdout
        return {p.name for p in cache.rglob("*") if p.is_file()}

    entries = run()
    assert entries
    assert run() == entries
