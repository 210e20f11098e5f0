"""Dense-head / sparse-tail layout (DESIGN.md §2) for hashed multi-field
rows (``SparseRows``): its operators against the densified matrix, λ-paths
against the plain reference's KKT conditions and against the brick layout,
the layout's edge cases, and the front door's refusals."""
import dataclasses

import numpy as np
import pytest

import repro.core  # noqa: F401  (design↔ops import cycle: core first)
import jax.numpy as jnp

from repro.core import glm
from repro.core.dglmnet import DGLMNETConfig
from repro.core.solver import GLMSolver
from repro.data import design as design_lib
from repro.data.sparse import SparseRows

FAMILIES = ["logistic", "squared", "probit", "poisson"]
N, P, T, H = 4096, 8192, 128, 512
# 13 binned integer fields, then 26 categorical ones cut to this p
CARDS = [16] * 13 + [1460, 583, 8192, 2048, 305, 24, 4096, 633, 3, 2048,
                     1024, 8192, 512, 27, 2048, 4096, 10, 1024, 512, 4,
                     8192, 18, 15, 2048, 105, 2048]


def _rows(n=N, p=P, seed=0):
    """39 one-hot fields a row, Zipf(1.1)-popular values hashed into p
    features, and labels from a planted logistic model."""
    rng = np.random.default_rng(seed)
    card = np.asarray(CARDS)
    u = rng.random((n, len(card)))
    top = (card + 1.0) ** -0.1
    value = np.clip(np.floor((1.0 - u * (1.0 - top)) ** -10.0) - 1, 0,
                    card - 1).astype(np.int64)
    field = np.arange(len(card))
    ids = ((field * 1_000_003 + value * 7919) * 2654435761 % 2 ** 32) % p
    effect = np.where(rng.random((len(card), 16)) < 0.5,
                      rng.normal(0, 0.5, (len(card), 16)), 0.0)
    margin = np.where(value < 16, effect[field, np.minimum(value, 15)],
                      0.0).sum(1) - 1.0
    y = np.where(rng.random(n) < 1 / (1 + np.exp(-margin)), 1.0, -1.0)
    vals = np.ones(ids.shape, np.float32)
    return SparseRows(ids.astype(np.int32), vals, p), y.astype(np.float32)


def _dense(rows):
    ids, vals = np.asarray(rows.ids), np.asarray(rows.vals, np.float64)
    X = np.zeros(rows.shape)
    np.add.at(X, (np.repeat(np.arange(rows.shape[0]), ids.shape[1]),
                  ids.ravel()), vals.ravel())
    return X


@pytest.fixture(scope="module")
def data():
    rows, y = _rows()
    return rows, y, _dense(rows)


def _packed_dense(design, info, X):
    """The densified matrix in the design's packed column order."""
    out = np.zeros(design.shape)
    out[:X.shape[0], info.col_of_feature] = X
    return out


@pytest.mark.parametrize("family", FAMILIES)
def test_operators_match_dense(data, family):
    rows, y, X = data
    design, info = design_lib.head_tail_design(rows, T, H)
    Xp = _packed_dense(design, info, X)
    np.testing.assert_array_equal(np.asarray(design.to_dense()), Xp)
    rng = np.random.default_rng(1)
    n_pad = design.shape[0]
    v = rng.normal(size=design.shape[1]).astype(np.float32)
    np.testing.assert_allclose(design.matvec(v), Xp @ v, rtol=1e-5,
                               atol=1e-4)
    # the family's statistics at some margins weight the column sums
    yp = jnp.asarray(np.pad(y, (0, n_pad - N), constant_values=1.0))
    xb = jnp.asarray(rng.normal(size=n_pad).astype(np.float32))
    wobs = jnp.asarray((np.arange(n_pad) < N).astype(np.float32))
    _, s, w = glm.get_family(family).stats(yp, xb, weights=wobs)
    s, w = np.asarray(s, np.float64), np.asarray(w, np.float64)
    np.testing.assert_allclose(design.rmatvec(jnp.asarray(s, jnp.float32)),
                               Xp.T @ s, rtol=1e-4, atol=1e-3)
    m1, m2 = design.col_moments(jnp.asarray(w, jnp.float32))
    np.testing.assert_allclose(m1, Xp.T @ w, rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(m2, (Xp * Xp).T @ w, rtol=1e-4, atol=1e-3)
    g_t, h_t = design.tail_stats(jnp.asarray(s, jnp.float32),
                                 jnp.asarray(w, jnp.float32))
    np.testing.assert_allclose(g_t, Xp[:, H:].T @ s, rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(h_t, (Xp[:, H:] ** 2).T @ w, rtol=1e-4,
                               atol=1e-3)


def test_working_set_reads_its_columns_only(data, monkeypatch):
    """The superstep's tail over a working set equals the whole tail's on
    the set's columns, is zero elsewhere, and a set too large for its
    capacity reads the whole tail."""
    rows, y, X = data
    monkeypatch.setattr(design_lib, "_WS_CAPACITY", 4096)
    monkeypatch.setattr(design_lib, "_WS_CHUNK", 512)
    design, info = design_lib.head_tail_design(rows, T, H)
    rng = np.random.default_rng(2)
    n_pad = design.shape[0]
    s = jnp.asarray(rng.normal(size=n_pad).astype(np.float32))
    w = jnp.asarray(rng.uniform(size=n_pad).astype(np.float32))
    g_all, h_all = design.tail_stats(s, w)
    counts = info.tail_counts
    cols = np.flatnonzero(counts > 0)[::13][:120]
    ws, count = design.with_working_set(counts, cols)
    assert 512 < count <= 4096                 # several chunks, one short
    g, h = ws.tail_stats_ws(s, w)
    on = np.zeros(design.tail_cols, bool)
    on[cols] = True
    np.testing.assert_allclose(np.asarray(g)[on], np.asarray(g_all)[on],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(h)[on], np.asarray(h_all)[on],
                               rtol=1e-5, atol=1e-5)
    assert not np.asarray(g)[~on].any() and not np.asarray(h)[~on].any()
    d = np.where(on, rng.normal(size=design.tail_cols), 0.0).astype(
        np.float32)
    np.testing.assert_allclose(ws.tail_matvec_ws(jnp.asarray(d)),
                               design.tail_matvec(jnp.asarray(d)),
                               rtol=1e-5, atol=1e-5)
    from repro.core.linesearch import penalty_changes
    pen = lambda b, db, pf: penalty_changes(b, db, jnp.asarray([0.25, 1.0]),
                                            0.5, 0.1, pf)
    vecs = (jnp.asarray(d), jnp.asarray(-d), jnp.ones(design.tail_cols))
    full, count = design.with_working_set(counts,
                                          np.arange(design.tail_cols))
    assert count > 4096
    np.testing.assert_allclose(full.tail_stats_ws(s, w)[0], g_all,
                               rtol=1e-6)
    np.testing.assert_allclose(ws.over_working_set(pen, *vecs),
                               full.over_working_set(pen, *vecs), rtol=1e-5)


def _reference_kkt(X, y, lambdas, betas, intercepts=None):
    """Worst KKT residual per solution in units of λ1, float64, as
    ``bench/reference/elastic_net.py`` computes it (logistic, λ2 = 0), at
    the margins X β (+ the fitted intercept)."""
    M = X @ betas.T + (0.0 if intercepts is None else intercepts[None, :])
    S = y[:, None] / (1.0 + np.exp(y[:, None] * M))
    G = X.T @ S
    out = []
    for k, (lam, b) in enumerate(zip(lambdas, betas)):
        nz = b != 0
        res = np.where(nz, np.abs(G[:, k] - lam * np.sign(b)),
                       np.maximum(np.abs(G[:, k]) - lam, 0.0))
        out.append(res.max() / lam)
    return np.asarray(out)


def _cfg(head, **kw):
    return DGLMNETConfig(head_features=head, coupling="jacobi",
                         fuse_superstep=True, tile_size=T, tol=1e-10,
                         max_outer=300, **kw)


@pytest.fixture(scope="module")
def grid(data):
    """Five λ down to 0.05 λ_max (about 90 nonzeros on these 4,096 rows),
    where the brick layout's path converges too."""
    rows, y, _ = data
    lmax = GLMSolver(rows, y, config=_cfg(H)).lambda_max()
    return lmax * np.logspace(0, -1.3, 5)


@pytest.fixture(scope="module")
def path(data, grid):
    rows, y, _ = data
    return GLMSolver(rows, y, config=_cfg(H)).fit_path(lambdas=grid)


def test_path_meets_reference_kkt(data, grid, path):
    _, y, X = data
    kkt = _reference_kkt(X, y.astype(np.float64), grid, path.betas)
    assert kkt.max() <= 0.02, kkt
    assert path.nnz[-1] > 10                   # a non-degenerate path


def test_path_to_one_percent_meets_reference_kkt(data, grid):
    """The benchmark configuration's ratio, 0.01 λ_max: about 510 nonzeros
    on these 4,096 rows.  (The brick layout stops early there, with KKT
    residuals near 0.25: its outer loop compares whole float32 sums.)"""
    rows, y, X = data
    lambdas = grid[0] * np.logspace(0, -2, 5)
    res = GLMSolver(rows, y, config=_cfg(H)).fit_path(lambdas=lambdas)
    kkt = _reference_kkt(X, y.astype(np.float64), lambdas, res.betas)
    assert kkt.max() <= 0.02, kkt
    assert res.nnz[-1] > 300


def test_intercept_path_meets_reference_kkt(data):
    """An unpenalized intercept on hashed rows, asked for by the
    configuration: λ_max is taken at the intercept-only model, every
    solution down to 0.01 λ_max meets the KKT conditions at its fitted
    intercept, and the intercept's own gradient vanishes."""
    rows, y, X = data
    s = GLMSolver(rows, y, config=_cfg(H, fit_intercept=True))
    assert s.fit_intercept
    assert not GLMSolver(rows, y, config=_cfg(H, fit_intercept=True),
                         fit_intercept=False).fit_intercept
    t = (y > 0).astype(np.float64)
    lmax = s.lambda_max()
    assert lmax == pytest.approx(np.abs(X.T @ (t - t.mean())).max(),
                                 rel=1e-4)
    lambdas = lmax * np.logspace(0, -2, 5)
    res = s.fit_path(lambdas=lambdas)
    kkt = _reference_kkt(X, y.astype(np.float64), lambdas, res.betas,
                         res.intercepts)
    assert kkt.max() <= 0.02, kkt
    M = X @ res.betas.T + res.intercepts[None, :]
    g0 = np.sum(y[:, None] / (1.0 + np.exp(y[:, None] * M)), axis=0)
    assert (np.abs(g0) / lambdas).max() <= 0.03, g0 / lambdas
    assert res.nnz[-1] > 300


def test_path_objective_matches_bricks(data, grid, path):
    rows, y, _ = data
    cfg = DGLMNETConfig(coupling="jacobi", fuse_superstep=True, tile_size=T,
                        tol=1e-10, max_outer=300)
    bricks = GLMSolver(rows.to_coo(), y, config=cfg).fit_path(lambdas=grid)
    gap = np.abs(path.f - bricks.f) / np.abs(bricks.f)
    assert gap.max() <= 1e-5, gap


@pytest.mark.parametrize("head", [P, T], ids=["all_head", "one_tile_head"])
def test_head_width_edges_reach_the_same_optimum(data, grid, path, head):
    rows, y, X = data
    other = GLMSolver(rows, y, config=_cfg(head)).fit_path(lambdas=grid)
    gap = np.abs(other.f - path.f) / np.abs(path.f)
    assert gap.max() <= 1e-5, gap
    kkt = _reference_kkt(X, y.astype(np.float64), grid, other.betas)
    assert kkt.max() <= 0.05, kkt


def test_tail_counts_its_working_set(data, grid):
    """A one-tile head leaves informative features in the tail: the
    session counts the working-set entries its supersteps read."""
    rows, y, _ = data
    s = GLMSolver(rows, y, config=_cfg(T))
    s.fit_path(lambdas=grid)
    assert s.launch_stats["tail_entries"] > 0
    assert s.launch_stats["sweep_tile_launches"] <= s.launch_stats[
        "supersteps"]


def test_sparse_rows_need_head_features(data):
    rows, y, _ = data
    with pytest.raises(ValueError, match="head_features"):
        GLMSolver(rows, y, config=DGLMNETConfig(coupling="jacobi",
                                                tile_size=T))
    with pytest.raises(ValueError, match="fused Jacobi"):
        GLMSolver(rows, y, config=dataclasses.replace(_cfg(H),
                                                      fuse_superstep=False))


def test_predict_and_score_sparse_rows(data, path):
    rows, y, X = data
    s = GLMSolver(rows, y, config=_cfg(H))
    beta = path.betas[-1]
    np.testing.assert_allclose(s.predict(rows, beta=beta, kind="link"),
                               X @ beta, rtol=1e-5, atol=1e-5)
    acc = s.score(rows, y, beta=beta)
    assert acc == pytest.approx(np.mean(np.sign(X @ beta + 1e-30) == y),
                                abs=1e-3)


def test_device_bytes_count_head_and_tail(data):
    rows, y, _ = data
    s = GLMSolver(rows, y, config=_cfg(H))
    d = s._Xs
    head = 2 * d.shape[0] * H * 4
    tail = (d.tail_ids.size + d.tail_vals.size + d.ws_rows.size
            + d.ws_cols.size + d.ws_vals.size + d.ws_colset.size + 1) * 4
    rows_vecs = 3 * d.shape[0] * 4
    assert sum(s.device_bytes().values()) == head + tail + rows_vecs


def test_libsvm_fixed_width_file_fits_and_scores(tmp_path):
    """A LIBSVM file with a fixed number of nonzeros a row reads into
    ``SparseRows``, trains through the normal front door and scores with
    the same rows."""
    from repro.data.sparse import SparseCOO
    from repro.io.libsvm import LibsvmReader, write_libsvm
    rng = np.random.default_rng(3)
    n, p, k = 600, 300, 6
    cols = np.stack([rng.choice(p, k, replace=False) for _ in range(n)])
    rows = np.repeat(np.arange(n), k)
    coo = SparseCOO(rows, cols.ravel(), np.ones(n * k, np.float32), (n, p))
    X = coo.to_dense()
    y = np.where(X[:, :20].sum(1) + rng.normal(size=n) > 0.4, 1.0, -1.0)
    path = write_libsvm(tmp_path / "fixed.libsvm", coo, y)
    reader = LibsvmReader(path, chunk_rows=128)
    got = reader.to_rows()
    assert got.shape == (n, p) and got.ids.shape[1] == k
    np.testing.assert_array_equal(_dense(got), X)
    s = GLMSolver(got, reader.labels(), config=DGLMNETConfig(
        head_features=128, coupling="jacobi", tile_size=128, tol=1e-10))
    res = s.fit_path(n_lambdas=4, lam_ratio=0.1)
    beta = res.betas[-1]
    assert np.count_nonzero(beta) > 0
    np.testing.assert_allclose(s.predict(got, kind="link"), X @ beta,
                               rtol=1e-5, atol=1e-5)
    assert s.score(got, y) == pytest.approx(
        np.mean(np.where(X @ beta > 0, 1.0, -1.0) == y), abs=1e-9)


def _pallas_calls(jaxpr, out):
    """Every ``pallas_call`` equation in a jaxpr and its sub-jaxprs."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn)
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _pallas_calls(sub, out)
    return out


def _superstep_calls(solver):
    import jax
    X, _ = solver._round_design(None)
    jp = jax.make_jaxpr(solver._superstep)(
        X, solver._ys, solver._wobs, solver._offsets, solver._budget_const,
        jnp.zeros(2), solver._active_ones, solver._penf,
        solver._init_state(None))
    return {e.params["jaxpr"].debug_info.func_src_info.split()[0]:
            len(e.invars) for e in _pallas_calls(jp.jaxpr, [])}


def test_dense_superstep_keeps_six_margin_ls_operands(data):
    """The dense layout's fused superstep launches ``margin_ls`` with its
    six operands (no tail delta); a head/tail design's adds the seventh."""
    from repro.data import synthetic
    ds = synthetic.make_dense(n=2048, p=512, k_true=8, family="logistic",
                              seed=5)
    cfg = DGLMNETConfig(tile_size=256, coupling="jacobi",
                        kernel_backend="pallas")
    dense = _superstep_calls(GLMSolver(ds.train.X, ds.train.y, config=cfg))
    rows, y, _ = data
    ht = _superstep_calls(GLMSolver(rows, y, config=dataclasses.replace(
        _cfg(H), kernel_backend="pallas")))
    margin = [k for k in dense if "margin_ls" in k]
    assert len(margin) == 1 and dense[margin[0]] == 6, dense
    assert ht[margin[0]] == 7, ht
    gram = [k for k in dense if "stats_gram_solve" in k]
    assert dense[gram[0]] == ht[gram[0]] == 8
