"""repro.serve: artifacts, fused sparse scoring, micro-batching
(DESIGN.md §7).

Covers the PR-5 contracts: artifact save→load→score round-trip parity
with ``solver.predict`` on all four families (with intercept +
standardize + offset), active-set-compacted ≡ full-β scoring, int8
margins within the documented shared-scale bound, kernel ≡ oracle to
≤ 1e-5, the batcher's bounded shape-bucket set and deadline flush, and
the estimator save/load + SparseCOO routing satellites.
"""
import json

import numpy as np
import pytest

from repro.core.dglmnet import DGLMNETConfig
from repro.core.solver import GLMSolver
from repro.data.sparse import SparseCOO
from repro.glm import ElasticNetGLM, LogisticRegressionCD
from repro.serve import (MicroBatcher, ScoringEngine, artifact_bytes,
                         load_artifact, quantize_int8, save_artifact)
from repro.serve import artifact as artifact_lib
from repro.serve.batcher import _bucket_up
from repro.serve.engine import coo_to_requests

FAMILIES = ("logistic", "squared", "probit", "poisson")


def _problem(family, n=120, p=24, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, p)).astype(np.float32)
    beta = np.zeros(p, np.float32)
    beta[: p // 4] = rng.normal(size=p // 4)
    m = X @ beta + 0.1 * rng.normal(size=n)
    if family in ("logistic", "probit"):
        y = np.where(m > 0, 1.0, -1.0)
    elif family == "poisson":
        y = rng.poisson(np.exp(np.clip(m, None, 3.0)))
    else:
        y = m
    return X, np.asarray(y, np.float32), rng


def _fit(family, X, y, **kw):
    solver = GLMSolver(X, y, family=family,
                       config=DGLMNETConfig(tile_size=8, max_outer=60,
                                            tol=1e-9), **kw)
    solver.fit(lam1=0.05, lam2=0.01)
    return solver


def _sparse_requests(rng, n_req, p, nnz_max=10):
    reqs = []
    for _ in range(n_req):
        k = int(rng.integers(1, nnz_max))
        idx = rng.choice(p, size=k, replace=False)
        reqs.append((idx, rng.normal(size=k).astype(np.float32)))
    return reqs


# ---------------------------------------------------------------- artifacts


@pytest.mark.parametrize("family", FAMILIES)
def test_roundtrip_parity_with_solver_predict(tmp_path, family):
    """save → load → engine score == solver.predict, all four families,
    under intercept + standardization + a prediction offset."""
    X, y, rng = _problem(family)
    solver = _fit(family, X, y, fit_intercept=True, standardize=True)
    art = solver.save(tmp_path / family)
    eng = ScoringEngine(load_artifact(art))
    X_new = rng.normal(size=(17, X.shape[1])).astype(np.float32)
    off = rng.normal(size=17).astype(np.float32) * 0.1
    for kind in ("link", "response"):
        want = solver.predict(X_new, offset=off, kind=kind)
        got = eng.score_dense(X_new, kind=kind, offset=off)[:, 0]
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_artifact_is_original_scale(tmp_path):
    """Standardization moments are folded into the exported coefficients:
    the artifact scores RAW feature values correctly."""
    X, y, rng = _problem("squared")
    solver = _fit("squared", X, y, fit_intercept=True, standardize=True)
    eng = ScoringEngine(load_artifact(solver.save(tmp_path / "m")))
    m = eng.score_dense(X, kind="link")[:, 0]
    want = X @ solver.beta_ + solver.intercept_
    np.testing.assert_allclose(m, want, atol=1e-5)
    assert load_artifact(tmp_path / "m").standardized


def test_versioning_rejects_unknown(tmp_path):
    save_artifact(tmp_path / "m", betas=np.ones((1, 3), np.float32),
                  family="squared")
    mf = tmp_path / "m" / artifact_lib.MANIFEST
    rec = json.loads(mf.read_text())
    rec["version"] = artifact_lib.VERSION + 1
    mf.write_text(json.dumps(rec))
    with pytest.raises(ValueError, match="newer"):
        load_artifact(tmp_path / "m")
    rec["version"] = artifact_lib.VERSION
    rec["format"] = "something-else"
    mf.write_text(json.dumps(rec))
    with pytest.raises(ValueError, match="format"):
        load_artifact(tmp_path / "m")
    rec["format"] = artifact_lib.FORMAT
    rec["intercepts"] = [0.0, 0.0]          # 2 intercepts, 1 output
    mf.write_text(json.dumps(rec))
    with pytest.raises(ValueError, match="intercepts"):
        load_artifact(tmp_path / "m")


def test_servable_model_is_immutable(tmp_path):
    save_artifact(tmp_path / "m", betas=np.ones((2, 3), np.float32),
                  family="squared")
    m = load_artifact(tmp_path / "m")
    with pytest.raises(ValueError):
        m.betas[0, 0] = 5.0


def test_int8_quantization_bounds(tmp_path):
    """Shared-scale int8: per-element error ≤ scale/2; scored margins
    within (scale/2)·‖x‖₁ of fp32; artifact ≥ 2× smaller at real sizes."""
    rng = np.random.default_rng(3)
    K, p = 6, 800
    betas = (rng.normal(size=(K, p)) *
             (rng.random((K, p)) < 0.3)).astype(np.float32)
    q, scale = quantize_int8(betas)
    assert np.abs(q.astype(np.float32) * scale - betas).max() \
        <= scale / 2 + 1e-7
    # all-zero table round-trips to exactly zero
    qz, sz = quantize_int8(np.zeros((2, 4), np.float32))
    assert (qz == 0).all() and (qz.astype(np.float32) * sz == 0).all()

    b0 = rng.normal(size=K).astype(np.float32)
    save_artifact(tmp_path / "fp32", betas=betas, intercepts=b0,
                  family="logistic")
    save_artifact(tmp_path / "int8", betas=betas, intercepts=b0,
                  family="logistic", quantize="int8")
    assert artifact_bytes(tmp_path / "fp32") \
        >= 2.0 * artifact_bytes(tmp_path / "int8")

    m8 = load_artifact(tmp_path / "int8")
    assert m8.quant["mode"] == "int8"
    e32 = ScoringEngine(load_artifact(tmp_path / "fp32"))
    e8 = ScoringEngine(m8)
    reqs = _sparse_requests(rng, 40, p, nnz_max=30)
    m_fp = e32.score_sparse(reqs, kind="link")
    m_i8 = e8.score_sparse(reqs, kind="link")
    for i, (_, val) in enumerate(reqs):
        bound = m8.margin_error_bound(np.abs(val).sum())
        assert np.abs(m_fp[i] - m_i8[i]).max() <= bound + 1e-6


# ------------------------------------------------------------------ engine


def test_active_set_compaction_equals_full_beta():
    rng = np.random.default_rng(4)
    K, p = 3, 60
    betas = (rng.normal(size=(K, p)) *
             (rng.random((K, p)) < 0.2)).astype(np.float32)
    b0 = rng.normal(size=K).astype(np.float32)
    m = artifact_lib.ServableModel(betas=betas, intercepts=b0,
                                   family="logistic")
    eng = ScoringEngine(m)
    assert eng.n_active == int((betas != 0).any(axis=0).sum()) < p
    X = rng.normal(size=(11, p)).astype(np.float32)
    full = X @ betas.T + b0
    np.testing.assert_allclose(eng.score_dense(X, kind="link"), full,
                               atol=1e-5)
    # sparse path through the kernel agrees too
    mask = rng.random((11, p)) < 0.25
    Xs = (X * mask).astype(np.float32)
    coo = SparseCOO(*np.nonzero(Xs), Xs[np.nonzero(Xs)], Xs.shape)
    np.testing.assert_allclose(eng.score_coo(coo, kind="link"),
                               Xs @ betas.T + b0, atol=1e-5)


def test_multi_output_path_artifact(tmp_path):
    """A λ-path exports as one multi-output artifact; one launch scores
    every λ column identically to per-λ scoring."""
    X, y, rng = _problem("logistic", n=150, p=20)
    solver = GLMSolver(X, y, family="logistic",
                       config=DGLMNETConfig(tile_size=8, max_outer=40),
                       fit_intercept=True)
    path = solver.fit_path(n_lambdas=5, lam_ratio=1e-2)
    art = solver.save(tmp_path / "path", path_result=path)
    m = load_artifact(art)
    assert m.n_outputs == 5
    np.testing.assert_allclose(m.lambdas, path.lambdas, rtol=1e-6)
    eng = ScoringEngine(m)
    X_new = rng.normal(size=(9, 20)).astype(np.float32)
    out = eng.score_dense(X_new, kind="link")
    assert out.shape == (9, 5)
    for k in range(5):
        want = X_new @ path.betas[k] + path.intercepts[k]
        np.testing.assert_allclose(out[:, k], want, atol=1e-5)
    # subset serving: the selected λ only (different matmul shape → agrees
    # with the 5-output program only to f32 ULP at margin scale)
    eng1 = ScoringEngine(m, outputs=[3])
    np.testing.assert_allclose(eng1.score_dense(X_new, kind="link")[:, 0],
                               out[:, 3], rtol=1e-6, atol=1e-6)


def test_engine_out_of_range_features_score_zero():
    m = artifact_lib.ServableModel(
        betas=np.ones((1, 4), np.float32),
        intercepts=np.zeros(1, np.float32), family="squared")
    eng = ScoringEngine(m)
    out = eng.score_sparse([(np.array([0, 9999, -3]),
                             np.array([1.0, 5.0, 5.0], np.float32))],
                           kind="link")
    assert out[0, 0] == pytest.approx(1.0)


def test_score_coo_chunked_parity():
    """Chunked COO scoring (small chunk_rows, ragged tail, one skewed
    wide row) matches the dense product — no whole-input densification."""
    rng = np.random.default_rng(7)
    p = 40
    betas = (rng.normal(size=(2, p)) *
             (rng.random((2, p)) < 0.4)).astype(np.float32)
    m = artifact_lib.ServableModel(betas=betas,
                                   intercepts=np.zeros(2, np.float32),
                                   family="squared")
    eng = ScoringEngine(m)
    X = (rng.normal(size=(23, p)) *
         (rng.random((23, p)) < 0.1)).astype(np.float32)
    X[5] = rng.normal(size=p)          # one near-dense row
    coo = SparseCOO(*np.nonzero(X), X[np.nonzero(X)], X.shape)
    off = rng.normal(size=23).astype(np.float32)
    for cr in (4, 7, 64):
        out = eng.score_coo(coo, kind="link", offset=off, chunk_rows=cr)
        np.testing.assert_allclose(out, X @ betas.T + off[:, None],
                                   atol=1e-5)
    # a tiny launch budget forces the wide row into its own window and
    # must not change the result (the B·J·K memory cap)
    out = eng.score_coo(coo, kind="link", offset=off, launch_budget=64)
    np.testing.assert_allclose(out, X @ betas.T + off[:, None], atol=1e-5)


def test_servable_model_does_not_freeze_caller_arrays():
    mine = np.ones((1, 4), np.float32)
    artifact_lib.ServableModel(betas=mine,
                               intercepts=np.zeros(1, np.float32),
                               family="squared")
    mine[0, 0] = 7.0                   # caller's array stays writable


def test_coo_to_requests_handles_empty_rows():
    coo = SparseCOO(np.array([0, 2, 2]), np.array([1, 0, 3]),
                    np.array([1.0, 2.0, 3.0], np.float32), (4, 5))
    reqs = coo_to_requests(coo)
    assert len(reqs) == 4
    assert len(reqs[1][0]) == 0 and len(reqs[3][0]) == 0
    assert list(reqs[2][1]) == [2.0, 3.0]


# -------------------------------------------------------------- kernel


def _predict_tile_parity(family, kind, A, L, B, J, seed, scale=1.0):
    import jax.numpy as jnp

    from repro.kernels import ops, ref
    rng = np.random.default_rng(seed)
    table = np.zeros((A + 1, L), np.float32)
    table[:-1] = rng.normal(size=(A, L)) * scale
    slots = rng.integers(0, A + 1, size=(B, J)).astype(np.int32)
    vals = rng.normal(size=(B, J)).astype(np.float32)
    b0 = (rng.normal(size=L) * scale).astype(np.float32)
    o = ref.predict_tile(jnp.asarray(slots), jnp.asarray(vals),
                         jnp.asarray(table), jnp.asarray(b0).reshape(1, -1),
                         family, kind=kind)
    k = ops.predict_tile(jnp.asarray(slots), jnp.asarray(vals),
                         jnp.asarray(table), b0, family, kind=kind,
                         backend="pallas")
    assert k.shape == (B, L)
    np.testing.assert_allclose(np.asarray(k), np.asarray(o), atol=1e-5)


@pytest.mark.parametrize("family", FAMILIES)
@pytest.mark.parametrize("kind", ("link", "response"))
def test_predict_tile_kernel_matches_oracle(family, kind):
    _predict_tile_parity(family, kind, A=19, L=3, B=11, J=7, seed=5)


@pytest.mark.parametrize("family", FAMILIES)
@pytest.mark.parametrize("kind", ("link", "response"))
def test_predict_tile_kernel_multi_block_table_matches_oracle(
        family, kind, monkeypatch):
    """A table of several row blocks: 300 outputs pad to 384 lanes, and a
    20-row block budget rounds down to 16 rows, so the 46 table rows span
    three blocks that the 99 (slot, value) pairs all reach."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_TABLE_BLOCK_BYTES", 20 * 4 * 384)
    _predict_tile_parity(family, kind, A=45, L=300, B=11, J=9, seed=7,
                         scale=0.2)


def test_predict_tile_unknown_family_falls_back_to_oracle():
    import jax.numpy as jnp

    from repro.kernels import ops
    slots = np.array([[0, 1, 1]], np.int32)
    vals = np.ones((1, 3), np.float32)
    table = np.array([[2.0], [0.0]], np.float32)   # row 1 is the zero row
    # a family with no Pallas link body must silently take the oracle path
    # even when the pallas backend is requested (same rule as glm_stats)
    out = ops.predict_tile(jnp.asarray(slots), jnp.asarray(vals),
                           jnp.asarray(table), np.zeros(1, np.float32),
                           "no-such-family", kind="link", backend="pallas")
    assert np.asarray(out)[0, 0] == pytest.approx(2.0)


# -------------------------------------------------------------- batcher


def _toy_engine(p=30, K=2, seed=6):
    rng = np.random.default_rng(seed)
    betas = (rng.normal(size=(K, p)) *
             (rng.random((K, p)) < 0.5)).astype(np.float32)
    m = artifact_lib.ServableModel(
        betas=betas, intercepts=np.zeros(K, np.float32), family="squared")
    return ScoringEngine(m), betas, rng


def test_bucket_up():
    assert _bucket_up(1, (1, 4, 16)) == 1
    assert _bucket_up(5, (1, 4, 16)) == 16
    assert _bucket_up(99, (1, 4, 16)) == 99      # outsized: its own shape


def test_batcher_results_and_bounded_shapes():
    eng, betas, rng = _toy_engine()
    reqs = _sparse_requests(rng, 50, 30, nnz_max=12)
    with MicroBatcher(eng, max_delay_ms=5.0, batch_buckets=(1, 4, 16),
                      nnz_buckets=(4, 16), kind="link") as b:
        b.warmup()
        n_shapes = eng.compile_count
        assert n_shapes <= 3 * 2
        outs = np.stack([h.get(timeout=30.0) for h in
                         [b.submit(i, v) for i, v in reqs]])
        st = b.stats()
    # steady state compiled nothing new (the bounded-bucket contract)
    assert eng.compile_count == n_shapes
    exact = np.stack([betas[:, i] @ v if len(i) else np.zeros(2)
                      for i, v in
                      [(np.asarray(i), np.asarray(v)) for i, v in reqs]])
    np.testing.assert_allclose(outs, exact, atol=1e-5)
    assert st["n_requests"] == 50
    assert st["p50_ms"] is not None and st["p99_ms"] >= st["p50_ms"]
    assert st["rows_per_s"] > 0 and st["mean_batch"] >= 1.0


def test_batcher_deadline_flush_underfull():
    """A lone request must be served within ~max_delay even though the
    batch bucket never fills."""
    eng, betas, _ = _toy_engine()
    with MicroBatcher(eng, max_delay_ms=10.0, kind="link") as b:
        h = b.submit(np.array([2]), np.array([1.0], np.float32))
        out = h.get(timeout=5.0)
    np.testing.assert_allclose(out, betas[:, 2], atol=1e-6)


def test_batcher_offset_and_response():
    eng, betas, _ = _toy_engine()
    with MicroBatcher(eng, max_delay_ms=5.0, kind="link") as b:
        h = b.submit(np.array([0]), np.array([2.0], np.float32),
                     offset=1.5)
        out = h.get(timeout=5.0)
    np.testing.assert_allclose(out, 2.0 * betas[:, 0] + 1.5, atol=1e-6)


def test_batcher_survives_engine_failure():
    """A failing flush must error ITS handles and leave the flusher alive
    for subsequent traffic — one bad batch cannot brick the server."""
    eng, betas, _ = _toy_engine()
    b = MicroBatcher(eng, max_delay_ms=2.0, kind="link")
    orig = eng.score_sparse
    calls = {"n": 0}

    def flaky(*a, **k):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("transient engine failure")
        return orig(*a, **k)

    eng.score_sparse = flaky
    try:
        h1 = b.submit(np.array([1]), np.array([1.0], np.float32))
        with pytest.raises(RuntimeError, match="transient"):
            h1.get(timeout=10.0)
        h2 = b.submit(np.array([1]), np.array([1.0], np.float32))
        out = h2.get(timeout=10.0)          # flusher thread still serving
        np.testing.assert_allclose(out, betas[:, 1], atol=1e-6)
        assert b.stats()["n_failed"] == 1
    finally:
        eng.score_sparse = orig
        b.close()


def test_request_length_mismatch_rejected():
    """A short value vector must raise, not numpy-broadcast into every
    slot and score garbage — at the engine and at submit time."""
    eng, _, _ = _toy_engine()
    with pytest.raises(ValueError, match="disagree"):
        eng.score_sparse([(np.array([0, 1]), np.array([1.0], np.float32))])
    with MicroBatcher(eng, kind="link") as b:
        with pytest.raises(ValueError, match="disagree"):
            b.submit(np.array([0, 1]), np.array([1.0], np.float32))


def test_warmup_covers_offset_link_path():
    """warmup() on a response batcher also precompiles the link programs
    that offset-bearing requests take — offset traffic re-jits nothing."""
    eng, _, _ = _toy_engine()
    with MicroBatcher(eng, max_delay_ms=5.0, batch_buckets=(1, 4),
                      nnz_buckets=(4,), kind="response") as b:
        b.warmup()
        n0 = eng.compile_count
        assert n0 == 2 * 2 * 1              # (link + response) per bucket
        h = b.submit(np.array([0]), np.array([1.0], np.float32),
                     offset=0.5)
        h.get(timeout=10.0)
        assert eng.compile_count == n0


def test_submit_after_close_raises():
    eng, _, _ = _toy_engine()
    b = MicroBatcher(eng, kind="link")
    b.close()
    with pytest.raises(RuntimeError, match="closed"):
        b.submit(np.array([0]), np.array([1.0], np.float32))


def test_batch1_baseline_matches_coalesced_results():
    eng, betas, rng = _toy_engine()
    reqs = _sparse_requests(rng, 8, 30, nnz_max=6)
    b = MicroBatcher(eng, batch_buckets=(1,), kind="link")
    singles = np.stack([b.score_one(i, v) for i, v in reqs])
    b.close()
    with MicroBatcher(eng, max_delay_ms=5.0, kind="link") as b2:
        coalesced = np.stack([h.get(timeout=30.0) for h in
                              [b2.submit(i, v) for i, v in reqs]])
    np.testing.assert_allclose(singles, coalesced, atol=1e-5)


# --------------------------------------------------- solver / estimator


def test_solver_sparse_coo_predict_routes_through_engine():
    X, y, rng = _problem("logistic", n=100, p=16)
    solver = _fit("logistic", X, y, fit_intercept=True)
    mask = rng.random((30, 16)) < 0.3
    Xs = (rng.normal(size=(30, 16)) * mask).astype(np.float32)
    coo = SparseCOO(*np.nonzero(Xs), Xs[np.nonzero(Xs)], Xs.shape)
    for kind in ("link", "response"):
        np.testing.assert_allclose(solver.predict(coo, kind=kind),
                                   solver.predict(Xs, kind=kind),
                                   atol=1e-5)
    assert solver._serve_cache is not None          # engine path was taken


def test_logistic_load_from_solver_artifact(tmp_path):
    """GLMSolver.save writes no frontend label state; a classifier loaded
    from it must still predict — with the solver's {-1, +1} encoding."""
    X, y, rng = _problem("logistic")
    solver = _fit("logistic", X, y, fit_intercept=True)
    solver.save(tmp_path / "s")
    clf = LogisticRegressionCD.load(tmp_path / "s")
    pred = clf.predict(X)
    assert set(np.unique(pred)) <= {-1.0, 1.0}
    np.testing.assert_allclose(clf.decision_function(X),
                               solver.predict(X, kind="link"), atol=1e-5)
    assert clf.predict_proba(X).shape == (len(X), 2)


def test_estimator_save_load_roundtrip(tmp_path):
    X, y, rng = _problem("logistic", n=140, p=20)
    y01 = (y > 0).astype(int)
    clf = LogisticRegressionCD(lam1=0.05, tile_size=8, max_outer=60)
    clf.fit(X, y01)
    clf.save(tmp_path / "clf")
    clf2 = LogisticRegressionCD.load(tmp_path / "clf")
    np.testing.assert_allclose(clf2.coef_, clf.coef_, atol=1e-7)
    assert clf2.intercept_ == pytest.approx(clf.intercept_)
    assert (clf2.classes_ == clf.classes_).all()
    X_new = rng.normal(size=(25, 20)).astype(np.float32)
    assert (clf2.predict(X_new) == clf.predict(X_new)).all()
    np.testing.assert_allclose(clf2.predict_proba(X_new),
                               clf.predict_proba(X_new), atol=1e-5)
    assert clf2.score(X, y01) == pytest.approx(clf.score(X, y01))
    # loaded estimator serves SparseCOO through the fused path
    mask = rng.random((10, 20)) < 0.4
    Xs = (X_new[:10] * mask).astype(np.float32)
    coo = SparseCOO(*np.nonzero(Xs), Xs[np.nonzero(Xs)], Xs.shape)
    np.testing.assert_allclose(clf2.decision_function(coo),
                               clf2.decision_function(Xs), atol=1e-5)


def test_estimator_load_guards(tmp_path):
    X, y, _ = _problem("squared", n=80, p=10)
    est = ElasticNetGLM(family="squared", lam1=0.05, tile_size=8,
                        max_outer=40)
    est.fit(X, y)
    est.save(tmp_path / "sq")
    with pytest.raises(ValueError, match="fixed to the 'logistic'"):
        LogisticRegressionCD.load(tmp_path / "sq")
    est2 = ElasticNetGLM.load(tmp_path / "sq")
    np.testing.assert_allclose(est2.predict(X), est.predict(X), atol=1e-5)
    assert est2.score(X, y) == pytest.approx(est.score(X, y), abs=1e-5)
    # unfitted estimators still refuse to predict
    with pytest.raises(ValueError, match="not fitted"):
        ElasticNetGLM(family="squared").predict(X)


def test_loaded_estimator_reexport_preserves_provenance(tmp_path):
    """load → save must not overwrite manifest provenance (standardize,
    lam2, λ) with constructor defaults."""
    X, y, _ = _problem("squared", n=80, p=10)
    est = ElasticNetGLM(family="squared", lam1=0.07, lam2=0.5,
                        standardize=False, tile_size=8, max_outer=40)
    est.fit(X, y)
    est.save(tmp_path / "a")
    re_exported = ElasticNetGLM.load(tmp_path / "a")
    re_exported.save(tmp_path / "b")
    m = load_artifact(tmp_path / "b")
    assert m.standardized is False
    assert m.lam2 == pytest.approx(0.5)
    assert m.lambdas is not None and m.lambdas[0] == pytest.approx(0.07)


def test_estimator_load_rejects_multi_output(tmp_path):
    save_artifact(tmp_path / "p", betas=np.ones((3, 4), np.float32),
                  family="squared")
    with pytest.raises(ValueError, match="output columns"):
        ElasticNetGLM.load(tmp_path / "p")
