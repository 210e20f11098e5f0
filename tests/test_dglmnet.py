"""d-GLMNET end-to-end behaviour (single device): convergence to the
FISTA-oracle optimum across loss families and couplings, trust-region
sparsity (paper Section 4), line-search/μ dynamics, padding inertness."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dglmnet, glm, prox_ref
from repro.core.dglmnet import DGLMNETConfig
from repro.data import synthetic


def _obj(family, X, y, beta, lam1, lam2):
    return float(glm.objective(glm.get_family(family), jnp.asarray(y),
                               jnp.asarray(X), jnp.asarray(beta),
                               lam1, lam2))


@pytest.mark.parametrize("family", ["logistic", "squared", "probit"])
@pytest.mark.parametrize("coupling", ["gauss-seidel", "jacobi"])
def test_converges_to_oracle(family, coupling):
    ds = synthetic.make_dense(n=500, p=80, family=family, seed=2)
    X, y = ds.train.X, ds.train.y
    lam1, lam2 = 0.7, 0.4
    cfg = DGLMNETConfig(family=family, lam1=lam1, lam2=lam2, tile_size=16,
                        coupling=coupling, max_outer=120, tol=1e-12)
    res = dglmnet.fit(X, y, cfg)
    beta_o, hist = prox_ref.fit_fista(X, y, family=family, lam1=lam1,
                                      lam2=lam2, max_iter=4000)
    f_d = _obj(family, X, y, res.beta, lam1, lam2)
    f_o = hist[-1]
    assert f_d <= f_o + 1e-3 * max(1.0, abs(f_o)), (f_d, f_o)


def test_objective_monotone_decrease():
    """The Armijo rule guarantees monotone descent (paper Theorem via
    Tseng-Yun)."""
    ds = synthetic.make_dense(n=400, p=60, seed=3)
    cfg = DGLMNETConfig(lam1=1.0, lam2=0.1, tile_size=16, max_outer=40)
    res = dglmnet.fit(ds.train.X, ds.train.y, cfg)
    f = res.history["f"]
    assert all(f[i + 1] <= f[i] + 1e-5 * max(1, abs(f[i]))
               for i in range(len(f) - 1)), f


def test_sparsity_increases_with_lam1():
    ds = synthetic.make_dense(n=400, p=100, k_true=10, seed=4)
    nnzs = []
    for lam1 in (0.1, 2.0, 20.0):
        cfg = DGLMNETConfig(lam1=lam1, lam2=0.0, tile_size=32, max_outer=60)
        res = dglmnet.fit(ds.train.X, ds.train.y, cfg)
        nnzs.append(int((res.beta != 0).sum()))
    assert nnzs[0] >= nnzs[1] >= nnzs[2]
    # lam1 >= ||X^T s(0)||_inf / ... large enough -> all-zero solution
    cfg = DGLMNETConfig(lam1=1e5, lam2=0.0, tile_size=32, max_outer=10)
    res = dglmnet.fit(ds.train.X, ds.train.y, cfg)
    assert (res.beta == 0).all()


def test_adaptive_mu_reacts_to_rejected_steps():
    """Algorithm 1 lines 8-12: α<1 ⇒ μ grows; α=1 ⇒ μ shrinks toward 1."""
    ds = synthetic.make_dense(n=300, p=120, rho=0.95, seed=5)  # correlated!
    cfg = DGLMNETConfig(lam1=0.5, lam2=0.0, tile_size=8,
                        coupling="jacobi", max_outer=40)
    res = dglmnet.fit(ds.train.X, ds.train.y, cfg)
    alphas = np.asarray(res.history["alpha"])
    mus = np.asarray(res.history["mu"])
    # whenever a step was rejected, the NEXT mu must be >= current
    for i in range(len(alphas) - 1):
        if alphas[i] < 1.0:
            assert mus[i] >= (mus[i - 1] if i else 1.0)
    assert mus.min() >= 1.0


def test_exact_zeros_from_unit_steps():
    """Section 4: sparsity comes from α=1 steps — solution coordinates are
    EXACT zeros, not small floats."""
    ds = synthetic.make_dense(n=500, p=100, k_true=5, seed=6)
    cfg = DGLMNETConfig(lam1=5.0, lam2=0.0, tile_size=32, max_outer=80)
    res = dglmnet.fit(ds.train.X, ds.train.y, cfg)
    zeros = res.beta == 0.0
    assert zeros.sum() > 50
    assert np.abs(res.beta[~zeros]).min() > 1e-8


def test_feature_padding_is_inert():
    ds = synthetic.make_dense(n=200, p=37, seed=7)   # 37 % 16 != 0
    cfg = DGLMNETConfig(lam1=0.3, lam2=0.1, tile_size=16, max_outer=50)
    res = dglmnet.fit(ds.train.X, ds.train.y, cfg)
    assert res.beta.shape == (37,)
    beta_o, hist = prox_ref.fit_fista(ds.train.X, ds.train.y,
                                      lam1=0.3, lam2=0.1, max_iter=3000)
    f_d = _obj("logistic", ds.train.X, ds.train.y, res.beta, 0.3, 0.1)
    assert f_d <= hist[-1] + 1e-3 * abs(hist[-1])


def test_poisson_family_fits():
    ds = synthetic.make_dense(n=400, p=30, family="poisson", seed=8)
    cfg = DGLMNETConfig(family="poisson", lam1=0.1, lam2=0.5, tile_size=16,
                        max_outer=60, nu=1e-4)
    res = dglmnet.fit(ds.train.X, ds.train.y, cfg)
    f = res.history["f"]
    assert f[-1] < f[0]
    assert np.isfinite(res.beta).all()


def test_fit_path_single_lambda_matches_cold_fit():
    """A fit_path evaluated at any single λ equals a cold fit at that λ
    (warm starts + screening must not change the solution)."""
    from repro.core.solver import GLMSolver
    ds = synthetic.make_dense(n=400, p=64, seed=10)
    X, y = ds.train.X, ds.train.y
    cfg = DGLMNETConfig(tile_size=16, max_outer=150, tol=1e-12)
    s = GLMSolver(X, y, config=cfg)
    path = s.fit_path(n_lambdas=5, lam_ratio=1e-2)
    for k in (1, 4):
        lam1 = float(path.lambdas[k])
        cold = s.fit(lam1=lam1, lam2=0.0)
        f_cold = _obj("logistic", X, y, cold.beta, lam1, 0.0)
        f_warm = _obj("logistic", X, y, path.betas[k], lam1, 0.0)
        assert f_warm <= f_cold + 1e-5 * max(1.0, abs(f_cold)), (k, f_warm)
