"""Per-kernel validation: Pallas (interpret mode on CPU) vs the pure-jnp
ref.py oracles, swept over shapes/dtypes, plus hypothesis property tests on
the tile-solve invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    import hypothesis
    import hypothesis.strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # parity tests below still run without it
    HAVE_HYPOTHESIS = False

from repro.core import glm
from repro.kernels import ops, ref

FAMS = ["logistic", "squared", "probit", "poisson"]


def _mk_tile(rng, n, T, mu=1.0, nu=1e-6, lam1=0.3, lam2=0.1):
    X = rng.normal(size=(n, T)).astype(np.float32)
    w = rng.uniform(0.01, 0.25, size=n).astype(np.float32)
    s = rng.normal(size=n).astype(np.float32)
    beta = (rng.normal(size=T) * 0.3).astype(np.float32)
    dbeta = np.zeros(T, np.float32)
    G = (X.T * w) @ X
    g = X.T @ (s - mu * w * (X @ dbeta))
    h = np.diag(G).copy()
    return X, w, s, beta, dbeta, G, g, h, (mu, nu, lam1, lam2)


@pytest.mark.parametrize("n,T", [(64, 8), (200, 32), (500, 128), (123, 64)])
def test_cd_tile_solve_matches_ref(n, T, rng):
    X, w, s, beta, dbeta, G, g, h, (mu, nu, l1, l2) = _mk_tile(rng, n, T)
    a = ref.cd_tile_solve(jnp.asarray(G), jnp.asarray(g), jnp.asarray(h),
                          jnp.asarray(beta), jnp.asarray(dbeta),
                          mu, nu, l1, l2)
    b = ops.cd_tile_solve(jnp.asarray(G), jnp.asarray(g), jnp.asarray(h),
                          jnp.asarray(beta), jnp.asarray(dbeta),
                          mu, nu, l1, l2, backend="pallas")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("mu", [1.0, 2.0, 8.0])
def test_tile_solve_decreases_local_model(mu, rng):
    """One tile pass must not increase the penalized quadratic model
    (exact coordinate minimization ⇒ monotone block descent)."""
    n, T = 300, 64
    nu, l1, l2 = 1e-6, 0.5, 0.2
    X, w, s, beta, dbeta, G, g, h, _ = _mk_tile(rng, n, T, mu=mu,
                                                nu=nu, lam1=l1, lam2=l2)

    def model_obj(d):
        xd = X @ d
        return (-(s @ xd) + 0.5 * mu * xd @ (w * xd) + 0.5 * nu * d @ d
                + l1 * np.abs(beta + d).sum()
                + 0.5 * l2 * ((beta + d) ** 2).sum())

    d_new = np.asarray(ref.cd_tile_solve(
        jnp.asarray(G), jnp.asarray(g), jnp.asarray(h), jnp.asarray(beta),
        jnp.asarray(dbeta), mu, nu, l1, l2))
    assert model_obj(d_new) <= model_obj(dbeta) + 1e-5


def test_tile_solve_kkt_fixed_point(rng):
    """Iterating the tile solve to convergence must satisfy the elastic-net
    KKT conditions of the local quadratic model."""
    n, T = 400, 32
    mu, nu, l1, l2 = 1.0, 1e-8, 0.4, 0.3
    X, w, s, beta, dbeta, G, g, h, _ = _mk_tile(rng, n, T, mu=mu, nu=nu,
                                                lam1=l1, lam2=l2)
    d = jnp.asarray(dbeta)
    for _ in range(60):
        g_cur = jnp.asarray(X.T @ (s - mu * w * (X @ np.asarray(d))))
        d = ref.cd_tile_solve(jnp.asarray(G), g_cur, jnp.asarray(h),
                              jnp.asarray(beta), d, mu, nu, l1, l2)
    d = np.asarray(d)
    # gradient of smooth part at d (w.r.t. u = beta + d):
    grad = -(X.T @ (s - mu * w * (X @ d))) + nu * d + l2 * (beta + d)
    u = beta + d
    on = np.abs(u) > 1e-7
    np.testing.assert_allclose(grad[on], -l1 * np.sign(u[on]), atol=5e-3)
    assert np.all(np.abs(grad[~on]) <= l1 + 5e-3)


@pytest.mark.parametrize("family", FAMS)
@pytest.mark.parametrize("n", [100, 256, 1000])
def test_glm_stats_pallas_vs_ref(family, n, rng):
    y = (rng.poisson(2.0, n) if family == "poisson"
         else rng.choice([-1.0, 1.0], n)).astype(np.float32)
    xb = rng.normal(size=n).astype(np.float32) * 2
    r1 = ops.glm_stats(jnp.asarray(y), jnp.asarray(xb), family,
                       backend="ref")
    r2 = ops.glm_stats(jnp.asarray(y), jnp.asarray(xb), family,
                       backend="pallas", block_rows=8)
    # probit: kernel uses erfc-based log Phi vs ref's log_ndtr — agree to
    # ~1e-4 rel (identical asymptotics, different polynomial approximations)
    tol = dict(rtol=3e-4, atol=3e-4) if family == "probit" \
        else dict(rtol=1e-5, atol=1e-5)
    for a, b in zip(r1, r2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), **tol)


@pytest.mark.parametrize("family", FAMS)
@pytest.mark.parametrize("K", [1, 4, 21])
def test_alpha_search_pallas_vs_ref(family, K, rng):
    n = 513
    y = (rng.poisson(2.0, n) if family == "poisson"
         else rng.choice([-1.0, 1.0], n)).astype(np.float32)
    xb = rng.normal(size=n).astype(np.float32)
    xdb = rng.normal(size=n).astype(np.float32)
    alphas = jnp.asarray(np.logspace(-3, 0, K), jnp.float32)
    a = ops.alpha_search(jnp.asarray(y), jnp.asarray(xb), jnp.asarray(xdb),
                         alphas, family, backend="ref")
    b = ops.alpha_search(jnp.asarray(y), jnp.asarray(xb), jnp.asarray(xdb),
                         alphas, family, backend="pallas", block_rows=8)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-4, atol=2e-3)


def _tile_solve_property(seed, T, lam1, mu):
    """Pallas == ref for arbitrary well-formed tiles; padded (all-zero)
    columns stay exactly zero."""
    rng = np.random.default_rng(seed)
    n = 50
    X = rng.normal(size=(n, T)).astype(np.float32)
    X[:, T // 2] = 0.0  # a dead column
    w = rng.uniform(0.0, 0.25, size=n).astype(np.float32)
    s = rng.normal(size=n).astype(np.float32)
    beta = np.zeros(T, np.float32)
    G = (X.T * w) @ X
    g = X.T @ s
    h = np.diag(G).copy()
    a = ref.cd_tile_solve(jnp.asarray(G), jnp.asarray(g), jnp.asarray(h),
                          jnp.asarray(beta), jnp.zeros(T), mu, 1e-6,
                          lam1, 0.1)
    b = ops.cd_tile_solve(jnp.asarray(G), jnp.asarray(g), jnp.asarray(h),
                          jnp.asarray(beta), jnp.zeros(T), mu, 1e-6,
                          lam1, 0.1, backend="pallas")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    assert float(a[T // 2]) == 0.0  # dead column untouched


if HAVE_HYPOTHESIS:
    @hypothesis.settings(deadline=None, max_examples=30)
    @hypothesis.given(
        seed=st.integers(0, 2**31 - 1),
        T=st.sampled_from([8, 16, 32]),
        lam1=st.floats(0.0, 5.0),
        mu=st.floats(1.0, 16.0),
    )
    def test_tile_solve_property_sweep(seed, T, lam1, mu):
        _tile_solve_property(seed, T, lam1, mu)
else:
    @pytest.mark.parametrize("seed,T,lam1,mu",
                             [(0, 16, 0.5, 2.0), (1, 8, 0.0, 1.0),
                              (2, 32, 4.0, 16.0)])
    def test_tile_solve_property_sweep(seed, T, lam1, mu):
        # fixed-case fallback when hypothesis is not installed
        _tile_solve_property(seed, T, lam1, mu)


def test_oracle_dispatches_are_recorded_on_tpu(monkeypatch):
    """On a TPU every dispatch that lands on the jnp oracle is recorded
    with its reason while an ``oracle_trace`` is active (the chip smoke
    lists them); nothing is recorded outside one."""
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
    y, xb = jnp.ones((8,)), jnp.zeros((8,))
    ops.glm_stats(y, xb, "logistic", backend="ref")
    with ops.oracle_trace() as rec:
        ops.glm_stats(y, xb, "logistic", backend="ref")
        monkeypatch.setenv("REPRO_KERNEL_BACKEND", "ref")
        ops.alpha_search(y, xb, xb, jnp.ones((2,)), "logistic")
    assert dict(rec) == {
        ("glm_stats", "backend='ref' requested"): 1,
        ("alpha_search", "REPRO_KERNEL_BACKEND=ref"): 1}
