"""The per-kernel operation counts of ``bench/kernels/`` against XLA's own
count (``roofline/hlo.py`` ``analyze_hlo``) of the matching jnp oracle in
``kernels/ref.py``, wherever the oracle's work is matrix products XLA can
count.  Elementwise work (link statistics, candidate losses, the serial
chain) is not counted by ``analyze_hlo`` and is left out of the comparison
through the formulas' own per-element constants."""
import jax
import jax.numpy as jnp
import pytest

from bench import harness
from repro.kernels import ref
from repro.roofline.hlo import analyze_hlo

KERNELS = harness.BENCH / "kernels"


def formula(name):
    return harness.load_module(KERNELS / f"{name}.py", "kernels")


def xla_flops(fn, *args):
    return analyze_hlo(jax.jit(fn).lower(*args).compile().as_text()).flops


def spec(*shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


@pytest.mark.parametrize("nt,n,T", [(3, 2048, 256), (2, 1024, 512)])
def test_stats_gram_solve_matches_xla(nt, n, T):
    """With every tile live, the Gram and gradient work of
    ``stats_gram_solve`` is that of the oracle's batched products."""
    f = formula("superstep_tile")
    flops, _ = f.stats_gram_solve(nt, n, T, live=nt)
    elementwise = nt * (n * T + 2.0 * T * T + 10.0 * T) \
        + f.STATS_FLOPS * n
    want = xla_flops(ref.gram_dense_tiles, spec(nt, n, T), spec(n), spec(n))
    assert flops - elementwise == pytest.approx(want, rel=5e-3)


@pytest.mark.parametrize("nt,n,T,K", [(3, 2048, 256, 294)])
def test_margin_ls_matches_xla(nt, n, T, K):
    """The margin delta of ``margin_ls`` is the oracle's product X dbeta."""
    f = formula("superstep_tile")
    flops, _ = f.margin_ls(nt, n, T, K)
    want = xla_flops(
        lambda X, y, xb, d, w, a: ref.fused_ls_dense(X, y, xb, d, w, a,
                                                     "logistic"),
        spec(nt, n, T), spec(n), spec(n), spec(nt * T), spec(n), spec(K))
    assert flops - f.CAND_FLOPS * K * n == pytest.approx(want, rel=5e-3)


def test_elementwise_kernel_counts_every_row():
    """``glm_stats`` reads and writes whole rows: its byte count is that of
    its operands and results."""
    R = 64
    _, b = formula("glm_stats").cost([("f32", (R, 128))] * 3, {})
    assert b == 4 * 6 * R * 128
