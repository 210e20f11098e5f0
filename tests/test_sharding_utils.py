"""Sharding/scale utilities: compression error bounds and the ALB budget
rule."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    import hypothesis
    import hypothesis.strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # the rest of this module runs without it
    HAVE_HYPOTHESIS = False

from jax.sharding import PartitionSpec as P

from repro.core import alb
from repro.sharding import compat
from repro.sharding.compress import psum_compressed


def _psum_int8_via_shard_map(x):
    """Run psum_compressed(int8) through a real (1-device) mesh axis so the
    pmax'd-shared-scale path is exercised, not the axis=None passthrough."""
    mesh = compat.make_mesh((1,), ("model",))
    fn = jax.jit(compat.shard_map(
        lambda v: psum_compressed(v, "model", "int8"), mesh=mesh,
        in_specs=(P(),), out_specs=P()))
    return np.asarray(fn(jnp.asarray(x)))


def test_int8_psum_dequantization_error_bound():
    """Shared-scale int8 psum: |dequant − x| ≤ scale/2 = amax/254 per
    element (the docstring's bound, measured through the real collective)."""
    rng = np.random.default_rng(0)
    for scale in (1e-3, 1.0, 1e3):
        x = (rng.normal(size=512) * scale).astype(np.float32)
        out = _psum_int8_via_shard_map(x)
        amax = np.abs(x).max()
        bound = (amax / 127.0) * 0.5 + amax * 1e-6
        assert np.max(np.abs(out - x)) <= bound, (scale, np.max(np.abs(out - x)))


def test_int8_psum_all_zero_shard():
    """An all-zero shard must round-trip to exactly zero (the scale floors
    at 1e-30; no 0/0, no NaN)."""
    out = _psum_int8_via_shard_map(np.zeros(64, np.float32))
    assert np.isfinite(out).all()
    np.testing.assert_array_equal(out, 0.0)


def test_compress_none_axis_is_identity():
    x = jnp.asarray(np.random.default_rng(0).normal(size=100).astype(np.float32))
    for mode in (None, "bf16", "int8"):
        np.testing.assert_array_equal(np.asarray(psum_compressed(x, None,
                                                                 mode)),
                                      np.asarray(x))


def _int8_quantization_error_bound(seed, scale):
    """|dequant(quant(x)) - x| <= amax/127 per element (pre-psum)."""
    rng = np.random.default_rng(seed)
    x = (rng.normal(size=256) * scale).astype(np.float32)
    amax = np.abs(x).max()
    s = max(amax, 1e-30) / 127.0
    q = np.clip(np.round(x / s), -127, 127) * s
    assert np.max(np.abs(q - x)) <= s * 0.5 + 1e-12 + amax * 1e-6


if HAVE_HYPOTHESIS:
    @hypothesis.given(seed=st.integers(0, 1000), scale=st.floats(1e-3, 1e3))
    @hypothesis.settings(deadline=None, max_examples=50)
    def test_int8_quantization_error_bound(seed, scale):
        _int8_quantization_error_bound(seed, scale)
else:
    @pytest.mark.parametrize("seed,scale", [(0, 1e-3), (1, 1.0), (2, 1e3)])
    def test_int8_quantization_error_bound(seed, scale):
        # fixed-case fallback when hypothesis is not installed
        _int8_quantization_error_bound(seed, scale)


class TestALB:
    def test_kappa_rule(self):
        """At least a (1-kappa)-quantile node completes exactly one cycle;
        faster nodes get bigger budgets, slower smaller."""
        speeds = np.array([1.0, 1.0, 1.0, 0.25, 2.0, 1.0, 1.0, 1.0])
        b = alb.alb_budgets(speeds, n_tiles=100, kappa=0.75)
        assert b.min() >= 1
        # the straggler gets ~quarter of a cycle
        assert b[3] < 50
        # the fast node exceeds one cycle
        assert b[4] > 100
        # at least 75% of nodes complete >= one full cycle
        assert (b >= 100).mean() >= 0.5

    def test_budget_cap_and_floor(self):
        speeds = np.array([1e-3, 1.0, 1e3])
        b = alb.alb_budgets(speeds, n_tiles=10, kappa=0.5)
        assert b.min() >= 1
        assert b.max() <= alb.max_budget(10)

    def test_homogeneous_is_one_cycle(self):
        b = alb.alb_budgets(np.ones(8), n_tiles=64, kappa=0.75)
        np.testing.assert_array_equal(b, np.full(8, 64))

    def test_pivot_completes_exactly_one_cycle(self):
        """The κ-pivot node's budget is EXACTLY n_tiles.  Linear quantile
        interpolation would put the pivot speed between two nodes (here
        1.0 and 1.3 → 1.225) and give the pivot node round(100/1.225) = 82
        tiles — the regression the method="lower" fix pins down."""
        speeds = np.array([1.0, 1.3, 2.0, 4.0])
        for kappa in (0.75, 0.5, 0.25):
            b = alb.alb_budgets(speeds, n_tiles=100, kappa=kappa)
            # the pivot is the (1-κ)-quantile speed, snapped DOWN to an
            # actual node; that node's budget is exactly one full cycle
            try:
                pivot = np.quantile(speeds, 1.0 - kappa, method="lower")
            except TypeError:
                pivot = np.quantile(speeds, 1.0 - kappa,
                                    interpolation="lower")
            assert pivot in speeds
            np.testing.assert_array_equal(b[speeds == pivot], 100)
        # irregular speeds where interpolation is guaranteed off-node
        rng = np.random.default_rng(7)
        for _ in range(20):
            speeds = rng.uniform(0.3, 3.0, size=rng.integers(2, 12))
            b = alb.alb_budgets(speeds, n_tiles=64, kappa=0.75)
            assert (b == 64).any(), (speeds, b)

    def test_rejects_nonpositive(self):
        with pytest.raises(ValueError):
            alb.alb_budgets(np.array([1.0, 0.0]), 10, 0.75)

    def test_speed_sampler_positive(self):
        rng = np.random.default_rng(0)
        s = alb.sample_speeds(rng, np.ones(64))
        assert (s > 0).all()
