"""The rest of a run, at test size on the CPU, with the timed path broken
underneath: every fault a cell can have makes ``correct`` come out false."""
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from conftest import args

CELLS = ["epsilon_dense.path"]


def state_unchanged(session):
    """Each superstep hands back the state it was given."""
    solver = session.solver
    step = solver._superstep

    def frozen(*a):
        _, metrics = step(*a)
        return a[-1], metrics
    solver._superstep = frozen
    return session


def half_the_rows(session):
    """The second half of the rows is left out of every superstep."""
    solver = session.solver
    step = solver._superstep
    n = solver._n_tot
    keep = jnp.asarray(np.arange(n) < n // 2, jnp.float32)

    def halved(X, y, weights, *rest):
        return step(X, y, weights * keep, *rest)
    solver._superstep = halved
    return session


def answer_altered(session):
    """One coefficient of every fitted solution is changed where the path
    returns it."""
    fit = session.fit

    def altered(lambdas):
        lam, betas, f, iters = fit(lambdas)
        betas = betas.copy()
        j = int(np.argmax(np.abs(betas[-1])))
        betas[:, j] += 0.5 * max(1.0, float(np.abs(betas[-1, j])))
        return lam, betas, f, iters
    session.fit = altered
    return session


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [state_unchanged, half_the_rows,
                                   answer_altered])
def test_fault_is_not_correct(small, cell, fault):
    result, lines = harness.run_cell(args(cell), reg=small,
                                     require_tpu=False, session_hook=fault)
    assert result["correct"] is False, lines
    assert result["failed"] >= 1

