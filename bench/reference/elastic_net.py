"""Plain reference for an elastic-net GLM path: the objective

    f(beta) = sum_i l(y_i, x_i . beta) + lam1 |beta|_1 + lam2/2 |beta|^2

and its optimality (KKT) conditions, evaluated in float64 on the
benchmark's own copy of the data for coefficients that the program
returned.  For every coordinate, with g = -grad of the loss:

    beta_j != 0:  |g_j - lam2 beta_j - lam1 sign(beta_j)| / lam1
    beta_j == 0:  max(|g_j| - lam1, 0) / lam1

The largest of these over the coordinates is the KKT residual of one
solution, in units of lam1; a solution is optimal exactly where it is 0.
"""
from __future__ import annotations

import importlib.util
import pathlib
import sys

import numpy as np

_FAMILIES = pathlib.Path(__file__).parent / "families"


def family(name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_family_{name}", _FAMILIES / f"{name}.py")
    if spec is None or not (_FAMILIES / f"{name}.py").exists():
        raise FileNotFoundError(f"no reference family {name!r} in {_FAMILIES}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def check_path(problem, family_name, lambdas, lam2, betas, f_reported):
    """(kkt residual per solution, relative objective gap per solution) for
    the (K, p) ``betas`` fitted at ``lambdas``, against the objective values
    the program reported."""
    fam = family(family_name)
    betas = np.asarray(betas, np.float64)
    lambdas = np.asarray(lambdas, np.float64)
    M = problem.margins(betas.T)
    y = np.asarray(problem.y, np.float64)[:, None]
    loss, S = fam.loss_and_score(y, M)
    G = problem.rmatvec(S)
    kkt, fgap = [], []
    for k, (lam1, b) in enumerate(zip(lambdas, betas)):
        g = G[:, k]
        nz = b != 0.0
        res_nz = np.abs(g[nz] - lam2 * b[nz] - lam1 * np.sign(b[nz]))
        res_z = np.maximum(np.abs(g[~nz]) - lam1, 0.0)
        worst = max(res_nz.max(initial=0.0), res_z.max(initial=0.0))
        kkt.append(worst / lam1)
        f_ref = loss[:, k].sum() + lam1 * np.abs(b).sum() \
            + 0.5 * lam2 * np.dot(b, b)
        fgap.append(abs(float(f_reported[k]) - f_ref) / abs(f_ref))
    bad = ~np.isfinite(betas).all(axis=1) | ~np.isfinite(
        np.asarray(f_reported, np.float64))
    kkt = np.where(bad, np.inf, kkt)
    fgap = np.where(bad, np.inf, fgap)
    return kkt, fgap


def margin_gap(problem, beta, margins):
    """Largest gap between the margins the program maintains and X beta of
    its coefficients, over the rows, in units of the largest |X beta| (at
    least 1)."""
    want = problem.margins(np.asarray(beta, np.float64)[:, None])[:, 0]
    gap = np.abs(np.asarray(margins, np.float64) - want)
    return float(gap.max() / max(1.0, np.abs(want).max()))
