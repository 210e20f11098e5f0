"""d-GLMNET line search (paper Algorithm 3), vectorized over candidates.

Procedure (σ, b, γ, δ from the paper; defaults b=0.5, σ=0.01, γ=0):
  1. If α=1 satisfies the Armijo condition f(β+Δβ) ≤ f(β) + σ·D, take α=1
     (this is what lets the trust-region μ preserve sparsity — see §4).
  2. Else pick α_init = argmin_{δ≤α≤1} f(β + αΔβ) over a log-spaced grid,
     then Armijo-backtrack α_init·b^j.

All candidate objectives are evaluated with the one-pass ``alpha_search``
kernel; penalties are separable and psum'd over the feature (``model``) axis.
Everything is branch-free (jnp.where selection) so the whole search lives
inside one jitted superstep.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.kernels import ops


class LineSearchResult(NamedTuple):
    alpha: jnp.ndarray      # chosen step
    f_new: jnp.ndarray      # objective at the chosen step
    accepted_unit: jnp.ndarray  # bool: α==1 accepted by Armijo directly
    D: jnp.ndarray          # paper's directional decrease bound


def _psum(x, axis):
    return jax.lax.psum(x, axis) if axis is not None else x


def candidate_alphas(delta, grid_size):
    """Algorithm 3's candidate set: ``[1, logspace(delta … 1)]`` — the unit
    step first, then the α_init pre-search grid.  Shared by the in-memory
    search below and the streaming superstep (which precomputes the losses
    of every candidate in one chunk pass), so the two paths can never
    drift apart."""
    grid = jnp.logspace(jnp.log10(delta), 0.0, grid_size)
    return jnp.concatenate([jnp.ones((1,)), grid])


def backtrack_chains(alphas, b, max_backtracks):
    """(K, max_backtracks) matrix of Armijo chains ``alphas[i]·b^j``."""
    powers = jnp.power(b, jnp.arange(max_backtracks, dtype=jnp.float32))
    return alphas[:, None] * powers[None, :]


def armijo_select(f_unit, f_bt, bt, f_current, sigma, D) -> LineSearchResult:
    """Branch-free Algorithm-3 acceptance from precomputed objectives:
    take α = 1 if it satisfies the Armijo condition, else the first
    (largest-α) passing backtrack candidate, falling back to the smallest
    step.  ``f_unit`` is f(β + Δβ); ``f_bt``/``bt`` the backtracking
    chain's objectives and step sizes."""
    ok_unit = f_unit <= f_current + sigma * D
    ok_bt = f_bt <= f_current + bt * sigma * D
    idx = jnp.argmax(ok_bt)
    idx = jnp.where(jnp.any(ok_bt), idx, bt.shape[0] - 1)
    alpha = jnp.where(ok_unit, 1.0, bt[idx])
    f_new = jnp.where(ok_unit, f_unit, f_bt[idx])
    return LineSearchResult(alpha, f_new, ok_unit, D)


def full_candidates(delta, grid_size, b, max_backtracks):
    """The ONE-PASS candidate set: ``[1, grid]`` followed by every
    candidate's full Armijo backtracking chain, flattened —
    ``(1 + grid_size) * (1 + max_backtracks)`` step sizes total.  Paired
    with ``select_precomputed``, a single loss sweep over this set
    replicates the two-phase ``search`` exactly; it is the candidate
    contract of both the streaming superstep (losses accumulated across
    chunks) and the fused superstep's margin+line-search launch
    (DESIGN.md §8)."""
    alphas0 = candidate_alphas(delta, grid_size)
    chains = backtrack_chains(alphas0, b, max_backtracks)
    return jnp.concatenate([alphas0, chains.reshape(-1)])


def select_precomputed(losses, cand, beta, dbeta, lam1, lam2, *, f_current,
                       grad_dot_dir, quad_form, sigma, gamma, grid_size,
                       max_backtracks, axis_model=None,
                       penf=None) -> LineSearchResult:
    """Algorithm-3 selection from the precomputed losses of
    ``full_candidates``: grid argmin, then the argmin's backtracking chain
    by dynamic slice — bit-identical decisions to ``search`` without any
    further data passes."""
    K0 = 1 + grid_size
    B = max_backtracks
    pens = penalty_terms(beta, dbeta, cand, lam1, lam2, axis_model, penf)
    f_cand = losses + pens
    R1 = pens[0]                              # R(β + Δβ)
    R0 = penalty_terms(beta, dbeta, jnp.zeros((1,)), lam1, lam2, axis_model,
                       penf)[0]
    D = grad_dot_dir + gamma * quad_form + R1 - R0
    i0 = jnp.argmin(f_cand[:K0])
    bt = jax.lax.dynamic_slice(cand, (K0 + i0 * B,), (B,))
    f_bt = jax.lax.dynamic_slice(f_cand, (K0 + i0 * B,), (B,))
    return armijo_select(f_cand[0], f_bt, bt, f_current, sigma, D)


def select_changes(dlosses, dpens, cand, *, grad_dot_dir, quad_form, sigma,
                   gamma, grid_size, max_backtracks) -> LineSearchResult:
    """``select_precomputed`` from each candidate's CHANGE of the loss
    (``dlosses``, summed row by row) and of the penalty (``dpens``,
    ``penalty_changes``): the same decisions in exact arithmetic, but
    rounded at the size of the changes, so they still hold where a
    superstep moves the objective by less than the rounding of its float32
    sum.  The result's ``f_new`` is the chosen step's change of the
    objective."""
    K0 = 1 + grid_size
    B = max_backtracks
    df = dlosses + dpens
    D = grad_dot_dir + gamma * quad_form + dpens[0]
    i0 = jnp.argmin(df[:K0])
    bt = jax.lax.dynamic_slice(cand, (K0 + i0 * B,), (B,))
    df_bt = jax.lax.dynamic_slice(df, (K0 + i0 * B,), (B,))
    return armijo_select(df[0], df_bt, bt, 0.0, sigma, D)


def penalty_changes(beta, dbeta, alphas, lam1, lam2, penf=None):
    """R(β + α·Δβ) − R(β) for every α: (K,), summed coordinate by
    coordinate (single device)."""
    pf = jnp.ones_like(beta) if penf is None else penf
    l1 = jnp.sum(pf[None, :] * (jnp.abs(beta[None, :] + alphas[:, None]
                                        * dbeta[None, :])
                                - jnp.abs(beta)[None, :]), axis=-1)
    bd = jnp.sum(pf * beta * dbeta)
    d2 = jnp.sum(pf * dbeta * dbeta)
    return lam1 * l1 + 0.5 * lam2 * (2.0 * alphas * bd + alphas * alphas * d2)


def penalty_terms(beta, dbeta, alphas, lam1, lam2, axis_model, penf=None):
    """R(β + α·Δβ) for every α: (K,). beta/dbeta are the LOCAL shards.

    ``penf``: optional (p_loc,) per-coordinate penalty factors — R becomes
    Σ_j pf_j (λ1 |b_j| + λ2/2 b_j²); pf_j = 0 leaves coordinate j (the
    intercept) out of both penalty terms.
    """
    pf = jnp.ones_like(beta) if penf is None else penf
    # L1: needs a full |.| pass per alpha over local coords, psum over model.
    l1 = jnp.sum(pf[None, :]
                 * jnp.abs(beta[None, :] + alphas[:, None] * dbeta[None, :]),
                 axis=-1)
    # L2: quadratic in alpha from three local (pf-weighted) scalars.
    b2 = jnp.sum(pf * beta * beta)
    bd = jnp.sum(pf * beta * dbeta)
    d2 = jnp.sum(pf * dbeta * dbeta)
    stacked = _psum(jnp.concatenate([l1, jnp.stack([b2, bd, d2])]), axis_model)
    l1, (b2, bd, d2) = stacked[:-3], stacked[-3:]
    l2 = b2 + 2.0 * alphas * bd + alphas * alphas * d2
    return lam1 * l1 + 0.5 * lam2 * l2


def search(y, xb, xdb, beta, dbeta, *, family, lam1, lam2, mu, nu,
           f_current, grad_dot_dir, quad_form,
           sigma=0.01, b=0.5, gamma=0.0, delta=1e-3,
           grid_size=13, max_backtracks=20, weights=None, offset=None,
           penf=None,
           axis_data: Optional[str] = None, axis_model: Optional[str] = None,
           backend: Optional[str] = None) -> LineSearchResult:
    """Run Algorithm 3.

    y, xb, xdb: (n_loc,) — labels, margins, margin delta (model-replicated).
    beta, dbeta: (p_loc,) local weight shards.
    lam1, lam2: penalty weights — may be traced runtime scalars (the λ pair
      is a superstep *argument*, not a compile-time constant, so one
      compiled search serves a whole regularization path).
    weights: (n_loc,) per-example observation weights (sample weights × fold
      mask × padding) — every candidate objective is the WEIGHTED loss sum,
      matching f_current, or the Armijo comparison is offset.
    offset: (n_loc,) margin offsets; candidate losses evaluate at
      ``xb + offset + α·xdb``.
    penf: (p_loc,) per-coordinate penalty factors for the penalty terms.
    f_current: f(β) (global scalar, already reduced).
    grad_dot_dir: ∇L(β)ᵀΔβ (global scalar, already reduced).
    quad_form: Δβᵀ(μ(H̃+νI))Δβ (global scalar) — only used when γ>0.
    """
    # Candidate set: [1.0, grid...] — grid log-spaced on [delta, 1].
    alphas = candidate_alphas(delta, grid_size)

    losses = _psum(ops.alpha_search(y, xb, xdb, alphas, family,
                                    weights=weights, offset=offset,
                                    backend=backend), axis_data)
    pens = penalty_terms(beta, dbeta, alphas, lam1, lam2, axis_model, penf)
    f_cand = losses + pens

    # Paper's D (eq. 12):
    R1 = pens[0]                              # R(β + Δβ)
    R0 = penalty_terms(beta, dbeta, jnp.zeros((1,)), lam1, lam2, axis_model,
                       penf)[0]
    D = grad_dot_dir + gamma * quad_form + R1 - R0

    a_init = alphas[jnp.argmin(f_cand)]
    bt = backtrack_chains(a_init[None], b, max_backtracks)[0]
    losses_bt = _psum(ops.alpha_search(y, xb, xdb, bt, family,
                                       weights=weights, offset=offset,
                                       backend=backend), axis_data)
    f_bt = losses_bt + penalty_terms(beta, dbeta, bt, lam1, lam2, axis_model,
                                     penf)
    return armijo_select(f_cand[0], f_bt, bt, f_current, sigma, D)
