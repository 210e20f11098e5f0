"""Pallas TPU kernel: sequential Gauss-Seidel soft-threshold tile solve.

This is the hot sequential core of d-GLMNET's Algorithm 2 after the Gram
re-blocking described in DESIGN.md §2: all O(n·T) work has already been done
by MXU matmuls (producing the T×T Gram block ``G`` and the gradient vector
``g``); what remains is a strictly sequential chain of T exact coordinate
minimizations where step j updates a T-vector by an axpy with row j of G.

XLA is poor at this shape of computation (a scan of dynamic-slices over a
matrix it keeps in HBM); Pallas pins G in VMEM for the whole chain and runs
the T-step loop on-core. VMEM footprint: T² + 6T floats (T=512 ⇒ ~1.06 MB).

Mosaic does not index in-register vectors dynamically, so the chain
(``solve_chain``) works on whole (1, T) rows and selects coordinate j with
lane masks; row j of G comes straight from the VMEM ref (a dynamic sublane
slice, which Mosaic does support).  The scalars (μ, ν, λ1, λ2) live in
SMEM.

The kernel is gridless (grid=(1,)) by design: tiles are coupled through the
margin delta, so cross-tile parallelism would change the algorithm (Jacobi
instead of Gauss-Seidel) — that trade-off is explored at the *block* level by
the distributed driver instead, exactly like the paper does across nodes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# params vector layout (passed as a (4,) f32 SMEM array):
MU, NU, LAM1, LAM2 = 0, 1, 2, 3


def solve_chain(G_row, g, h, beta, pf, d0, mu, nu, lam1, lam2):
    """Exact cyclic coordinate minimization over one tile (the
    ``ref.cd_tile_solve`` chain) on (1, T) rows; ``G_row(j)`` returns row j
    of the tile Gram as (1, T).  Returns the new Δβ as (1, T).

    Coordinate j is visited once per pass, so its entering Δβ is ``d0[j]``
    for the whole chain and only the gradient row changes between steps.
    Each step therefore evaluates the update of EVERY coordinate from the
    current gradient (a few T-wide vector ops) and keeps lane j: one masked
    lane reduction extracts the step δ_j that the rank-1 gradient
    correction needs.
    """
    T = g.shape[-1]
    lam1v = lam1 * pf
    den = mu * h + nu + lam2 * pf
    den_safe = jnp.maximum(den, 1e-30)
    c = mu * h * (beta + d0) + nu * beta
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)

    def body(j, carry):
        g_c, d = carry
        num = g_c + c
        u = jnp.sign(num) * jnp.maximum(jnp.abs(num) - lam1v, 0.0) / den_safe
        # dead coordinate (all-zero column, nu == lam2 == 0): keep at 0
        u = jnp.where(den > 0, u, beta)
        d_new = u - beta
        at_j = lane == j
        delta = jnp.sum(jnp.where(at_j, d_new - d0, 0.0), axis=1,
                        keepdims=True)                              # (1, 1)
        # rank-1 correction of the tile gradient: g -= mu*delta*G[j, :]
        # (G is symmetric, so row j is column j)
        g_c = g_c - (mu * delta) * G_row(j)
        return g_c, jnp.where(at_j, d_new, d)

    _, d = jax.lax.fori_loop(0, T, body, (g, d0))
    return d


def _kernel(params_ref, G_ref, g_ref, h_ref, beta_ref, dbeta_ref, pf_ref,
            out_ref):
    out_ref[...] = solve_chain(
        lambda j: G_ref[pl.ds(j, 1), :], g_ref[...], h_ref[...],
        beta_ref[...], pf_ref[...], dbeta_ref[...],
        params_ref[MU], params_ref[NU], params_ref[LAM1], params_ref[LAM2])


@functools.partial(jax.jit, static_argnames=("interpret",))
def cd_tile_solve_pallas(G, g, h, beta_t, dbeta_t, params, penf, *,
                         interpret=True):
    """params: (4,) f32 [mu, nu, lam1, lam2]; penf: (T,) per-coordinate
    penalty factors (all ones when unpenalized scaling is not in play).
    Returns new dbeta_t (T,)."""
    T = g.shape[0]
    f32 = jnp.float32
    row = pl.BlockSpec((1, T), lambda i: (0, 0))
    out = pl.pallas_call(
        _kernel,
        grid=(1,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),    # params
            pl.BlockSpec((T, T), lambda i: (0, 0)),   # G      — VMEM resident
            row, row, row, row, row,                  # g, h, beta, dbeta, pf
        ],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((1, T), f32),
        interpret=interpret,
    )(
        params.astype(f32),
        G.astype(f32),
        g.astype(f32)[None, :],
        h.astype(f32)[None, :],
        beta_t.astype(f32)[None, :],
        dbeta_t.astype(f32)[None, :],
        penf.astype(f32)[None, :],
    )
    return out[0]
