"""Block coordinate-descent sweeps over a local feature block.

This is the compute core of d-GLMNET's Algorithm 2, re-blocked for TPU as
described in DESIGN.md §2: features are processed in tiles of the design's
``tile_size``; per tile, the gradient vector ``g`` and the Gram block ``G``
are produced through the ``DesignMatrix`` operator interface (MXU matmuls for
``DenseDesign``, the brick-gather ``ops.tile_gram`` kernel for
``BlockSparseDesign`` — with a psum over the ``data`` mesh axis when examples
are sharded), and the strictly sequential chain of exact coordinate updates
runs in the ``cd_tile_solve`` kernel with everything VMEM-resident.

The sweeps never touch a raw (n, p) array: every access to the design matrix
goes through ``design.tile_gram`` / ``design.tile_matvec`` /
``design.all_tile_grams`` / ``design.matvec``, so the same sweep code drives
dense and blocked-sparse layouts (DESIGN.md §2).

Two tile-coupling modes:

  * ``gauss-seidel`` (paper-faithful node semantics): tiles are processed
    cyclically; tile t sees the margin delta produced by tiles < t.  One
    (G, g) psum per tile.
  * ``jacobi``: all tile Grams/gradients are computed up-front from the
    iteration-start state and solved independently (vmapped).  Mathematically
    this equals d-GLMNET with a finer feature partition (every tile is a
    virtual node), so the paper's convergence story is unchanged — conflicts
    between tiles are handled by the same μ/line-search machinery that
    handles conflicts between nodes.  One fused psum per sweep and fully
    parallel tile solves: this is the collective-batching optimization
    explored in EXPERIMENTS.md §Perf.

All functions are shard_map-friendly: pass ``axis_data`` to psum partial row
reductions; pass ``None`` when rows are unsharded (the paper's 1-D layout).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ops


def _psum(x, axis: Optional[str]):
    return jax.lax.psum(x, axis) if axis is not None else x


def coordinate_prox(g, h, beta, pf, *, mu, nu, lam1, lam2):
    """Δβ of one exact coordinate step for every coordinate at once: the
    tile solve's chain (``ref.cd_tile_solve``) for a tile of one feature,
    from Δβ = 0.  ``g`` = Xᵀs and ``h`` = diag XᵀWX at the iterate; the
    blocks of one feature are coupled by Jacobi, as tiles are."""
    den = mu * h + nu + lam2 * pf
    num = g + mu * h * beta + nu * beta
    u = jnp.sign(num) * jnp.maximum(jnp.abs(num) - lam1 * pf, 0.0) \
        / jnp.maximum(den, 1e-30)
    # dead coordinate (all-zero column, nu == lam2 == 0): keep at 0
    return jnp.where(den > 0, u, beta) - beta


def alb_live_mask(n_tiles: int, start_tile, num_tiles):
    """(n_tiles,) bool: tiles [start, start+budget) in cyclic order — the
    ALB budget window of one jacobi sweep (Section 7).  Shared by the
    unfused sweeps and the fused superstep's tile-occupancy pass."""
    tids = jnp.arange(n_tiles, dtype=jnp.int32)
    offset = jax.lax.rem(tids - jnp.asarray(start_tile, jnp.int32),
                         jnp.asarray(n_tiles, jnp.int32))
    offset = jnp.where(offset < 0, offset + n_tiles, offset)
    return offset < jnp.minimum(jnp.asarray(num_tiles, jnp.int32), n_tiles)


def sweep_gauss_seidel(design, s, w, beta, dbeta, xdb, *, mu, nu, lam1, lam2,
                       start_tile=0, num_tiles=None,
                       max_num_tiles: Optional[int] = None,
                       active=None, penf=None,
                       axis_data: Optional[str] = None,
                       backend: Optional[str] = None):
    """Cyclic tile sweep; returns (dbeta, xdb, tiles_done).

    design: local DesignMatrix block, shape (n_loc, p_loc).
    s, w: (n_loc,) link stats at the outer iterate (FIXED during the sweep).
      Observation weights are already folded in upstream (glm_stats weights),
      so the Gram/gradient psums are the weighted sums without further work.
    beta, dbeta: (p_loc,); xdb: (n_loc,) = X @ dbeta (local block only).
    lam1/lam2 may be traced scalars — the λ pair is a *runtime* argument of
      the superstep so one compiled sweep serves a whole regularization path.
    num_tiles: how many tiles this node is budgeted to process this superstep
      (ALB); defaults to one full cycle.  May exceed a full cycle (fast
      nodes).  ``max_num_tiles`` is the static loop bound all SPMD peers run
      (masked work beyond the local budget) — required because collectives
      inside the loop must be executed in lockstep.
    active: optional (p_loc,) 0/1 screening mask — coordinates with
      ``active == 0`` are frozen at their entering Δβ (the λ-path driver's
      strong-rule/KKT active set; see solver.fit_path).
    penf: optional (p_loc,) per-coordinate penalty factors (runtime, like
      ``active``): coordinate j is solved under (λ1·penf_j, λ2·penf_j);
      penf_j = 0 is unpenalized (the intercept column).
    """
    T = design.tile_size
    n_tiles_total = design.n_tiles
    if num_tiles is None:
        num_tiles = n_tiles_total
    num_tiles = jnp.asarray(num_tiles, jnp.int32)
    static_bound = int(max_num_tiles if max_num_tiles is not None else n_tiles_total)

    # Dead-tile skip (active-set-shaped launches, DESIGN.md §8): when rows
    # are unsharded, a tile whose coordinates are all screened out skips its
    # Gram/solve/matvec entirely via a real branch.  With ``axis_data`` the
    # psum inside the body must run in SPMD lockstep, so the branch is
    # disabled and dead tiles keep doing (masked) work.
    skip_dead = active is not None and axis_data is None

    def tile_body(t, carry):
        dbeta_c, xdb_c = carry
        live = t < num_tiles
        tid = jax.lax.rem(jnp.asarray(start_tile, jnp.int32) + t, n_tiles_total)
        col0 = tid * T
        dt = jax.lax.dynamic_slice(dbeta_c, (col0,), (T,))

        def do_tile():
            r = s - mu * (w * xdb_c)
            G, g = design.tile_gram(tid, w, r, backend=backend)
            G, g = _psum((G, g), axis_data)
            h = jnp.diagonal(G)
            bt = jax.lax.dynamic_slice(beta, (col0,), (T,))
            pf_t = None if penf is None else \
                jax.lax.dynamic_slice(penf, (col0,), (T,))
            dt_new = ops.cd_tile_solve(G, g, h, bt, dt, mu, nu, lam1, lam2,
                                       penf=pf_t, backend=backend)
            if active is not None:
                at = jax.lax.dynamic_slice(active, (col0,), (T,))
                dt_new = jnp.where(at > 0, dt_new, dt)
            dt_new = jnp.where(live, dt_new, dt)
            return dt_new, design.tile_matvec(tid, dt_new - dt)

        if skip_dead:
            at = jax.lax.dynamic_slice(active, (col0,), (T,))
            tile_on = live & (jnp.max(at) > 0)
            dt_new, xdb_add = jax.lax.cond(
                tile_on, do_tile, lambda: (dt, jnp.zeros_like(xdb_c)))
        else:
            dt_new, xdb_add = do_tile()
        xdb_c = xdb_c + xdb_add
        dbeta_c = jax.lax.dynamic_update_slice(dbeta_c, dt_new, (col0,))
        return dbeta_c, xdb_c

    dbeta, xdb = jax.lax.fori_loop(0, static_bound, tile_body, (dbeta, xdb))
    return dbeta, xdb, jnp.minimum(num_tiles, static_bound)


def sweep_jacobi(design, s, w, beta, dbeta, xdb, *, mu, nu, lam1, lam2,
                 start_tile=0, num_tiles=None,
                 max_num_tiles: Optional[int] = None,
                 active=None, penf=None,
                 axis_data: Optional[str] = None,
                 backend: Optional[str] = None):
    """Jacobi-across-tiles sweep: one fused psum, vmapped tile solves.

    Equivalent to d-GLMNET with each tile as a virtual node.  ``dbeta`` and
    ``xdb`` must be zero on entry (start of an outer iteration) — asserted by
    the driver.  ALB budgeting masks whole tiles; ``active`` / ``penf`` (see
    sweep_gauss_seidel) act per coordinate.
    """
    T = design.tile_size
    n_loc, p_loc = design.shape
    n_tiles_total = design.n_tiles
    if num_tiles is None:
        num_tiles = n_tiles_total
    num_tiles = jnp.asarray(num_tiles, jnp.int32)

    # Fused Gram blocks + gradient: ONE collective for the entire sweep.
    G_all, g_all = design.all_tile_grams(w, s, backend=backend)
    G_all, g_all = _psum((G_all, g_all), axis_data)
    h_all = jnp.diagonal(G_all, axis1=-2, axis2=-1)

    beta_r = beta.reshape(n_tiles_total, T)
    dbeta_r = jnp.zeros_like(beta_r)

    solve = functools.partial(ops.cd_tile_solve, mu=mu, nu=nu, lam1=lam1,
                              lam2=lam2, backend=backend)
    if penf is None:
        d_new = jax.vmap(
            lambda Gt, gt, ht, bt, dt: solve(Gt, gt, ht, bt, dt))(
            G_all, g_all, h_all, beta_r, dbeta_r)
    else:
        penf_r = penf.reshape(n_tiles_total, T)
        d_new = jax.vmap(
            lambda Gt, gt, ht, bt, dt, pt: solve(Gt, gt, ht, bt, dt,
                                                 penf=pt))(
            G_all, g_all, h_all, beta_r, dbeta_r, penf_r)

    # ALB mask: tiles [start, start+budget) in cyclic order are active.
    live = alb_live_mask(n_tiles_total, start_tile, num_tiles)
    d_new = jnp.where(live[:, None], d_new, 0.0)
    if active is not None:
        d_new = jnp.where(active.reshape(n_tiles_total, T) > 0, d_new, 0.0)

    dbeta_out = d_new.reshape(p_loc)
    ops.record_launch("matvec")  # the xdb merge pass is its own HBM sweep
    xdb_out = design.matvec(dbeta_out)
    return dbeta_out, xdb_out, jnp.minimum(num_tiles, n_tiles_total)


SWEEPS = {"gauss-seidel": sweep_gauss_seidel, "jacobi": sweep_jacobi}


# ---------------------------------------------------------------------------
# gram-mode sweeps (chunked statistics / StreamingDesign, DESIGN.md §6)
# ---------------------------------------------------------------------------
#
# When the rows are out of core, one pass over the chunks accumulates the
# full weighted Gram G_w = XᵀWX and gradient g0 = Xᵀs; the sweeps then run
# entirely on device from those statistics.  They are ALGEBRAICALLY the
# row-space sweeps above: at tile t the residual gradient is
#
#     g_t(r) = X_tᵀ (s − μ·W·XΔβ) = g0_t − μ·(G_w Δβ)_t
#
# so maintaining u = G_w Δβ (updated per tile by a (p, T) matmul) replaces
# maintaining the (n,) margin delta xdb.  Both sweeps return u, from which
# the line-search quadratic Σ w·xdb² = Δβᵀ G_w Δβ = Δβᵀu follows exactly.
# Entering Δβ is zero (the supersteps always start a sweep from Δβ = 0).


def sweep_gauss_seidel_gram(G_full, g0, beta, *, mu, nu, lam1, lam2,
                            tile_size, start_tile=0, num_tiles=None,
                            max_num_tiles: Optional[int] = None,
                            active=None, penf=None,
                            backend: Optional[str] = None):
    """Cyclic tile sweep from the full Gram; returns (dbeta, u, tiles_done)
    with u = G_full @ dbeta (for the line-search quadratic)."""
    T = tile_size
    p = g0.shape[0]
    n_tiles_total = p // T
    if num_tiles is None:
        num_tiles = n_tiles_total
    num_tiles = jnp.asarray(num_tiles, jnp.int32)
    static_bound = int(max_num_tiles if max_num_tiles is not None
                       else n_tiles_total)

    def tile_body(t, carry):
        dbeta_c, u = carry
        live = t < num_tiles
        tid = jax.lax.rem(jnp.asarray(start_tile, jnp.int32) + t,
                          n_tiles_total)
        col0 = tid * T
        Gt = jax.lax.dynamic_slice(G_full, (col0, col0), (T, T))
        g_t = jax.lax.dynamic_slice(g0, (col0,), (T,)) \
            - mu * jax.lax.dynamic_slice(u, (col0,), (T,))
        h = jnp.diagonal(Gt)
        bt = jax.lax.dynamic_slice(beta, (col0,), (T,))
        dt = jax.lax.dynamic_slice(dbeta_c, (col0,), (T,))
        pf_t = None if penf is None else \
            jax.lax.dynamic_slice(penf, (col0,), (T,))
        dt_new = ops.cd_tile_solve(Gt, g_t, h, bt, dt, mu, nu, lam1, lam2,
                                   penf=pf_t, backend=backend)
        if active is not None:
            at = jax.lax.dynamic_slice(active, (col0,), (T,))
            dt_new = jnp.where(at > 0, dt_new, dt)
        dt_new = jnp.where(live, dt_new, dt)
        u = u + jax.lax.dynamic_slice(G_full, (0, col0), (p, T)) \
            @ (dt_new - dt)
        dbeta_c = jax.lax.dynamic_update_slice(dbeta_c, dt_new, (col0,))
        return dbeta_c, u

    dbeta, u = jax.lax.fori_loop(
        0, static_bound, tile_body,
        (jnp.zeros_like(beta), jnp.zeros_like(beta)))
    return dbeta, u, jnp.minimum(num_tiles, static_bound)


def sweep_jacobi_gram(G_full, g0, beta, *, mu, nu, lam1, lam2, tile_size,
                      start_tile=0, num_tiles=None,
                      max_num_tiles: Optional[int] = None,
                      active=None, penf=None,
                      backend: Optional[str] = None):
    """Jacobi-across-tiles from the full Gram: block-diagonal tile solves
    from the iteration-start gradient, vmapped; (dbeta, u, tiles_done)."""
    T = tile_size
    p = g0.shape[0]
    n_tiles_total = p // T
    if num_tiles is None:
        num_tiles = n_tiles_total
    num_tiles = jnp.asarray(num_tiles, jnp.int32)

    tids = jnp.arange(n_tiles_total, dtype=jnp.int32)
    Gr = G_full.reshape(n_tiles_total, T, n_tiles_total, T)
    G_all = Gr[tids, :, tids, :]                        # (nt, T, T) diagonal
    g_all = g0.reshape(n_tiles_total, T)
    h_all = jnp.diagonal(G_all, axis1=-2, axis2=-1)
    beta_r = beta.reshape(n_tiles_total, T)
    dbeta_r = jnp.zeros_like(beta_r)

    solve = functools.partial(ops.cd_tile_solve, mu=mu, nu=nu, lam1=lam1,
                              lam2=lam2, backend=backend)
    if penf is None:
        d_new = jax.vmap(
            lambda Gt, gt, ht, bt, dt: solve(Gt, gt, ht, bt, dt))(
            G_all, g_all, h_all, beta_r, dbeta_r)
    else:
        penf_r = penf.reshape(n_tiles_total, T)
        d_new = jax.vmap(
            lambda Gt, gt, ht, bt, dt, pt: solve(Gt, gt, ht, bt, dt,
                                                 penf=pt))(
            G_all, g_all, h_all, beta_r, dbeta_r, penf_r)

    live = alb_live_mask(n_tiles_total, start_tile, num_tiles)
    d_new = jnp.where(live[:, None], d_new, 0.0)
    if active is not None:
        d_new = jnp.where(active.reshape(n_tiles_total, T) > 0, d_new, 0.0)

    dbeta = d_new.reshape(p)
    return dbeta, G_full @ dbeta, jnp.minimum(num_tiles, n_tiles_total)


GRAM_SWEEPS = {"gauss-seidel": sweep_gauss_seidel_gram,
               "jacobi": sweep_jacobi_gram}
