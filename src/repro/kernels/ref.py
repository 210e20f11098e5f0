"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantics contract: tests sweep shapes/dtypes and assert the
pallas kernels (interpret mode on CPU, compiled on TPU) match these to float
tolerance.  They are also the fallback implementation on backends without
Pallas support.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import glm as glm_lib


# ---------------------------------------------------------------------------
# cd_tile_solve: sequential Gauss-Seidel soft-threshold pass over one feature
# tile, using the tile Gram matrix (GLMNET "covariance updates" re-blocked).
# ---------------------------------------------------------------------------

def cd_tile_solve(G, g, h, beta_t, dbeta_t, mu, nu, lam1, lam2, penf=None):
    """One cyclic pass of exact coordinate minimization over a feature tile.

    Args:
      G: (T, T)  tile Gram block  X_t^T diag(w) X_t  (row-psummed upstream).
      g: (T,)    g_k = sum_i x_ik [ s_i - mu * w_i * (X dbeta)_i ]   at tile
                 entry, where (X dbeta) is the *local block's* current margin
                 delta (Gauss-Seidel across tiles).
      h: (T,)    diag(G) = sum_i w_i x_ik^2.
      beta_t:  (T,) current outer-iterate weights for the tile (FIXED).
      dbeta_t: (T,) current accumulated step for the tile (updated).
      mu, nu, lam1, lam2: scalars (see DESIGN.md update rule).
      penf: optional (T,) per-coordinate penalty factors — coordinate j sees
        the effective penalties (lam1 penf_j, lam2 penf_j); penf_j = 0 is an
        unpenalized coordinate (intercept).  None = all ones.

    Returns:
      (T,) new dbeta_t.

    Invariant used: updating coordinate j by delta changes
    g_k by  -mu * delta * G[k, j]  for every k — no re-touch of X needed.
    """
    T = g.shape[0]
    pf = jnp.ones_like(g) if penf is None else penf
    lam1v = lam1 * pf
    lam2v = lam2 * pf
    den = mu * h + nu + lam2v

    def body(j, carry):
        g_c, d_c = carry
        num = g_c[j] + mu * h[j] * (beta_t[j] + d_c[j]) + nu * beta_t[j]
        u = glm_lib.soft_threshold(num, lam1v[j]) / jnp.maximum(den[j], 1e-30)
        # dead coordinate (all-zero column, nu == lam2 == 0): keep at 0
        u = jnp.where(den[j] > 0, u, beta_t[j])
        d_new = u - beta_t[j]
        delta = d_new - d_c[j]
        g_c = g_c - mu * delta * G[:, j]
        d_c = d_c.at[j].set(d_new)
        return g_c, d_c

    _, dbeta_new = jax.lax.fori_loop(0, T, body, (g, dbeta_t))
    return dbeta_new


# ---------------------------------------------------------------------------
# tile_gram: brick-gather Gram/gradient for one feature tile of the
# CSR-of-bricks layout (DESIGN.md §2).
# ---------------------------------------------------------------------------

def tile_gram(bricks, rows, n_valid, w2, r2):
    """G = Σ_k b_kᵀ diag(w[rows[k]]) b_k,  g = Σ_k b_kᵀ r[rows[k]].

    bricks: (K, rb, T) gathered bricks of ONE feature tile (K is the static
            max_bricks_per_tile bound; entries at k >= n_valid are ignored).
    rows:   (K,) i32 row-block index per brick (in-range even when invalid).
    n_valid: () i32 — number of live bricks.
    w2, r2: (n_row_blocks, rb) — w and the residual r, row-block-reshaped.

    Returns (G (T, T), g (T,)).
    """
    K = bricks.shape[0]
    mask = (jnp.arange(K) < n_valid).astype(bricks.dtype)
    b = bricks * mask[:, None, None]
    wk = w2[rows]                                  # (K, rb)
    rk = r2[rows]
    G = jnp.einsum("kit,kiu->tu", b * wk[:, :, None], b)
    g = jnp.einsum("kit,ki->t", b, rk)
    return G, g


# ---------------------------------------------------------------------------
# glm_stats: fused per-example link statistics.
# ---------------------------------------------------------------------------

def glm_stats(y, xb, weights, family, offset=None):
    """(loss_i, s_i, w_i) at margins ``xb + offset``, scaled by the
    per-example ``weights`` (observation weights; padding rows carry 0)."""
    fam = glm_lib.resolve_family(family)
    return fam.stats(y, xb, weights=weights, offset=offset)


def multinomial_stats(y, margins, weights=None, offset=None):
    """K-column oracle for the softmax family: margins are (n, K), labels
    integer class ids, s and w come back (n, K) (loss stays (n,)).

    There is no Pallas stats body for multinomial — ``ops.glm_stats``
    falls back to this jnp path automatically, and the class-cycling
    solver only ever needs the scalar logistic kernel anyway
    (``glm/estimators.py`` MultinomialGLM).
    """
    fam = glm_lib.resolve_family("multinomial")
    return fam.stats(y, margins, weights=weights, offset=offset)


# ---------------------------------------------------------------------------
# alpha_search: K-candidate line-search objective sweep in one data pass.
# ---------------------------------------------------------------------------

def alpha_search(y, xb, xdb, weights, alphas, family, offset=None,
                 relative=False):
    """losses[k] = sum_i weights_i * l(y_i, xb_i + o_i + alphas[k] * xdb_i),
    less sum_i weights_i * l(y_i, xb_i + o_i) row by row with ``relative``.

    Shapes: y, xb, xdb, weights[, offset]: (n,);  alphas: (K,);  out: (K,).
    """
    fam = glm_lib.resolve_family(family)
    if offset is not None:
        xb = xb + offset
    m = xb[None, :] + alphas[:, None] * xdb[None, :]        # (K, n)
    loss, _, _ = fam.stats(y[None, :], m)
    if relative:
        loss = loss - fam.stats(y, xb)[0][None, :]
    return jnp.sum(loss * weights[None, :], axis=-1)


# ---------------------------------------------------------------------------
# fused superstep (DESIGN.md §8): stats + all-tile Gram (+ solve upstream in
# ops) in one pass, and margin-delta + candidate-loss in one pass.  These are
# the oracles for kernels/superstep_tile.py and the CPU/unknown-family
# fallback of the fused fast path.
# ---------------------------------------------------------------------------

def _acc_dtype(precision):
    """Matmul INPUT dtype of the fused Gram/margin accumulations: bf16 under
    ``precision="bf16"`` (accumulation itself stays f32 via
    ``preferred_element_type``), f32 otherwise.  Masters and Armijo loss sums
    are always f32 (DESIGN.md §8 precision policy)."""
    return jnp.bfloat16 if precision == "bf16" else jnp.float32


def gram_dense_tiles(Xt3, w, r, precision="fp32"):
    """(G_all (nt, T, T), g_all (nt, T)) from the tile-major transposed dense
    layout Xt3 (nt, n, T): one batched MXU matmul per quantity instead of an
    einsum re-gather of the (n, p) array."""
    dt = _acc_dtype(precision)
    Xc = Xt3.astype(dt)
    wX = (Xt3 * w[None, :, None]).astype(dt)
    G = jnp.matmul(jnp.swapaxes(wX, 1, 2), Xc,
                   preferred_element_type=jnp.float32)
    g = jnp.matmul(jnp.swapaxes(Xc, 1, 2), r.astype(dt)[None, :, None],
                   preferred_element_type=jnp.float32)[..., 0]
    return G, g


def gram_brick_tiles(b3, rows, valid, w, r, precision="fp32"):
    """(G_all, g_all) from the batched brick layout of
    ``BlockSparseDesign.gather_all_tiles``: b3 (nt, K, rb, T), rows (nt, K)
    row-block ids, valid (nt, K) 0/1.  Each tile's K bricks are flattened to
    one (K·rb, T) operand so the whole sweep is a single batched matmul."""
    nt, K, rb, T = b3.shape
    b3f = b3.reshape(nt, K * rb, T)
    w2 = w.reshape(-1, rb)
    r2 = r.reshape(-1, rb)
    wk = (w2[rows] * valid[..., None]).reshape(nt, K * rb, 1)
    rk = (r2[rows] * valid[..., None]).reshape(nt, K * rb, 1)
    dt = _acc_dtype(precision)
    G = jnp.matmul(jnp.swapaxes((b3f * wk).astype(dt), 1, 2), b3f.astype(dt),
                   preferred_element_type=jnp.float32)
    g = jnp.matmul(jnp.swapaxes(b3f.astype(dt), 1, 2), rk.astype(dt),
                   preferred_element_type=jnp.float32)[..., 0]
    return G, g


def shaped_tile_grams(n_tiles, gram_of_ids, gram_full, tile_live):
    """Active-set-shaped Gram launch: when few enough tiles are live, gather
    the live tiles into a static-size compact batch (live-first order),
    compute only those Grams, and scatter back zeros elsewhere.

    ``gram_of_ids(ids (k,)) -> (G (k, T, T), g (k, T))``; ``gram_full()`` the
    unshaped computation.  Branching is a runtime ``lax.cond`` over two
    static compaction sizes (nt/2, nt/4), so one compiled superstep serves
    every active-set size with no retraces; dead tiles get G = g = 0, which
    the tile solve maps to Δβ = 0 (den ≥ ν > 0), and the caller masks Δβ by
    tile liveness anyway.  Screening therefore buys wall-clock, not just
    FLOPs (ISSUE 6 tentpole b).
    """
    if tile_live is None or n_tiles < 8:
        return gram_full()
    live_i = tile_live.astype(jnp.int32)
    order = jnp.argsort(1 - live_i, stable=True).astype(jnp.int32)
    n_live = jnp.sum(live_i)

    def compact(n_sub):
        def fn():
            ids = order[:n_sub]
            G_s, g_s = gram_of_ids(ids)
            G = jnp.zeros((n_tiles,) + G_s.shape[1:], G_s.dtype)
            g = jnp.zeros((n_tiles,) + g_s.shape[1:], g_s.dtype)
            return G.at[ids].set(G_s), g.at[ids].set(g_s)
        return fn

    return jax.lax.cond(
        n_live <= n_tiles // 4, compact(max(n_tiles // 4, 1)),
        lambda: jax.lax.cond(n_live <= n_tiles // 2,
                             compact(n_tiles // 2), gram_full))


def fused_stats_gram_dense(Xt3, y, xb, weights, family, offset=None,
                           tile_live=None, precision="fp32"):
    """Oracle for the fused stats→Gram launch on the dense tile-major layout:
    (loss_i, s, w, G_all, g_all) — the link stats and every tile's
    Gram/gradient from ONE conceptual pass over the rows."""
    loss_i, s, w = glm_stats(y, xb, weights, family, offset=offset)
    nt = Xt3.shape[0]
    G, g = shaped_tile_grams(
        nt, lambda ids: gram_dense_tiles(Xt3[ids], w, s, precision),
        lambda: gram_dense_tiles(Xt3, w, s, precision), tile_live)
    return loss_i, s, w, G, g


def fused_stats_gram_bricks(b3, rows, valid, y, xb, weights, family,
                            offset=None, tile_live=None, precision="fp32"):
    """Brick-layout twin of ``fused_stats_gram_dense``."""
    loss_i, s, w = glm_stats(y, xb, weights, family, offset=offset)
    nt = b3.shape[0]
    G, g = shaped_tile_grams(
        nt,
        lambda ids: gram_brick_tiles(b3[ids], rows[ids], valid[ids], w, s,
                                     precision),
        lambda: gram_brick_tiles(b3, rows, valid, w, s, precision),
        tile_live)
    return loss_i, s, w, G, g


def fused_ls_dense(Xt3, y, xb, dbeta, weights, alphas, family, offset=None,
                   precision="fp32", xdb_base=None, relative=False):
    """Oracle for the fused margin→line-search launch: apply the margin
    delta (xdb = XΔβ, accumulated over tiles, plus ``xdb_base`` when
    given) and evaluate every candidate step's loss in the same pass.
    Returns (xdb (n,), losses (K,))."""
    nt, n, T = Xt3.shape
    dt = _acc_dtype(precision)
    dr = dbeta.reshape(nt, T).astype(dt)
    xdb = jnp.sum(jnp.matmul(Xt3.astype(dt), dr[:, :, None],
                             preferred_element_type=jnp.float32)[..., 0],
                  axis=0)
    if xdb_base is not None:
        xdb = xdb + xdb_base
    losses = alpha_search(y, xb, xdb, weights, alphas, family, offset=offset,
                          relative=relative)
    return xdb, losses


# ---------------------------------------------------------------------------
# predict_tile: fused sparse scoring (gather + dot + link) for serving.
# ---------------------------------------------------------------------------

def predict_tile(slots, vals, table, b0, family, kind="link"):
    """out[b, l] = link(Σ_j vals[b, j] · table[slots[b, j], l] + b0[l]).

    slots: (B, J) i32 rows of the compacted weight table — padding / inactive
    features point at the table's trailing all-zero row; vals: (B, J) f32;
    table: (A+1, L) f32; b0: (1, L).  ``kind="link"`` returns raw margins,
    ``"response"`` the family's inverse link.
    """
    rows = jnp.take(table, slots, axis=0)                   # (B, J, L)
    m = jnp.einsum("bj,bjl->bl", vals.astype(jnp.float32), rows) + b0
    if kind == "link":
        return m
    fam = glm_lib.resolve_family(family)
    return fam.predict(m)
