"""d-GLMNET: distributed block-coordinate Newton descent for regularized GLMs.

Implements the paper's Algorithms 1–4 as one jitted SPMD "superstep"
(= one outer iteration), parameterized by mesh axis names so the same code
runs:

  * single-device (axis names None) — reference/unit-test path,
  * 1-D feature split over ``model`` (the paper's exact layout, D=1),
  * 2-D (data × model) — the beyond-paper scale-out (DESIGN.md §3),

with the host loop only checking convergence and recording history.

Superstep structure (paper Algorithm 4):
  1. link stats (s, w, loss) at β from the maintained margin Xβ    [glm_stats]
  2. local tile CD sweep over this node's feature block            [cd.py]
  3. AllReduce XΔβ over the feature axis (optionally compressed)
  4. global line search for α; Armijo with α_init pre-search     [linesearch]
  5. β += αΔβ, Xβ += α·XΔβ; trust-region μ update (Algorithm 1 lines 9–12)
  6. ALB cursor/budget bookkeeping (Section 7)
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cd as cd_lib
from repro.core import linesearch
from repro.data import design as design_lib
from repro.kernels import ops
from repro.sharding.compress import psum_compressed


@dataclasses.dataclass(frozen=True)
class DGLMNETConfig:
    family: str = "logistic"
    # default regularization — λ is a *runtime* argument of the compiled
    # superstep (solver.GLMSolver passes per-fit values, so one compiled
    # superstep serves a whole λ-path); these fields only seed the default
    lam1: float = 0.0
    lam2: float = 0.0
    # trust region (paper Algorithm 1 / Section 4):
    mu_init: float = 1.0
    adaptive_mu: bool = True
    eta1: float = 2.0
    eta2: float = 2.0
    nu: float = 1e-6
    # line search (paper Algorithm 3):
    sigma: float = 0.01
    backtrack_b: float = 0.5
    gamma: float = 0.0
    ls_delta: float = 1e-3
    ls_grid_size: int = 13
    max_backtracks: int = 20
    # sweep:
    tile_size: int = 256
    coupling: str = "gauss-seidel"          # or "jacobi"
    kernel_backend: Optional[str] = None    # None = auto (ref on CPU)
    # fused superstep fast path (DESIGN.md §8): collapse the
    # stats→Gram→solve and margin→line-search chains into two launches.
    # Applies to single-device jacobi supersteps (collectives pin the
    # distributed path to the unfused launch structure); elsewhere inert.
    fuse_superstep: bool = True
    # "fp32" | "bf16": matmul-input precision of the fused Gram/margin
    # accumulations (accumulation + masters + Armijo sums stay fp32)
    precision: str = "fp32"
    # distribution:
    compress_margin: Optional[str] = None   # None | "bf16" | "int8"
    # ALB (Section 7): None = BSP (P^m = S^m every superstep)
    alb: bool = False
    alb_kappa: float = 0.75
    # outer loop:
    max_outer: int = 100
    tol: float = 1e-8
    # layout of a ``SparseRows`` input (data/design.py HeadTailDesign): the
    # number of most frequent features held dense, a multiple of tile_size
    head_features: Optional[int] = None
    # model: an unpenalized intercept column (GLMSolver's ``fit_intercept``
    # argument, where given, overrides this)
    fit_intercept: bool = False


class FitState(NamedTuple):
    beta: jnp.ndarray      # (p_loc,) feature-sharded weights
    xb: jnp.ndarray        # (n_loc,) margins Xβ (model-replicated)
    mu: jnp.ndarray        # () trust-region scale, replicated
    cursor: jnp.ndarray    # (1,) per-feature-shard ALB tile cursor
    step: jnp.ndarray      # () int32


class FitResult(NamedTuple):
    beta: np.ndarray
    history: dict
    n_iter: int
    converged: bool


def _psum(x, axis):
    return jax.lax.psum(x, axis) if axis is not None else x


def make_superstep(config: DGLMNETConfig, *, axis_data=None, axis_model=None,
                   n_tiles_local: int, max_budget: Optional[int] = None):
    """Build the jittable superstep closure.

    ``X`` may be a raw (n_loc, p_loc) dense array (wrapped into a
    ``DenseDesign`` on the fly) or any ``DesignMatrix`` pytree — e.g. the
    sharded ``BlockSparseDesign`` whose leaves the partitioner has already
    localized.  The observation model is carried by three RUNTIME row/
    feature vectors (so folds, weights and penalty layouts swap with zero
    recompiles):

      * ``weights`` (n_loc,): combined per-example observation weight —
        sample weight × CV fold mask × row-padding mask;
      * ``offset`` (n_loc,): fixed margin offsets (loss at ``Xβ + o``);
      * ``penf``   (p_loc,): per-coordinate penalty factors (0 = the
        unpenalized intercept column).

    ``budget`` is (1,) int32 per feature shard.  ``lams`` is a (2,)
    [λ1, λ2] runtime array (replicated) — λ is NOT baked into the closure,
    so one compiled superstep serves a whole regularization path
    (solver.GLMSolver.fit_path).  ``active`` is a (p_loc,) 0/1 screening
    mask (feature-sharded); coordinates with ``active == 0`` are frozen
    during the CD sweep (strong-rule/KKT active-set screening).
    """
    sweep = cd_lib.SWEEPS[config.coupling]
    backend = config.kernel_backend
    fam = config.family
    static_bound = int(max_budget if max_budget is not None else n_tiles_local)

    # Fused fast path (DESIGN.md §8): jacobi coupling, single device only —
    # the xdb merge and the Armijo sums are collectives when sharded, and a
    # collective is a launch boundary, so the distributed superstep keeps
    # the unfused structure.  Backend resolved at build time: "pallas" gets
    # the one-pass margin+line-search launch (all 294 candidate losses in
    # one sweep); "ref" keeps the two-phase search (grid then chain), which
    # is cheaper when XLA is fusing everything into one CPU program anyway.
    use_fused = (config.fuse_superstep and config.coupling == "jacobi"
                 and axis_data is None and axis_model is None)
    resolved_backend = backend or ops.default_backend()
    one_pass_ls = resolved_backend == "pallas"

    def superstep_fused(X, y, weights, offset, budget, lams, active, penf,
                        state: FitState):
        design = design_lib.as_local_design(X, config.tile_size)
        beta, xb, mu, cursor, step = state
        lam1, lam2 = lams[0], lams[1]
        T = config.tile_size
        nt = n_tiles_local
        # a head/tail design runs its dense head through the two fused
        # launches and its sparse tail through one XLA step between them:
        # one exact coordinate step per tail feature, Jacobi-coupled, over
        # the working set of tail columns the screening lets move
        tail = design if isinstance(design, design_lib.HeadTailDesign) \
            else None
        if tail is None:
            swept, head_of = design, (lambda v: v)
        else:
            swept, head_of = tail.head, (lambda v: tail.split(v)[0])
        nt_swept = head_of(beta).shape[0] // T

        # tile occupancy = ALB budget window ∧ any-active-coordinate: dead
        # tiles cost no Gram/solve work (active-set-shaped launch)
        alb_live = cd_lib.alb_live_mask(nt_swept, cursor[0], budget[0])
        tile_act = jnp.any(head_of(active).reshape(nt_swept, T) > 0, axis=1)
        tile_live = alb_live & tile_act

        # (1+2) fused launch: stats + every live tile's Gram/gradient +
        # the Jacobi tile solves, one pass over the rows
        loss_i, s, w, dbeta, _, _ = ops.fused_stats_sweep(
            swept, y, xb, head_of(beta), fam, mu=mu, nu=config.nu,
            lam1=lam1, lam2=lam2, weights=weights, offset=offset,
            penf=head_of(penf), tile_live=tile_live,
            precision=config.precision, backend=backend)
        dbeta = jnp.where(head_of(active) > 0, dbeta, 0.0)
        xdb_tail = None
        if tail is not None:
            # the tail between the launches: each feature's gradient and
            # diagonal Hessian, one exact coordinate step, its margin delta
            _, beta_t = tail.split(beta)
            with jax.named_scope("head_tail/tail_stats"):
                g_t, h_t = tail.tail_stats_ws(s, w)
                dbeta_t = jnp.where(tail.split(active)[1] > 0,
                                    cd_lib.coordinate_prox(
                                        g_t, h_t, beta_t, tail.split(penf)[1],
                                        mu=mu, nu=config.nu, lam1=lam1,
                                        lam2=lam2), 0.0)
            with jax.named_scope("head_tail/tail_margin"):
                xdb_tail = tail.tail_matvec_ws(dbeta_t)
            dbeta = jnp.concatenate([dbeta, dbeta_t])
        L = jnp.sum(loss_i)
        R0 = linesearch.penalty_terms(beta, jnp.zeros_like(beta),
                                      jnp.zeros((1,)), lam1, lam2, None,
                                      penf)[0]
        f_cur = L + R0

        # (3+4) fused launch: margin delta + candidate losses; Algorithm-3
        # selection happens on the accumulated scalars (same decisions as
        # linesearch.search — see select_precomputed).  A head/tail design
        # scores each candidate by its change of the objective, summed row
        # by row and coordinate by coordinate, and reports the chosen
        # change ("df") for the outer loop's stopping test: a hashed
        # design's supersteps move a large objective by less than the
        # rounding of its float32 sum, which the differences of whole sums
        # would turn into accepted increases and early stops
        if one_pass_ls or tail is not None:
            cand = linesearch.full_candidates(
                config.ls_delta, config.ls_grid_size, config.backtrack_b,
                config.max_backtracks)
            xdb, losses = ops.fused_ls(
                swept, y, xb, head_of(dbeta), cand, fam, weights=weights,
                offset=offset, precision=config.precision, backend=backend,
                xdb_base=xdb_tail, relative=tail is not None)
            grad_dot_dir = -jnp.sum(s * xdb)
            quad_form = (mu * jnp.sum(w * xdb * xdb)
                         + config.nu * jnp.sum(dbeta * dbeta))
            select = dict(grad_dot_dir=grad_dot_dir, quad_form=quad_form,
                          sigma=config.sigma, gamma=config.gamma,
                          grid_size=config.ls_grid_size,
                          max_backtracks=config.max_backtracks)
            if tail is None:
                ls = linesearch.select_precomputed(
                    losses, cand, beta, dbeta, lam1, lam2, f_current=f_cur,
                    penf=penf, **select)
            else:
                # the penalty's changes over the head, and over the tail
                # columns the working set lets move
                pen = lambda b, d, pf: linesearch.penalty_changes(
                    b, d, cand, lam1, lam2, pf)
                dpens = pen(head_of(beta), head_of(dbeta), head_of(penf)) \
                    + tail.over_working_set(pen, beta_t, dbeta_t,
                                            tail.split(penf)[1])
                ls = linesearch.select_changes(losses, dpens, cand, **select)
                df = ls.f_new
                ls = ls._replace(f_new=f_cur + df)
        else:
            xdb = design.matvec(dbeta)
            grad_dot_dir = -jnp.sum(s * xdb)
            quad_form = (mu * jnp.sum(w * xdb * xdb)
                         + config.nu * jnp.sum(dbeta * dbeta))
            ls = linesearch.search(
                y, xb, xdb, beta, dbeta, family=fam,
                lam1=lam1, lam2=lam2, mu=mu, nu=config.nu,
                f_current=f_cur, grad_dot_dir=grad_dot_dir,
                quad_form=quad_form, sigma=config.sigma,
                b=config.backtrack_b, gamma=config.gamma,
                delta=config.ls_delta, grid_size=config.ls_grid_size,
                max_backtracks=config.max_backtracks, weights=weights,
                offset=offset, penf=penf, backend=backend)

        # (5+6) identical to the unfused superstep
        beta_new = beta + ls.alpha * dbeta
        xb_new = xb + ls.alpha * xdb
        if config.adaptive_mu:
            mu_new = jnp.where(ls.alpha < 1.0, config.eta1 * mu,
                               jnp.maximum(1.0, mu / config.eta2))
        else:
            mu_new = mu
        tiles_done = jnp.minimum(budget[0], nt)
        cursor_new = jnp.remainder(cursor + tiles_done, nt)
        nnz = jnp.sum((beta_new != 0.0).astype(jnp.int32))
        metrics = {
            "f": ls.f_new, "f_before": f_cur, "loss": L,
            "alpha": ls.alpha, "mu": mu_new, "nnz": nnz,
            "accepted_unit": ls.accepted_unit.astype(jnp.int32),
            "D": ls.D,
        }
        if tail is not None:
            metrics["df"] = df
        return FitState(beta_new, xb_new, mu_new, cursor_new, step + 1), \
            metrics

    def superstep(X, y, weights, offset, budget, lams, active, penf,
                  state: FitState):
        design = design_lib.as_local_design(X, config.tile_size)
        beta, xb, mu, cursor, step = state
        lam1, lam2 = lams[0], lams[1]

        # (1) link statistics at the current iterate (weighted, offset)
        loss_i, s, w = ops.glm_stats(y, xb, fam, weights=weights,
                                     offset=offset, backend=backend)
        L = _psum(jnp.sum(loss_i), axis_data)
        R0 = linesearch.penalty_terms(beta, jnp.zeros_like(beta),
                                      jnp.zeros((1,)), lam1,
                                      lam2, axis_model, penf)[0]
        f_cur = L + R0

        # (2) local quadratic sub-problem: one (budgeted) tile CD cycle
        dbeta0 = jnp.zeros_like(beta)
        xdb0 = jnp.zeros_like(xb)
        dbeta, xdb_local, tiles_done = sweep(
            design, s, w, beta, dbeta0, xdb0,
            mu=mu, nu=config.nu, lam1=lam1, lam2=lam2,
            start_tile=cursor[0],
            num_tiles=budget[0], max_num_tiles=static_bound,
            active=active, penf=penf,
            axis_data=axis_data, backend=backend)

        # (3) merge margin deltas across feature blocks (paper step 6)
        xdb = psum_compressed(xdb_local, axis_model, config.compress_margin)

        # (4) line search (weighted Armijo sums — s/w already carry weights)
        grad_dot_dir = _psum(-jnp.sum(s * xdb), axis_data)
        quad_local = _psum(jnp.sum(w * xdb_local * xdb_local), axis_data)
        quad_form = (mu * _psum(quad_local, axis_model)
                     + config.nu * _psum(jnp.sum(dbeta * dbeta), axis_model))
        ls = linesearch.search(
            y, xb, xdb, beta, dbeta, family=fam,
            lam1=lam1, lam2=lam2, mu=mu, nu=config.nu,
            f_current=f_cur, grad_dot_dir=grad_dot_dir, quad_form=quad_form,
            sigma=config.sigma, b=config.backtrack_b, gamma=config.gamma,
            delta=config.ls_delta, grid_size=config.ls_grid_size,
            max_backtracks=config.max_backtracks, weights=weights,
            offset=offset, penf=penf,
            axis_data=axis_data, axis_model=axis_model, backend=backend)

        # (5) apply the step; adapt μ (Algorithm 1 lines 8–12)
        beta_new = beta + ls.alpha * dbeta
        xb_new = xb + ls.alpha * xdb
        if config.adaptive_mu:
            mu_new = jnp.where(ls.alpha < 1.0, config.eta1 * mu,
                               jnp.maximum(1.0, mu / config.eta2))
        else:
            mu_new = mu

        # (6) ALB cursor rotation (Section 7)
        cursor_new = jnp.remainder(cursor + tiles_done, n_tiles_local)

        nnz = _psum(jnp.sum((beta_new != 0.0).astype(jnp.int32)), axis_model)
        metrics = {
            "f": ls.f_new, "f_before": f_cur, "loss": L,
            "alpha": ls.alpha, "mu": mu_new, "nnz": nnz,
            "accepted_unit": ls.accepted_unit.astype(jnp.int32),
            "D": ls.D,
        }
        return FitState(beta_new, xb_new, mu_new, cursor_new, step + 1), metrics

    return superstep_fused if use_fused else superstep


# ---------------------------------------------------------------------------
# streaming superstep (out-of-core row chunks, DESIGN.md §6)
# ---------------------------------------------------------------------------


class StreamingSuperstep(NamedTuple):
    """The jitted pieces of one out-of-core outer iteration.

    A streaming superstep is the in-memory superstep re-cut at the chunk
    boundary: per-example work happens inside per-chunk kernels, everything
    feature-sized runs once per iteration from accumulated statistics.

      pass 1   stats_chunk × n_chunks — accumulate (G_w = XᵀWX, g0 = Xᵀs,
               L = Σ w·l) over double-buffered chunks (margins Xβ are
               re-materialized per chunk, never carried);
      sweep    prepare — budgeted gram-mode CD sweep (cd.GRAM_SWEEPS: exact
               Gauss-Seidel/Jacobi tile coupling via g_t = g0_t − μ(G_wΔβ)_t)
               plus the line-search scalars and the full candidate-α set;
      pass 2   ls_chunk × n_chunks — ONE chunk pass accumulates the losses
               of EVERY line-search candidate (the unit step, the α-init
               grid, and all backtracking chains α_i·b^j), so the Armijo
               selection needs no further data passes;
      finish   — Algorithm-3 selection over the accumulated candidate
               losses, β/μ/cursor update, metrics (same keys as the
               in-memory superstep).
    """
    stats_chunk: object
    prepare: object
    ls_chunk: object
    finish: object
    n_candidates: int


def make_streaming_superstep(config: DGLMNETConfig,
                             on_trace=None) -> StreamingSuperstep:
    """Build the jitted per-chunk/per-iteration pieces for streaming fits.

    Shapes are bound at first call (one compile per chunk geometry);
    ``on_trace`` is an optional trace-time callback (compile counting).
    The candidate-α layout is ``[1, grid(ls_grid_size)]`` followed by the
    ``max_backtracks`` backtracking chain of each of those candidates, so
    ``finish`` can read the chain of the argmin candidate with a dynamic
    slice — replicating ``linesearch.search`` exactly from per-candidate
    loss sums alone.
    """
    backend = config.kernel_backend
    fam = config.family
    T = config.tile_size
    sweep = cd_lib.GRAM_SWEEPS[config.coupling]
    K0 = 1 + config.ls_grid_size
    B = config.max_backtracks

    def _candidates():
        return linesearch.full_candidates(config.ls_delta,
                                          config.ls_grid_size,
                                          config.backtrack_b, B)

    @functools.partial(jax.jit, donate_argnums=(5,))
    def stats_chunk(Xc, yc, wc, oc, beta, acc):
        G, g0, L = acc
        if on_trace is not None:
            on_trace()
        xb = Xc @ beta
        loss_i, s, w = ops.glm_stats(yc, xb, fam, weights=wc, offset=oc,
                                     backend=backend)
        return (G + (Xc * w[:, None]).T @ Xc, g0 + Xc.T @ s,
                L + jnp.sum(loss_i))

    @jax.jit
    def prepare(acc, beta, mu, lams, active, penf, cursor, budget):
        G, g0, L = acc
        lam1, lam2 = lams[0], lams[1]
        R0 = linesearch.penalty_terms(beta, jnp.zeros_like(beta),
                                      jnp.zeros((1,)), lam1, lam2, None,
                                      penf)[0]
        dbeta, u, tiles_done = sweep(
            G, g0, beta, mu=mu, nu=config.nu, lam1=lam1, lam2=lam2,
            tile_size=T, start_tile=cursor[0], num_tiles=budget[0],
            active=active, penf=penf, backend=backend)
        return {
            "dbeta": dbeta,
            "cand": _candidates(),
            "loss": L,
            "f_cur": L + R0,
            "R0": R0,
            "grad_dot_dir": -jnp.dot(g0, dbeta),
            "quad_form": mu * jnp.dot(dbeta, u)
            + config.nu * jnp.dot(dbeta, dbeta),
            "tiles_done": tiles_done,
        }

    @functools.partial(jax.jit, donate_argnums=(7,))
    def ls_chunk(Xc, yc, wc, oc, beta, dbeta, cand, losses):
        xb = Xc @ beta
        xdb = Xc @ dbeta
        return losses + ops.alpha_search(yc, xb, xdb, cand, fam,
                                         weights=wc, offset=oc,
                                         backend=backend)

    @jax.jit
    def finish(losses, prep, state: FitState, lams, penf):
        beta, xb, mu, cursor, step = state
        lam1, lam2 = lams[0], lams[1]
        dbeta, cand = prep["dbeta"], prep["cand"]
        f_cur = prep["f_cur"]
        # Algorithm 3 through the SAME helpers as linesearch.search —
        # unit step, α_init grid argmin, Armijo backtracking over
        # α_init·b^j — but the candidate losses were all accumulated in
        # ONE chunk pass, so the backtracking chain of the argmin is a
        # dynamic slice instead of a second data pass (the shared
        # selection with the fused superstep fast path, DESIGN.md §8).
        ls = linesearch.select_precomputed(
            losses, cand, beta, dbeta, lam1, lam2, f_current=f_cur,
            grad_dot_dir=prep["grad_dot_dir"], quad_form=prep["quad_form"],
            sigma=config.sigma, gamma=config.gamma,
            grid_size=config.ls_grid_size, max_backtracks=B, penf=penf)

        beta_new = beta + ls.alpha * dbeta
        if config.adaptive_mu:
            mu_new = jnp.where(ls.alpha < 1.0, config.eta1 * mu,
                               jnp.maximum(1.0, mu / config.eta2))
        else:
            mu_new = mu
        n_tiles = beta.shape[0] // T
        cursor_new = jnp.remainder(cursor + prep["tiles_done"], n_tiles)
        metrics = {
            "f": ls.f_new, "f_before": f_cur, "loss": prep["loss"],
            "alpha": ls.alpha, "mu": mu_new,
            "nnz": jnp.sum((beta_new != 0.0).astype(jnp.int32)),
            "accepted_unit": ls.accepted_unit.astype(jnp.int32),
            "D": ls.D,
        }
        return FitState(beta_new, xb, mu_new, cursor_new, step + 1), metrics

    return StreamingSuperstep(stats_chunk, prepare, ls_chunk, finish,
                              K0 * (1 + B))


# ---------------------------------------------------------------------------
# deprecated one-shot drivers (thin wrappers over solver.GLMSolver)
# ---------------------------------------------------------------------------

_DEPRECATION_WARNED: set = set()


def _warn_deprecated(name: str):
    import warnings
    if name in _DEPRECATION_WARNED:
        return
    _DEPRECATION_WARNED.add(name)
    warnings.warn(
        f"repro.core.dglmnet.{name} is deprecated; construct a "
        "repro.core.solver.GLMSolver session instead — it packs/places the "
        "design and compiles the superstep once and supports warm-started "
        "λ-path fitting (solver.fit / solver.fit_path).",
        DeprecationWarning, stacklevel=3)


def fit(X, y, config: DGLMNETConfig, *, beta0=None, verbose=False,
        design_info=None) -> FitResult:
    """DEPRECATED one-shot single-device fit — use ``GLMSolver(...).fit()``.

    X: (n, p) dense array-like, a ``SparseCOO`` (trained through the
    blocked-sparse brick layout without densifying the full matrix), or a
    pre-built ``DesignMatrix`` (a ``BlockSparseDesign`` requires the
    builder's ``DesignInfo`` as ``design_info`` so β can be mapped back to
    the original feature order).
    """
    _warn_deprecated("fit")
    from repro.core.solver import GLMSolver
    solver = GLMSolver(X, y, config=config, design_info=design_info)
    return solver.fit(beta0=beta0, verbose=verbose)


def fit_sharded(X, y, config: DGLMNETConfig, mesh, *,
                axis_data: Optional[str] = "data",
                axis_model: str = "model",
                speeds=None, seed: int = 0, verbose=False,
                ckpt_manager=None, ckpt_every: int = 10,
                row_block: int = 256, reorder: bool = True,
                design_info=None) -> FitResult:
    """DEPRECATED one-shot sharded fit — use ``GLMSolver(..., mesh=mesh)``.

    Semantics are identical to the historical driver (rows over
    ``axis_data``, features over ``axis_model``, optional ALB speeds and
    superstep-boundary checkpointing); the session object it now delegates
    to simply makes the design packing / placement / compilation reusable
    across fits.
    """
    _warn_deprecated("fit_sharded")
    from repro.core.solver import GLMSolver
    solver = GLMSolver(X, y, config=config, mesh=mesh, axis_data=axis_data,
                       axis_model=axis_model, speeds=speeds, seed=seed,
                       row_block=row_block, reorder=reorder,
                       design_info=design_info)
    return solver.fit(verbose=verbose, ckpt_manager=ckpt_manager,
                      ckpt_every=ckpt_every)
