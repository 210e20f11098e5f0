"""GLMSolver: session API for warm-started λ-path fitting (DESIGN.md §4–§5).

The paper's experiments — like every GLMNET-lineage solver — are run over a
regularization *path* (λ_max → λ_min with warm starts), but the historical
entry points (``dglmnet.fit`` / ``fit_sharded``) re-packed the design,
re-placed it on the mesh and re-jitted the superstep on every call.  A
``GLMSolver`` session does that setup exactly once:

    solver = GLMSolver(X, y, family="logistic", mesh=mesh,
                       sample_weight=w, offset=o, standardize=True,
                       fit_intercept=True, penalty_factor=pf)
    res  = solver.fit(lam1=1.0, lam2=0.1)        # one (λ1, λ2) point
    path = solver.fit_path(n_lambdas=100)        # warm-started λ-path
    cv   = solver.fit_cv(n_folds=5)              # mask-based K-fold CV
    yhat = solver.predict(X_test)

The full estimator-grade observation model (DESIGN.md §5) rides RUNTIME
arguments of one compiled superstep:

  * **per-example weights** — sample weights, CV fold masks and row-padding
    masks are the same multiply on (loss, s, w); the superstep takes the
    combined weight vector per call, so ``fit_cv`` runs every fold by
    swapping a row mask with ZERO recompiles and no data movement;
  * **margin offsets** — the loss is evaluated at ``Xβ + o``;
  * **per-feature penalty factors** — coordinate j sees (λ1·pf_j, λ2·pf_j);
    the unpenalized intercept is just the appended all-ones column with
    pf = 0;
  * **standardization** — weighted column moments come from the
    ``DesignMatrix.col_moments`` operator; the placed design is rescaled
    (and, for dense layouts with an intercept, centered) in place, and β is
    mapped back to the original scale on the way out.

Three mechanisms make repeated fitting cheap:

  * **λ as a runtime argument** — the superstep takes a (2,) ``[λ1, λ2]``
    array (``dglmnet.make_superstep``), so one compiled superstep serves all
    λs of a path, all CV folds, and all subsequent ``fit`` calls.
  * **a module-level compiled-superstep cache** keyed on
    (config-sans-λ, layout geometry, mesh axes) — even *separate* sessions
    (e.g. repeated calls to the deprecated one-shot drivers) reuse the
    compiled superstep instead of re-jitting.
  * **active-set screening** — ``fit_path`` seeds each λ with the sequential
    strong rule |Xᵀs(β_prev)|_j ≥ pf_j (2λ_k − λ_{k−1}), freezes cold
    coordinates during the CD sweeps, and verifies the KKT conditions on the
    full gradient afterwards (re-fitting with violators added, so the screen
    can never change the solution).

``lambda_max(X, y, family)`` gives the smallest λ1 for which β = 0 is
optimal — by the KKT conditions of the elastic-net problem, β = 0 iff
λ1 ≥ max_j |[Xᵀ s(0)]_j| / pf_j over penalized coordinates, where s(0) is
the negative margin-gradient at zero margins (plus offsets).  The session
method refines this to the NULL model when unpenalized coordinates exist:
the intercept is fitted first, so the path head is genuinely all-zero in
the penalized coordinates.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import os
import time
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import compile_cache
from repro.core import dglmnet, glm
from repro.core.dglmnet import DGLMNETConfig, FitResult, FitState
from repro.data import design as design_lib
from repro.data.design import (BlockSparseDesign, DesignMatrix,
                               HeadTailDesign, SparseCOO, SparseRows,
                               StreamingDesign)
from repro.dist import bootstrap as dist_boot
from repro.kernels import ops
from repro.obs import convergence as conv_lib
from repro.obs import trace as obs_trace
from repro.sharding import compat

_METRIC_KEYS = ("f", "f_before", "loss", "alpha", "mu", "nnz",
                "accepted_unit", "D")
_HISTORY_KEYS = ("f", "alpha", "mu", "nnz", "accepted_unit")

_PF_EPS = 1e-12          # pf below this counts as "unpenalized"
_SIGMA_EPS = 1e-7        # columns with weighted std below this are not scaled


# ---------------------------------------------------------------------------
# compiled-superstep cache (fixes the historical re-jit-per-fit cost)
# ---------------------------------------------------------------------------

_SUPERSTEP_CACHE: "collections.OrderedDict[tuple, object]" = \
    collections.OrderedDict()
_TRACE_COUNTS: "collections.Counter[tuple]" = collections.Counter()
_CACHE_CAP = 32


def _config_key(config: DGLMNETConfig) -> tuple:
    """The config fields the superstep trace actually reads — λ, outer-loop
    and host-side knobs (mu_init, alb, max_outer, tol) are excluded so fits
    differing only in those share one compiled superstep."""
    return (config.family, config.adaptive_mu, config.eta1, config.eta2,
            config.nu, config.sigma, config.backtrack_b, config.gamma,
            config.ls_delta, config.ls_grid_size, config.max_backtracks,
            config.tile_size, config.coupling, config.kernel_backend,
            config.compress_margin, config.fuse_superstep, config.precision)


def _cached_superstep(key: tuple, build):
    fn = _SUPERSTEP_CACHE.get(key)
    if fn is None:
        fn = build()
        _SUPERSTEP_CACHE[key] = fn
        while len(_SUPERSTEP_CACHE) > _CACHE_CAP:
            _SUPERSTEP_CACHE.popitem(last=False)
    else:
        _SUPERSTEP_CACHE.move_to_end(key)
    return fn


def clear_superstep_cache():
    """Drop all cached compiled supersteps (tests / memory pressure)."""
    _SUPERSTEP_CACHE.clear()


# ---------------------------------------------------------------------------
# λ_max utility
# ---------------------------------------------------------------------------

def lambda_max(X, y, family="logistic", *, sample_weight=None, offset=None,
               penalty_factor=None) -> float:
    """Smallest λ1 for which β = 0 solves the elastic-net GLM problem.

    KKT at β = 0: 0 ∈ ∂f(0) ⇔ |[Xᵀ s(0)]_j| ≤ λ1 pf_j for all penalized j,
    where s(0) is the (weighted) negative margin-gradient at zero margins
    plus offsets, so λ_max = max_j |g_j| / pf_j.  Host-side utility over raw
    inputs (dense array or SparseCOO); sessions use the placed design via
    ``GLMSolver.lambda_max``.
    """
    fam = glm.resolve_family(family)
    y = np.asarray(y, np.float32)
    n = y.shape[0]
    w = None if sample_weight is None else \
        jnp.asarray(np.asarray(sample_weight, np.float32))
    o = None if offset is None else \
        jnp.asarray(np.asarray(offset, np.float32))
    _, s0, _ = fam.stats(jnp.asarray(y), jnp.zeros((n,), jnp.float32),
                         weights=w, offset=o)
    s0 = np.asarray(s0)
    if isinstance(X, SparseCOO):
        g = X.rmatvec(s0)
    else:
        g = np.asarray(X, np.float32).T @ s0
    g = np.abs(g)
    if penalty_factor is not None:
        pf = np.asarray(penalty_factor, np.float32)
        pen = pf > _PF_EPS
        if not pen.any():
            raise ValueError("lambda_max undefined: no penalized features")
        g = g[pen] / pf[pen]
    return float(g.max())


# ---------------------------------------------------------------------------
# result containers
# ---------------------------------------------------------------------------

class PathResult(NamedTuple):
    lambdas: np.ndarray     # (K,) λ1 grid in fit order (decreasing)
    lam2: float             # shared ridge weight
    betas: np.ndarray       # (K, p) solutions in original feature order/scale
    f: np.ndarray           # (K,) final objective per λ
    nnz: np.ndarray         # (K,) int — support size per λ
    n_iters: np.ndarray     # (K,) supersteps spent per λ
    converged: np.ndarray   # (K,) bool
    intercepts: Optional[np.ndarray] = None   # (K,) when fit_intercept

    def beta_at(self, lam1: float) -> np.ndarray:
        """Solution at the grid point closest to ``lam1``."""
        return self.betas[int(np.abs(self.lambdas - lam1).argmin())]


class CVResult(NamedTuple):
    lambdas: np.ndarray       # (K,) shared λ1 grid (decreasing)
    lam2: float
    dev_folds: np.ndarray     # (n_folds, K) mean validation deviance
    dev_mean: np.ndarray      # (K,) across folds
    dev_se: np.ndarray        # (K,) standard error across folds
    best_index: int           # argmin of dev_mean
    lam_best: float           # lambdas[best_index]
    path: PathResult          # full-data path over the same grid (the refit)
    beta: np.ndarray          # full-data solution at lam_best
    intercept: float


# ---------------------------------------------------------------------------
# the session
# ---------------------------------------------------------------------------

def _with_intercept_column(X, n: int):
    """Append an all-ones column (the unpenalized intercept) to a raw host
    input; pre-built designs cannot be augmented after packing (a
    StreamingDesign can — its chunks are produced on demand)."""
    if isinstance(X, StreamingDesign):
        return X.with_ones_column()
    if isinstance(X, SparseCOO):
        p = X.shape[1]
        rows = np.concatenate([X.rows,
                               np.arange(n, dtype=np.asarray(X.rows).dtype)])
        cols = np.concatenate([X.cols, np.full((n,), p,
                                               np.asarray(X.cols).dtype)])
        vals = np.concatenate([np.asarray(X.vals, np.float32),
                               np.ones((n,), np.float32)])
        return SparseCOO(rows, cols, vals, (n, p + 1))
    if isinstance(X, SparseRows):
        # one more pair a row, on the device: feature p with value 1
        n_rows = X.ids.shape[0]
        ids = jnp.concatenate([jnp.asarray(X.ids, jnp.int32), jnp.full(
            (n_rows, 1), X.n_features, jnp.int32)], axis=1)
        vals = jnp.concatenate([jnp.asarray(X.vals, jnp.float32),
                                jnp.ones((n_rows, 1), jnp.float32)], axis=1)
        return SparseRows(ids, vals, X.n_features + 1)
    if isinstance(X, DesignMatrix):
        raise ValueError(
            "fit_intercept=True needs a raw input (dense array, SparseCOO "
            "or SparseRows): the intercept column must be appended before the "
            "design is packed; pre-built designs should carry their own "
            "constant column")
    X = np.asarray(X, np.float32)
    return np.concatenate([X, np.ones((X.shape[0], 1), np.float32)], axis=1)


class GLMSolver:
    """Reusable solver session over one placed (X, y).

    Construction does the expensive, λ-independent work exactly once:
    design packing (dense padding or CSR-of-bricks), optional intercept
    column, weighted standardization, device placement over the optional
    (data × model) mesh, and superstep compilation (shared via the
    module-level cache).  ``fit`` / ``fit_path`` / ``fit_cv`` then only run
    the outer loop; ``predict`` / ``score`` evaluate the last (or a given)
    solution.

    Observation-model kwargs (all optional, DESIGN.md §5):
      * ``sample_weight`` (n,): per-example nonnegative weights — the loss
        becomes Σ w_i l_i.  An integer weight k is exactly equivalent to
        replicating the row k times.
      * ``offset`` (n,): fixed per-example margin offsets — the loss is
        evaluated at Xβ (+ intercept) + offset.  ``predict``/``score`` take
        their own offset for new rows.
      * ``fit_intercept``: append an unpenalized all-ones column; the fitted
        intercept is split off into ``intercept_`` and never penalized.
        None (the default) takes ``config.fit_intercept``.
      * ``standardize``: fit on weighted-variance-1 columns (dense layouts
        with an intercept are also mean-centered; brick layouts are
        scale-only, glmnet-style for sparse inputs) and return β on the
        ORIGINAL scale.
      * ``penalty_factor`` (p,): per-feature multipliers on (λ1, λ2);
        0 = unpenalized, the λ grid rescales as λ_max = max |g_j|/pf_j.

    Args mirror the historical ``fit_sharded`` driver: ``mesh=None`` is the
    single-device reference path; with a mesh, rows shard over ``axis_data``
    and features over ``axis_model``; ``speeds``/``seed`` drive ALB
    straggler simulation; ``row_block``/``reorder`` the sparse brick
    packing; ``design_info`` accompanies a pre-built design.

    Passing a ``StreamingDesign`` (DESIGN.md §6) switches the session to
    the OUT-OF-CORE mode: rows stay on host (or are produced by a pure
    chunk callable), each superstep is two double-buffered passes over
    fixed-size row chunks (chunked Gram/gradient statistics, then every
    line-search candidate in one sweep), and checkpoints gain a chunk
    cursor (``fit(..., ckpt_every_chunks=k)``).  The whole observation
    model, λ-paths with screening, and mask-based ``fit_cv`` work
    unchanged on top; ``mesh`` must be None.
    """

    def __init__(self, X, y, *, family=None,
                 config: Optional[DGLMNETConfig] = None, mesh=None,
                 axis_data: Optional[str] = "data", axis_model: str = "model",
                 speeds=None, seed: int = 0,
                 row_block: int = 256, reorder: bool = True,
                 design_info=None,
                 sample_weight=None, offset=None,
                 standardize: bool = False,
                 fit_intercept: Optional[bool] = None,
                 penalty_factor=None,
                 telemetry=None, fault_plan=None):
        compile_cache.init()
        config = DGLMNETConfig() if config is None else config
        if family is not None:
            fam = glm.resolve_family(family)
            if glm.FAMILIES.get(fam.name) is not fam:
                raise ValueError(
                    f"family {fam.name!r} is not registered; call "
                    "glm.register_family(family) so it resolves by name "
                    "inside the compiled superstep")
            if fam.name != config.family:
                config = dataclasses.replace(config, family=fam.name)
        self.config = config
        self.mesh = mesh
        self.axis_data = axis_data if mesh is not None else None
        self.axis_model = axis_model if mesh is not None else None
        self._rng = np.random.default_rng(seed)
        # multi-host bookkeeping (DESIGN.md §9): which processes own which
        # model columns, and which columns THIS process holds addressable
        # shards of.  Single-process meshes get the degenerate map.
        self._multiproc = mesh is not None and \
            dist_boot.is_multiprocess_mesh(mesh)
        if mesh is not None:
            ctx = dist_boot.context()
            self.dist_info = {
                "multiprocess": self._multiproc,
                "process_id": ctx.process_id,
                "num_processes": ctx.num_processes,
                "column_owner": dist_boot.column_process_map(
                    mesh, axis_model).tolist(),
                "local_columns": dist_boot.local_columns(mesh, axis_model),
            }
        else:
            self.dist_info = None
        self._telemetry = telemetry
        self._faults = fault_plan
        self._phase_fractions = None   # set_phase_fractions
        self._superstep_no = 0
        self._budgets_host: Optional[np.ndarray] = None
        if telemetry is not None and mesh is None:
            raise ValueError(
                "telemetry-driven ALB needs a mesh: node speeds map onto "
                "model columns (repro.dist.telemetry)")
        if fault_plan is not None and self.dist_info is not None and \
                fault_plan.num_processes != self.dist_info["num_processes"]:
            raise ValueError(
                f"fault plan covers {fault_plan.num_processes} processes "
                f"but the job has {self.dist_info['num_processes']}")
        self.beta_: Optional[np.ndarray] = None
        self.intercept_: float = 0.0
        self.fit_intercept = bool(config.fit_intercept if fit_intercept
                                  is None else fit_intercept)
        self.standardize = bool(standardize)
        self._state: Optional[FitState] = None
        self._lmax: Optional[float] = None
        self._grad0: Optional[np.ndarray] = None   # Xᵀs at β = 0
        self._matvec_fn = None
        self._grad_fn = None
        self._dev_fn = None
        self._streaming = False
        self._serve_cache = None        # (key, ScoringEngine) for predict
        self._ws_cache = None           # (key, design, entries) _round_design
        # host-side sweep launch bookkeeping (active-set-shaped launches,
        # DESIGN.md §8): tiles the CD sweep actually processed vs skipped
        # because every coordinate was screened out, and the Gram blocks
        # the fused kernel mirrored rather than contracted in the live
        # ones.  In-memory fits only.
        # The λ-path loop counts the λ points it finished and the ``_run``
        # calls (KKT rounds) it made for them.
        self.launch_stats = {"supersteps": 0, "sweep_tile_launches": 0,
                             "sweep_tiles_skipped": 0, "lambdas": 0,
                             "kkt_rounds": 0, "tail_entries": 0,
                             "gram_blocks_mirrored": 0}
        # convergence event stream (repro.obs, DESIGN.md §12): auto-opened
        # next to the trace shards when tracing targets a directory, or
        # attached explicitly via set_convergence_stream().
        self._conv = None
        self._conv_step = 0
        self._conv_ctx: dict = {}
        self._last_step_us = None
        self._last_phase_us = None
        td = obs_trace.trace_dir()
        if td is not None:
            self._conv = conv_lib.ConvergenceStream(
                td / f"convergence_{obs_trace.get_tracer().pid}.jsonl")

        # file / reader front door (repro.io): a path or an open reader
        # coerces to a StreamingDesign, and y=None pulls the labels from
        # the same source — GLMSolver("train.libsvm.gz", None) trains
        # out-of-core.  Lazy import: repro.io is optional machinery above
        # the solver, not a core dependency.
        self._reader = None
        if isinstance(X, (str, os.PathLike)) or (
                not isinstance(X, (np.ndarray, jnp.ndarray))
                and not hasattr(X, "shape")
                and hasattr(X, "to_design") and hasattr(X, "labels")):
            from repro import io as io_lib
            if mesh is not None:
                raise ValueError(
                    "file-backed fits stream through a single-process "
                    "StreamingDesign (mesh=None); for multi-process "
                    "out-of-core training use launch/dist_run.py, which "
                    "gives each process its own chunk range")
            X, labels, self._reader = io_lib.open_design(
                X, tile_size=config.tile_size)
            if y is None:
                y = labels

        y = np.asarray(y, np.float32)
        n = y.shape[0]
        self._n_user = n
        T = config.tile_size

        sw = np.ones((n,), np.float32) if sample_weight is None else \
            np.asarray(sample_weight, np.float32)
        off = np.zeros((n,), np.float32) if offset is None else \
            np.asarray(offset, np.float32)
        if sw.shape != (n,) or off.shape != (n,):
            raise ValueError(
                f"sample_weight/offset must be ({n},); got {sw.shape} / "
                f"{off.shape}")
        if (sw < 0).any():
            raise ValueError("sample_weight must be nonnegative")

        if self.fit_intercept:
            X = _with_intercept_column(X, n)

        if mesh is None:
            if isinstance(X, SparseRows) and not (
                    config.fuse_superstep and config.coupling == "jacobi"):
                raise ValueError(
                    "SparseRows train through the fused Jacobi superstep "
                    "(fuse_superstep=True, coupling='jacobi'): the sparse "
                    "tail has no per-tile Gram")
            with obs_trace.span("solver/pack_head_tail") \
                    if isinstance(X, SparseRows) else contextlib.nullcontext():
                design, info = design_lib.as_design(
                    X, T, row_block=row_block, reorder=reorder,
                    info=design_info, head_features=config.head_features)
            self._info = info
            self._streaming = isinstance(design, StreamingDesign)
            if self._streaming and design.tile_size != T:
                raise ValueError(
                    f"StreamingDesign was built with tile_size="
                    f"{design.tile_size} but the config says {T}; the "
                    "column padding is a function of the tile size, so "
                    "build the design with the session's tile_size")
            n_rows, p_pad = design.shape
            self._n_tot, self._p_tot = n_rows, p_pad
            self._n_tiles_local = design.n_tiles
            self._max_budget = design.n_tiles
            self._D = self._M = 1
            self._Xs = design
            y_pad = np.pad(y, (0, n_rows - n), constant_values=1.0)
            # streaming fits keep the (n,) row vectors on HOST — the driver
            # slices them per chunk (DESIGN.md §6)
            self._ys = y_pad if self._streaming else jnp.asarray(y_pad)
            self._budget_const = jnp.full((1,), design.n_tiles, jnp.int32)
            self._base_speeds = None
            if self._streaming:
                self._design_layout = {
                    "kind": "streaming", "tile": T,
                    "chunk_rows": design.chunk_rows}
                layout_key = ("streaming", T, design.chunk_rows,
                              design.n_chunks, p_pad)
            elif isinstance(design, BlockSparseDesign):
                self._design_layout = {
                    "kind": "bricks", "D": 1, "M": 1, "tile": T,
                    "row_block": design.row_block, "reorder": bool(reorder)}
                layout_key = ("bricks", T, design.row_block, design.n_rows,
                              design.n_tiles, design.max_bricks_per_tile)
            elif isinstance(design, HeadTailDesign):
                self._design_layout = {
                    "kind": "head_tail", "tile": T,
                    "head": design.head_width}
                layout_key = ("head_tail", T, design.head_width, n_rows,
                              p_pad, design.tail_width)
            else:
                self._design_layout = None
                layout_key = ("dense",)
            self._x_specs = self._row_spec = self._feat_spec = None
            self._state_specs = None
        else:
            if isinstance(X, StreamingDesign):
                raise ValueError(
                    "StreamingDesign is a single-process out-of-core layout; "
                    "it cannot be mesh-sharded (mesh=None). Shard rows by "
                    "giving each process its own chunk range instead")
            if isinstance(X, SparseRows):
                raise ValueError(
                    "SparseRows train on one device (mesh=None): the "
                    "head/tail layout is not sharded over a mesh yet")
            D = mesh.shape[axis_data] if axis_data else 1
            M = mesh.shape[axis_model]
            self._D, self._M = D, M
            self._row_spec = P(axis_data)
            self._feat_spec = P(axis_model)

            if isinstance(X, (SparseCOO, BlockSparseDesign)):
                if isinstance(X, SparseCOO):
                    design_g, info = design_lib.build_block_sparse_sharded(
                        X, D=D, M=M, tile_size=T, row_block=row_block,
                        reorder=reorder)
                else:
                    if X.leading != 2 or X.tile_size != T:
                        raise ValueError(
                            "pre-built BlockSparseDesign must carry (D, M) "
                            "leading axes and match tile_size")
                    if design_info is None:
                        raise ValueError(
                            "pre-built BlockSparseDesign requires the "
                            "DesignInfo returned by "
                            "build_block_sparse_sharded (pass "
                            "design_info=...); the brick layout reorders "
                            "columns and beta must be unpacked with it")
                    design_g, info = X, design_info
                n_loc, p_loc = design_g.shape          # per-shard (static)
                n_tot, p_tot = D * n_loc, M * p_loc
                self._x_specs = design_g.partition_specs(axis_data,
                                                         axis_model)
                self._Xs = dist_boot.put_global(design_g, mesh,
                                                self._x_specs)
                # brick column packing + row padding are functions of
                # (D, M, T, rb): checkpoints record this layout so a resume
                # onto a different mesh fails loudly instead of continuing
                # from a permuted iterate
                self._design_layout = {
                    "kind": "bricks", "D": D, "M": M, "tile": T,
                    "row_block": design_g.row_block, "reorder": bool(reorder)}
                layout_key = ("bricks", T, design_g.row_block,
                              design_g.n_rows, design_g.n_tiles,
                              design_g.max_bricks_per_tile)
            else:
                X = np.asarray(X, np.float32)
                _, p = X.shape
                info = design_lib.DesignInfo(shape=(n, p))
                # pad rows to D, features to M*T multiples
                Xp = np.pad(X, ((0, (-n) % D), (0, (-p) % (M * T))))
                n_tot, p_tot = Xp.shape
                p_loc = p_tot // M
                self._x_specs = P(axis_data, axis_model)
                self._Xs = dist_boot.put_global(Xp, mesh, self._x_specs)
                self._design_layout = None  # dense layout is mesh-invariant
                layout_key = ("dense",)
            self._info = info
            self._n_tot, self._p_tot = n_tot, p_tot
            self._n_tiles_local = p_loc // T

            yp = np.pad(y, (0, n_tot - n), constant_values=1.0)
            self._ys = dist_boot.put_global(yp, mesh, self._row_spec)

            # ALB budgets: fraction-κ completion rule (paper Section 7).
            # Three sources, in precedence order: runtime telemetry
            # (measured node speeds, DESIGN.md §9), the harness-supplied
            # speed simulation (config.alb + speeds=), or the constant
            # full-budget BSP vector.
            from repro.core import alb as alb_lib
            if telemetry is not None:
                self._base_speeds = None
                self._max_budget = int(alb_lib.max_budget(
                    self._n_tiles_local))
            elif config.alb:
                self._base_speeds = (np.asarray(speeds, np.float32)
                                     if speeds is not None
                                     else np.ones((M,), np.float32))
                self._max_budget = int(alb_lib.max_budget(
                    self._n_tiles_local))
            else:
                self._base_speeds = None
                self._max_budget = self._n_tiles_local
                self._budget_const = dist_boot.put_global(
                    np.full((M,), self._n_tiles_local, np.int32),
                    mesh, self._feat_spec)

            self._state_specs = FitState(beta=self._feat_spec,
                                         xb=self._row_spec, mu=P(),
                                         cursor=self._feat_spec, step=P())

        # --- observation model: weights, offsets, penalty factors ----------
        # packed columns from here on are screened by their own KKT
        # condition, not the strong rule (a head/tail design's tail)
        self._tail_start = self._Xs.head_width \
            if isinstance(self._Xs, HeadTailDesign) else self._p_tot
        self._p_model = self._info.shape[1]       # columns incl. intercept
        self._p_user = self._p_model - (1 if self.fit_intercept else 0)
        self._wobs_host = np.pad(sw, (0, self._n_tot - n))   # padding → 0
        self._wobs = self._place_row(self._wobs_host)
        self._offsets = self._place_row(np.pad(off, (0, self._n_tot - n)))

        pf = np.ones((self._p_user,), np.float32) if penalty_factor is None \
            else np.asarray(penalty_factor, np.float32)
        if pf.shape != (self._p_user,):
            raise ValueError(
                f"penalty_factor must be ({self._p_user},); got {pf.shape}")
        if (pf < 0).any():
            raise ValueError("penalty_factor must be nonnegative")
        if self.fit_intercept:
            pf = np.concatenate([pf, np.zeros((1,), np.float32)])
        # padding columns keep pf = 1 so they stay pinned at zero
        self._penf_host = self._info.pack_cols(pf, self._p_tot, fill=1.0)
        self._penf = self._place_feat(self._penf_host)

        self._active_ones = self._place_feat(
            np.ones((self._p_tot,), np.float32))
        mesh_key = None if mesh is None else \
            (tuple(mesh.devices.flat), tuple(mesh.axis_names),
             self.axis_data, self.axis_model)
        self._key = (_config_key(config), self._n_tiles_local,
                     self._max_budget, layout_key, mesh_key)
        self._superstep = _cached_superstep(self._key, self._build_superstep)

        # --- standardization (after placement: moments via the operator) ---
        self._scale_packed: Optional[np.ndarray] = None
        self._center_packed: Optional[np.ndarray] = None
        if self.standardize:
            self._apply_standardization()

    # -------------------------------------------------------------- infra

    @property
    def compile_count(self) -> int:
        """Trace count of this session's compiled superstep (one per
        compilation; shared with other sessions on the same cache key —
        tests assert the DELTA across a whole λ-path / CV run is ≤ 1)."""
        return _TRACE_COUNTS[self._key]

    @property
    def info(self):
        return self._info

    def device_bytes(self) -> dict:
        """``{device id: bytes}`` of the placed design and row vectors —
        where the session's data actually lives (a mesh spreads it; a
        single-device session holds it all on one device)."""
        out: "collections.Counter[int]" = collections.Counter()
        for leaf in jax.tree.leaves((self._Xs, self._ys, self._wobs,
                                     self._offsets)):
            if isinstance(leaf, jax.Array):
                for shard in leaf.addressable_shards:
                    out[shard.device.id] += shard.data.nbytes
        return dict(out)

    def lower_superstep(self):
        """``jax.stages.Lowered`` of this session's compiled superstep at
        its placed arguments (zero iterate, full budgets, every coordinate
        active) — ``.compile()`` then gives the program a fit runs, e.g. to
        count its kernel launches or read its memory analysis."""
        if self._streaming:
            raise ValueError("streaming sessions run several programs per "
                             "superstep; there is no single one to lower")
        budgets = jnp.full((1,), self._n_tiles_local, jnp.int32) \
            if self.mesh is None else dist_boot.put_global(
                np.full((self._M,), self._n_tiles_local, np.int32),
                self.mesh, self._feat_spec)
        return self._superstep.lower(
            self._Xs, self._ys, self._wobs, self._offsets, budgets,
            jnp.zeros((2,), jnp.float32), self._active_ones, self._penf,
            self._init_state(None))

    def _place_feat(self, arr):
        if self.mesh is None:
            return jnp.asarray(arr)
        return dist_boot.put_global(np.asarray(arr), self.mesh,
                                    self._feat_spec)

    def _place_row(self, arr):
        if self._streaming:
            # row vectors stay host-side; the streaming driver slices them
            # per chunk and ships each slice with its design chunk
            return np.asarray(arr, np.float32)
        if self.mesh is None:
            return jnp.asarray(arr)
        return dist_boot.put_global(np.asarray(arr), self.mesh,
                                    self._row_spec)

    def _place_scalar(self, value, dtype):
        """A replicated scalar of the fit state (μ, step).  On a mesh it is
        placed replicated over the mesh, exactly as the compiled superstep
        returns it, so the first superstep of every fit sees the same input
        shardings as the later ones and the superstep compiles once."""
        arr = np.asarray(value, dtype)
        if self.mesh is None:
            return jnp.asarray(arr)
        return dist_boot.put_global(arr, self.mesh, P())

    def _host(self, arr) -> np.ndarray:
        """Host numpy copy of a device array — the collective all-gather
        readback when the mesh spans processes (every process calls it)."""
        if self._multiproc:
            return dist_boot.gather_to_host(arr)
        return np.asarray(arr)

    def _build_superstep(self):
        key = self._key
        if self._streaming:
            return dglmnet.make_streaming_superstep(
                self.config,
                on_trace=lambda k=key: _TRACE_COUNTS.update([k]))
        raw = dglmnet.make_superstep(
            self.config, axis_data=self.axis_data, axis_model=self.axis_model,
            n_tiles_local=self._n_tiles_local, max_budget=self._max_budget)

        def counted(X, y, weights, offset, budget, lams, active, penf,
                    state):
            _TRACE_COUNTS[key] += 1       # runs at trace time only
            return raw(X, y, weights, offset, budget, lams, active, penf,
                       state)

        if self.mesh is None:
            return jax.jit(counted)
        return jax.jit(compat.shard_map(
            counted, mesh=self.mesh,
            in_specs=(self._x_specs, self._row_spec, self._row_spec,
                      self._row_spec, self._feat_spec, P(), self._feat_spec,
                      self._feat_spec, self._state_specs),
            out_specs=(self._state_specs, {k: P() for k in _METRIC_KEYS}),
            check_vma=False,
        ))

    def _matvec(self, beta_dev):
        """Xβ over the placed design (warm starts from a host β)."""
        if self._matvec_fn is None:
            T = self.config.tile_size
            ax_m = self.axis_model

            def mv(X, v):
                design = design_lib.as_local_design(X, T)
                xb = design.matvec(v)
                return jax.lax.psum(xb, ax_m) if ax_m is not None else xb

            if self.mesh is None:
                self._matvec_fn = jax.jit(mv)
            else:
                self._matvec_fn = jax.jit(compat.shard_map(
                    mv, mesh=self.mesh,
                    in_specs=(self._x_specs, self._feat_spec),
                    out_specs=self._row_spec, check_vma=False))
        return self._matvec_fn(self._Xs, beta_dev)

    def _grad(self, xb_dev, weights=None):
        """g = Xᵀ s(β) in packed column order (λ_max / screening / KKT).

        ``s`` is the (weighted, offset) negative margin-gradient at the
        margins ``xb_dev``, so the KKT condition for a zero coordinate is
        |g_j| ≤ λ1 pf_j.  ``weights`` defaults to the session weights; CV
        fold fits pass their fold-masked vector.
        """
        if self._grad_fn is None:
            T = self.config.tile_size
            fam = self.config.family
            backend = self.config.kernel_backend
            ax_d = self.axis_data

            def grad(X, y, weights, offset, xb):
                design = design_lib.as_local_design(X, T)
                _, s, _ = ops.glm_stats(y, xb, fam, weights=weights,
                                        offset=offset, backend=backend)
                g = design.rmatvec(s)
                return jax.lax.psum(g, ax_d) if ax_d is not None else g

            if self.mesh is None:
                self._grad_fn = jax.jit(grad)
            else:
                self._grad_fn = jax.jit(compat.shard_map(
                    grad, mesh=self.mesh,
                    in_specs=(self._x_specs, self._row_spec, self._row_spec,
                              self._row_spec, self._row_spec),
                    out_specs=self._feat_spec, check_vma=False))
        weights = self._wobs if weights is None else weights
        return self._host(self._grad_fn(self._Xs, self._ys, weights,
                                        self._offsets, xb_dev))

    def _grad_state(self, state: FitState, weights=None):
        """g = Xᵀ s(β) at a fit state — in-memory reads the maintained
        margins; streaming re-materializes them chunk by chunk."""
        if not self._streaming:
            return self._grad(state.xb, weights)
        if self._grad_fn is None:
            fam = self.config.family
            backend = self.config.kernel_backend

            @functools.partial(jax.jit, donate_argnums=(5,))
            def grad_chunk(Xc, yc, wc, oc, beta, g):
                _, s, _ = ops.glm_stats(yc, Xc @ beta, fam, weights=wc,
                                        offset=oc, backend=backend)
                return g + Xc.T @ s

            self._grad_fn = grad_chunk
        g = jnp.zeros((self._p_tot,), jnp.float32)
        for _, Xc, yc, wc, oc in self._iter_row_chunks(weights):
            g = self._grad_fn(Xc, yc, wc, oc, state.beta, g)
        return np.asarray(g)

    # ------------------------------------------------------ standardization

    def _col_moments(self):
        """(Σ w x_j, Σ w x_j²) over the placed design, packed order, host."""
        if self.mesh is None:
            s1, s2 = self._Xs.col_moments(self._wobs)
            return np.asarray(s1), np.asarray(s2)
        T = self.config.tile_size
        ax_d = self.axis_data

        def cm(X, w):
            design = design_lib.as_local_design(X, T)
            s1, s2 = design.col_moments(w)
            if ax_d is not None:
                s1, s2 = jax.lax.psum((s1, s2), ax_d)
            return s1, s2

        fn = jax.jit(compat.shard_map(
            cm, mesh=self.mesh,
            in_specs=(self._x_specs, self._row_spec),
            out_specs=(self._feat_spec, self._feat_spec), check_vma=False))
        s1, s2 = fn(self._Xs, self._wobs)
        return self._host(s1), self._host(s2)

    def _apply_standardization(self):
        """Rescale (and for dense layouts with an intercept: center) the
        placed design to weighted variance 1 per column; record the packed
        (scale, center) so fitted coefficients map back to the original
        scale (DESIGN.md §5)."""
        s1, s2 = self._col_moments()
        wsum = float(self._wobs_host.sum())
        if wsum <= 0:
            raise ValueError("standardize=True needs positive total weight")
        mu = s1 / wsum
        var = np.maximum(s2 / wsum - mu * mu, 0.0)
        sigma = np.sqrt(var)
        scale = np.where(sigma > _SIGMA_EPS, 1.0 / np.maximum(sigma, 1e-30),
                         1.0).astype(np.float32)
        # dense and streaming layouts can center (chunks are dense on
        # device); brick layouts are scale-only (DESIGN.md §5)
        dense = self._design_layout is None or self._streaming
        center = mu.astype(np.float32) if (dense and self.fit_intercept) \
            else np.zeros_like(scale)
        if self.fit_intercept:
            # the intercept column must stay the exact ones column
            icol = self._p_user if self._info.col_of_feature is None \
                else int(self._info.col_of_feature[self._p_user])
            scale[icol] = 1.0
            center[icol] = 0.0

        if self.mesh is None:
            self._Xs = self._Xs.scale_columns(
                jnp.asarray(scale),
                jnp.asarray(center) if dense and self.fit_intercept
                else None)
        elif dense:
            # jit with explicit out_shardings so the rescaled design lands
            # back on its (data, model) placement — works unchanged when
            # the mesh spans processes (device_put onto a non-addressable
            # sharding would not)
            fn = jax.jit(lambda X, c, s: (X - c[None, :]) * s[None, :],
                         out_shardings=NamedSharding(self.mesh,
                                                     self._x_specs))
            self._Xs = fn(self._Xs, center, scale)
        else:
            M = self._M
            out_sh = jax.tree.map(
                lambda s: NamedSharding(self.mesh, s), self._x_specs)
            fn = jax.jit(lambda X, s: X.scale_columns(s),
                         out_shardings=out_sh)
            self._Xs = fn(self._Xs, scale.reshape(M, self._p_tot // M))
        self._scale_packed = scale
        self._center_packed = center

    # --------------------------------------------- β packing / unpacking

    def _unpack_user(self, beta_packed: np.ndarray):
        """Packed (standardized-scale) β → (original-scale β (p_user,),
        intercept).  Inverse of ``_pack_user``."""
        b = np.asarray(beta_packed, np.float32)
        corr = 0.0
        if self._scale_packed is not None:
            b = b * self._scale_packed
            corr = float(np.dot(self._center_packed, b))
        unpacked = self._info.unpack_beta(b)
        if self.fit_intercept:
            return unpacked[:self._p_user], float(unpacked[-1]) - corr
        return unpacked, 0.0

    def _pack_user(self, beta_user, intercept: float = 0.0) -> np.ndarray:
        beta_user = np.asarray(beta_user, np.float32)
        if beta_user.shape != (self._p_user,):
            raise ValueError(
                f"beta0 must be ({self._p_user},); got {beta_user.shape}")
        full = np.concatenate([beta_user, np.zeros((1,), np.float32)]) \
            if self.fit_intercept else beta_user
        packed = self._info.pack_beta(full, self._p_tot)
        if self._scale_packed is not None:
            corr = float(np.dot(self._center_packed, packed))
            packed = packed / self._scale_packed
        else:
            corr = 0.0
        if self.fit_intercept:
            icol = self._p_user if self._info.col_of_feature is None \
                else int(self._info.col_of_feature[self._p_user])
            packed[icol] = float(intercept) + corr
        return packed

    # ---------------------------------------------------------- state setup

    def _init_state(self, beta0=None, intercept0: float = 0.0) -> FitState:
        cfg = self.config
        if beta0 is not None:
            packed = self._pack_user(np.asarray(beta0, np.float32),
                                     intercept0)
            beta = self._place_feat(packed)
            xb = self._stream_xb() if self._streaming \
                else self._matvec(beta)
        elif self._streaming:
            beta = self._place_feat(np.zeros((self._p_tot,), np.float32))
            xb = self._stream_xb()
        else:
            beta = self._place_feat(np.zeros((self._p_tot,), np.float32))
            xb = self._place_row(np.zeros((self._n_tot,), np.float32))
        cursor = jnp.zeros((1,), jnp.int32) if self.mesh is None else \
            dist_boot.put_global(np.zeros((self._M,), np.int32),
                                 self.mesh, self._feat_spec)
        return FitState(beta=beta, xb=xb,
                        mu=self._place_scalar(cfg.mu_init, np.float32),
                        cursor=cursor, step=self._place_scalar(0, np.int32))

    def _budgets(self):
        from repro.core import alb as alb_lib
        if self._telemetry is not None:
            sp = self._telemetry.column_speeds(self.mesh, self.axis_model)
            if sp is None:        # warm-up: uniform full budgets (BSP)
                budgets = np.full((self._M,), self._n_tiles_local, np.int32)
            else:
                # measured speeds: sanitize, completion-rule pivot (the
                # quantile-lower pivot never down-budgets the slow node at
                # small M — see alb._pivot)
                budgets = alb_lib.alb_budgets(
                    sp, self._n_tiles_local, self.config.alb_kappa,
                    self._max_budget, sanitize=True,
                    pivot_rule="completion")
            self._budgets_host = np.asarray(budgets, np.int32)
            return dist_boot.put_global(self._budgets_host, self.mesh,
                                        self._feat_spec)
        if self._base_speeds is None:
            if self._budgets_host is None:
                self._budgets_host = np.full(
                    (self._M if self.mesh is not None else 1,),
                    self._n_tiles_local, np.int32)
            return self._budget_const
        budgets = alb_lib.alb_budgets(
            alb_lib.sample_speeds(self._rng, self._base_speeds),
            self._n_tiles_local, self.config.alb_kappa, self._max_budget)
        self._budgets_host = budgets.astype(np.int32)
        return dist_boot.put_global(self._budgets_host, self.mesh,
                                    self._feat_spec)

    # ---------------------------------------------------------- outer loop

    def _my_tiles(self) -> int:
        """Tiles THIS process's columns are budgeted for in the last
        computed budget vector (the fault/telemetry unit of work)."""
        if self._budgets_host is None:
            return self._n_tiles_local
        if self.dist_info is None or not self.dist_info["local_columns"]:
            return int(self._budgets_host.max())
        return int(max(self._budgets_host[m]
                       for m in self.dist_info["local_columns"]))

    def _dispatch_superstep(self, X, weights_dev, lams, active_dev, state):
        """One superstep, from its dispatch to its metrics on the host,
        with the distributed hooks around it (DESIGN.md §9): per-superstep
        budgets, fault-plan work injection, and telemetry recording.
        Returns (state, host metrics).  Without telemetry/faults this is
        the bare compiled-superstep call and its one sync.  Either way one
        ``solver/superstep`` span covers the dispatch and the sync, with
        the sync's wait nested in it as ``solver/sync``."""
        budgets = self._budgets()
        if self._telemetry is None and self._faults is None:
            with obs_trace.span("solver/superstep") as sp:
                state, m = self._superstep(X, self._ys, weights_dev,
                                           self._offsets, budgets, lams,
                                           active_dev, self._penf, state)
                mh = self._fetch_metrics(m)
            self._last_step_us = sp.elapsed_us or None
            self._last_phase_us = None
            return state, mh
        step_no = self._superstep_no
        self._superstep_no += 1
        pid = 0 if self.dist_info is None else self.dist_info["process_id"]
        tiles = self._my_tiles()
        work = work_phases = None
        if self._faults is not None and self._faults.tile_cost_s > 0:
            # simulated per-tile local-work cost: the sleep is REAL
            # wall-clock (what straggler_bench measures); the same value is
            # what telemetry records as this node's local-phase seconds
            # (see the measurement-source note in repro.dist.telemetry)
            work = self._faults.work_s(pid, step_no, tiles)
            work_phases = self._faults.work_phases(pid, step_no, tiles)
            if work > 0:
                with obs_trace.span("solver/fault_sleep",
                                    args={"work_s": round(work, 6)}):
                    time.sleep(work)
        # telemetry must read a clock even with tracing disabled
        # lint: allow OBS001 — raw local-work seconds feed the speed EMA
        t0 = time.perf_counter()
        with obs_trace.span("solver/superstep",
                            args={"step": step_no, "tiles": tiles}):
            state, m = self._superstep(X, self._ys, weights_dev,
                                       self._offsets, budgets, lams,
                                       active_dev, self._penf, state)
            if self._telemetry is not None:
                jax.block_until_ready(state)
                measured = time.perf_counter() - t0
            mh = self._fetch_metrics(m)
        if self._telemetry is not None:
            # under a fault plan the injected work IS the node's local-phase
            # seconds; raw wall-clock around a globally-synchronized SPMD
            # program would fold in collective-wait time (every process
            # waits for the straggler) and erase the very signal ALB needs
            sec = measured if work is None else work
            if work_phases is not None:
                # the fault plan's phase attribution (sweep by default,
                # "network"/"io" wait excess for phase faults), with the
                # compute share redistributed over any probe-measured
                # fractions
                phases = self._compose_phases(work_phases)
            elif self._phase_fractions:
                phases = {k: sec * f
                          for k, f in self._phase_fractions.items()}
            else:
                phases = None
            self._telemetry.record(step_no, tiles, sec, phases=phases)
            self._last_step_us = sec * 1e6
            self._last_phase_us = None if phases is None else \
                {k: round(v * 1e6, 1) for k, v in phases.items()}
        else:
            self._last_step_us = None
            self._last_phase_us = None
        return state, mh

    @staticmethod
    def _fetch_metrics(m) -> dict:
        """ONE device→host sync per superstep: fetching the metrics dict
        whole lets every scalar ride a single transfer instead of blocking
        the dispatch pipe per key (lint rule SYNC001)."""
        with obs_trace.span("solver/sync"):
            return jax.device_get(m)

    def _compose_phases(self, work_phases: dict) -> dict:
        """Fault-plan phase attribution composed with the registered probe
        fractions: the COMPUTE share is redistributed over
        ``set_phase_fractions`` (the probe knows the stats/sweep/merge/
        line-search split better than the fault model's single-phase
        charge); wait-state shares ("network"/"io") pass through, since a
        probe of the compiled superstep can never observe them."""
        if not self._phase_fractions:
            return dict(work_phases)
        from repro.dist.telemetry import COMPUTE_PHASES
        compute = sum(v for k, v in work_phases.items()
                      if k in COMPUTE_PHASES)
        out = {k: v for k, v in work_phases.items()
               if k not in COMPUTE_PHASES}
        for k, f in self._phase_fractions.items():
            out[k] = out.get(k, 0.0) + compute * f
        return out

    def set_phase_fractions(self, fractions):
        """Attribute each superstep's telemetry seconds to named phases.

        The compiled superstep is one fused program, so its internal
        stats / CD-sweep / line-search split is not directly observable
        at runtime; callers that probed the split with separately-jitted
        ops at the same shapes (``benchmarks/path_bench``'s phase
        breakdown) register the measured fractions here, and every
        subsequent telemetry record carries ``phases = fraction ×
        seconds`` (``repro.dist.telemetry.phase_breakdown``).  Pass None
        to stop attributing."""
        if fractions is not None:
            fractions = {str(k): float(v) for k, v in fractions.items()}
        self._phase_fractions = fractions

    def set_convergence_stream(self, stream):
        """Attach (or detach, with None) a convergence event stream —
        sessions created while tracing targets a directory get one
        automatically (``<trace_dir>/convergence_<pid>.jsonl``).  Accepts
        a ``repro.obs.convergence.ConvergenceStream`` or a path."""
        if stream is not None and not hasattr(stream, "emit"):
            stream = conv_lib.ConvergenceStream(stream)
        self._conv = stream

    def _emit_conv(self, outer_it, mh, *, lam1, lam2, active_size,
                   step_us=None, phase_us=None):
        """One convergence event per outer iteration — host scalars only,
        all already fetched by the superstep's single device_get, so the
        stream adds no device syncs (SYNC001)."""
        self._conv_step += 1
        ctx = self._conv_ctx
        self._conv.emit(
            step=self._conv_step, outer_it=int(outer_it),
            lam_index=ctx.get("lam_index"),
            lam1=float(lam1), lam2=float(lam2),
            f=float(mh["f"]), loss=float(mh["loss"]),
            deviance=float(mh["D"]) if "D" in mh else None,
            alpha=float(mh["alpha"]), mu=float(mh["mu"]),
            nnz=int(mh["nnz"]),
            accepted_unit=float(mh["accepted_unit"]),
            active_size=int(active_size),
            screened=ctx.get("screened"),
            kkt_violations=ctx.get("kkt_violations"),
            supersteps=self.launch_stats["supersteps"],
            sweep_tile_launches=self.launch_stats["sweep_tile_launches"],
            sweep_tiles_skipped=self.launch_stats["sweep_tiles_skipped"],
            step_us=self._last_step_us if step_us is None else step_us,
            phase_us=self._last_phase_us if phase_us is None else phase_us)

    def _round_design(self, active):
        """(design, tail entries read per superstep) for a run of
        supersteps under the host mask ``active`` (None = every column):
        a head/tail design carries the working set of its active tail
        columns (reused while that set is unchanged), or reads its whole
        tail where the set does not fit; any other design is the placed
        one."""
        X = self._Xs
        if not isinstance(X, HeadTailDesign):
            return X, 0
        cols = np.arange(X.tail_cols) if active is None else \
            np.flatnonzero(np.asarray(active)[X.head_width:] > 0)
        key = cols.tobytes()
        if self._ws_cache is None or self._ws_cache[0] != key:
            X, count = X.with_working_set(self._info.tail_counts, cols)
            read = X.tail_ids.size if count > X.ws_capacity else count
            self._ws_cache = (key, X, 2 * int(read))
        return self._ws_cache[1:]

    def _run(self, state: FitState, lam1: float, lam2: float, *,
             kkt_round: Optional[int] = None, **kwargs):
        """Drive supersteps at fixed (λ1, λ2) until the objective plateaus.

        Returns (state, history, n_iter, converged).  Keyword arguments
        (``weights``, ``active``, ``max_outer``, ``tol``, ``verbose``,
        checkpointing) go to the in-memory or the streaming loop.  One
        ``solver/run`` span covers the call; ``kkt_round`` (the λ-path
        loop's KKT round at its λ, 0 first) is recorded on it.
        """
        loop = self._run_streaming if self._streaming else \
            self._run_in_memory
        with obs_trace.span("solver/run", args=None if kkt_round is None
                            else {"round": kkt_round}):
            return loop(state, lam1, lam2, **kwargs)

    def _run_in_memory(self, state: FitState, lam1: float, lam2: float, *,
                       weights=None, active=None, max_outer=None, tol=None,
                       verbose=False, ckpt_manager=None, ckpt_every: int = 10,
                       ckpt_every_chunks: Optional[int] = None):
        """The in-memory outer loop of ``_run``.  ``active`` is a host
        (p_tot,) 0/1 mask in packed column order (None = all coordinates);
        ``weights`` a placed (n_tot,) row-weight vector (None = the session
        weights — CV fold fits pass fold-masked vectors)."""
        cfg = self.config
        max_outer = cfg.max_outer if max_outer is None else int(max_outer)
        tol = cfg.tol if tol is None else float(tol)
        lams = jnp.asarray([lam1, lam2], jnp.float32)
        weights_dev = self._wobs if weights is None else weights
        active_dev = self._active_ones if active is None else \
            self._place_feat(np.asarray(active, np.float32))

        # sweep-launch bookkeeping: the active mask is host-known, so the
        # tiles the shaped sweep will skip are too (the compiled superstep
        # itself is branch-predicated — it never retraces with the mask).
        # A head/tail design launches tiles over its head; its tail reads
        # the working set of the active tail columns every superstep
        X, tail_entries = self._round_design(active)
        swept_cols = X.head_width if isinstance(X, HeadTailDesign) \
            else self._p_tot
        total_tiles = swept_cols // cfg.tile_size
        if active is None:
            live_tiles = total_tiles
            live_active = self._p_tot
        else:
            act = np.asarray(active, np.float32)
            live_tiles = int((act[:swept_cols].reshape(
                total_tiles, cfg.tile_size).max(axis=1) > 0).sum())
            live_active = int((act > 0).sum())
        fused = cfg.fuse_superstep and cfg.coupling == "jacobi" and \
            self.axis_data is None and self.axis_model is None
        shaped = active is not None and self.axis_data is None and (
            cfg.coupling == "gauss-seidel" or fused)
        mirrored = ops.fused_gram_blocks_mirrored(
            X.head if isinstance(X, HeadTailDesign) else X, cfg.family,
            cfg.kernel_backend) if fused else 0

        history = {k: [] for k in _HISTORY_KEYS}
        f_prev, converged, it = np.inf, False, 0
        start_it = 1
        if ckpt_manager is not None and ckpt_manager.latest_step() is not None:
            # elastic resume: cursors are per-feature-shard; when M changed,
            # restart cursors at 0 (coverage guarantee unaffected)
            md = ckpt_manager.read_metadata()
            if "next_it" not in md:
                raise ValueError(
                    "checkpoint was written by fit_path (path state), not a "
                    "single fit; resume it with fit_path(ckpt_manager=...)")
            self._check_layout(md)
            saved, _ = ckpt_manager.restore(
                {"beta": state.beta, "xb": state.xb, "mu": state.mu})
            state = state._replace(
                beta=self._place_feat(self._adapt_cols(
                    self._host(saved["beta"]))),
                xb=self._place_row(self._adapt_rows(
                    self._host(saved["xb"]))),
                mu=self._place_scalar(np.asarray(saved["mu"]), np.float32),
                step=self._place_scalar(md["next_it"] - 1, np.int32))
            f_prev = md.get("f_prev", np.inf)
            start_it = int(md["next_it"])
        for it in range(start_it, max_outer + 1):
            state, mh = self._dispatch_superstep(X, weights_dev, lams,
                                                 active_dev, state)
            self.launch_stats["supersteps"] += 1
            self.launch_stats["tail_entries"] += tail_entries
            self.launch_stats["sweep_tile_launches"] += \
                live_tiles if shaped else total_tiles
            if shaped:
                self.launch_stats["sweep_tiles_skipped"] += \
                    total_tiles - live_tiles
            self.launch_stats["gram_blocks_mirrored"] += \
                (live_tiles if shaped else total_tiles) * mirrored
            # lint: allow SYNC001 — mh is the host copy fetched in dispatch
            f = float(mh["f"])
            for k in history:
                history[k].append(float(mh[k]))
            if self._conv is not None:
                self._emit_conv(it, mh, lam1=lam1, lam2=lam2,
                                active_size=live_active)
            if verbose:
                tag = "dglmnet" if self.mesh is None else \
                    f"dglmnet/{self._D}x{self._M}"
                print(f"[{tag}] it={it} f={f:.8f} "
                      f"alpha={float(mh['alpha']):.4f} "
                      f"mu={float(mh['mu']):.3f} nnz={int(mh['nnz'])}")
            if ckpt_manager is not None and it % ckpt_every == 0:
                ckpt_manager.save(it, {"beta": state.beta, "xb": state.xb,
                                       "mu": state.mu},
                                  metadata={"next_it": it + 1, "f_prev": f,
                                            "design_layout":
                                                self._design_layout})
            if "df" in mh:
                # the superstep measured its own change of the objective and
                # the change its full step predicts (D), both summed at the
                # change's size: converged once each is at most tol of the
                # objective (a line search that found no step moves
                # nothing, however far from the optimum)
                if max(abs(float(mh["df"])), abs(float(mh["D"]))) <= \
                        tol * max(1.0, abs(f)):
                    converged = True
                    break
            elif np.isfinite(f_prev) and \
                    abs(f_prev - f) <= tol * max(1.0, abs(f)):
                converged = True
                break
            f_prev = f
        if ckpt_manager is not None:
            ckpt_manager.wait()
        return state, history, it, converged

    # ------------------------------------------------- streaming outer loop

    def _stream_xb(self):
        """Streaming fits never carry the (n,) margins: Xβ is
        re-materialized chunk by chunk inside every pass, so the state's
        margin slot is an empty placeholder."""
        return jnp.zeros((0,), jnp.float32)

    def _iter_row_chunks(self, weights=None, start: int = 0):
        """Yield ``(i, X_chunk, y, w, offset)`` — the design's
        double-buffered device chunks zipped with the matching slices of
        the session's host row vectors.  THE one place chunk addressing
        lives; every streaming pass (stats, line search, gradient,
        deviance) iterates through here."""
        sd: StreamingDesign = self._Xs
        w = self._wobs if weights is None \
            else np.asarray(weights, np.float32)
        for i, Xc in sd.iter_chunks(start=start):
            sl = sd.row_slice(i)
            yield i, Xc, self._ys[sl], w[sl], self._offsets[sl]

    def _run_streaming(self, state: FitState, lam1: float, lam2: float, *,
                       weights=None, active=None, max_outer=None, tol=None,
                       verbose=False, ckpt_manager=None, ckpt_every: int = 10,
                       ckpt_every_chunks: Optional[int] = None):
        """Out-of-core twin of ``_run`` (DESIGN.md §6): each superstep is
        two double-buffered passes over the design's row chunks — pass 1
        accumulates (XᵀWX, Xᵀs, Σ loss), pass 2 accumulates every
        line-search candidate's loss — with the budgeted CD sweep and the
        Armijo selection running on device between and after them.

        Checkpoints grow a CHUNK CURSOR: besides the superstep-boundary
        saves (every ``ckpt_every`` iterations, like the in-memory path),
        ``ckpt_every_chunks`` saves the partial pass-1 accumulators every k
        chunks, so a mid-epoch crash resumes at the right chunk instead of
        replaying the whole pass.
        """
        cfg = self.config
        sd: StreamingDesign = self._Xs
        fns = self._superstep
        max_outer = cfg.max_outer if max_outer is None else int(max_outer)
        tol = cfg.tol if tol is None else float(tol)
        lams = jnp.asarray([lam1, lam2], jnp.float32)
        active_dev = self._active_ones if active is None else \
            self._place_feat(np.asarray(active, np.float32))
        p = self._p_tot

        def zero_acc():
            return (jnp.zeros((p, p), jnp.float32),
                    jnp.zeros((p,), jnp.float32), jnp.float32(0.0))

        history = {k: [] for k in _HISTORY_KEYS}
        f_prev, converged, it = np.inf, False, 0
        start_it, resume_chunk, acc = 1, 0, None
        if ckpt_manager is not None and ckpt_manager.latest_step() is not None:
            md = ckpt_manager.read_metadata()
            if "next_it" not in md:
                raise ValueError(
                    "checkpoint was written by fit_path (path state), not a "
                    "single fit; resume it with fit_path(ckpt_manager=...)")
            self._check_layout(md)
            template = {"beta": state.beta, "mu": state.mu}
            chunk_cursor = md.get("stream_chunk")
            if chunk_cursor is not None:
                template.update(G=np.zeros((p, p), np.float32),
                                g0=np.zeros((p,), np.float32),
                                L=np.float32(0.0))
            saved, _ = ckpt_manager.restore(template)
            state = state._replace(
                beta=self._place_feat(self._adapt_cols(saved["beta"])),
                mu=jnp.float32(np.asarray(saved["mu"])),
                step=jnp.int32(md["next_it"] - 1))
            f_prev = md.get("f_prev", np.inf)
            start_it = int(md["next_it"])
            if chunk_cursor is not None:
                resume_chunk = int(chunk_cursor)
                acc = (jnp.asarray(saved["G"]), jnp.asarray(saved["g0"]),
                       jnp.asarray(np.float32(saved["L"])))

        for it in range(start_it, max_outer + 1):
            # ---- pass 1: chunked statistics (G_w, g0, loss) ----
            if acc is None:
                acc, resume_chunk = zero_acc(), 0
            with obs_trace.span("solver/stream_stats",
                                args={"it": it}) as sp_stats:
                for i, Xc, yc, wc, oc in self._iter_row_chunks(
                        weights, start=resume_chunk):
                    acc = fns.stats_chunk(Xc, yc, wc, oc, state.beta, acc)
                    if (ckpt_manager is not None and ckpt_every_chunks
                            and (i + 1) % ckpt_every_chunks == 0
                            and i + 1 < sd.n_chunks):
                        G, g0, L = acc
                        ckpt_manager.save(
                            it, {"beta": state.beta, "mu": state.mu,
                                 "G": G, "g0": g0, "L": L},
                            metadata={"next_it": it, "stream_chunk": i + 1,
                                      "f_prev": float(f_prev),
                                      "design_layout": self._design_layout})
            with obs_trace.span("solver/stream_sweep") as sp_sweep:
                prep = fns.prepare(acc, state.beta, state.mu, lams,
                                   active_dev, self._penf, state.cursor,
                                   self._budgets())
            acc = None
            # ---- pass 2: every line-search candidate in one sweep ----
            with obs_trace.span("solver/stream_line_search") as sp_ls:
                losses = jnp.zeros((fns.n_candidates,), jnp.float32)
                for _, Xc, yc, wc, oc in self._iter_row_chunks(weights):
                    losses = fns.ls_chunk(Xc, yc, wc, oc, state.beta,
                                          prep["dbeta"], prep["cand"],
                                          losses)
                state, m = fns.finish(losses, prep, state, lams, self._penf)
            # one batched device→host sync per outer iteration (SYNC001)
            mh = jax.device_get(m)
            f = float(mh["f"])
            for k in history:
                history[k].append(float(mh[k]))
            if self._conv is not None:
                # per-phase µs from the pass spans (host-side dispatch;
                # zeros when tracing is disabled → emit None instead)
                phase_us = {"stats": round(sp_stats.elapsed_us, 1),
                            "sweep": round(sp_sweep.elapsed_us, 1),
                            "line_search": round(sp_ls.elapsed_us, 1)}
                total = sum(phase_us.values())
                self._emit_conv(
                    it, mh, lam1=lam1, lam2=lam2,
                    active_size=self._p_tot if active is None
                    else int((np.asarray(active) > 0).sum()),
                    step_us=total or None,
                    phase_us=phase_us if total else None)
            if verbose:
                print(f"[dglmnet/stream x{sd.n_chunks}] it={it} "
                      f"f={f:.8f} alpha={float(mh['alpha']):.4f} "
                      f"mu={float(mh['mu']):.3f} nnz={int(mh['nnz'])}")
            if ckpt_manager is not None and it % ckpt_every == 0:
                ckpt_manager.save(it, {"beta": state.beta, "mu": state.mu},
                                  metadata={"next_it": it + 1, "f_prev": f,
                                            "design_layout":
                                                self._design_layout})
            if np.isfinite(f_prev) and \
                    abs(f_prev - f) <= tol * max(1.0, abs(f)):
                converged = True
                break
            f_prev = f
        if ckpt_manager is not None:
            ckpt_manager.wait()
        return state, history, it, converged

    def _check_layout(self, md):
        if md.get("design_layout") != self._design_layout:
            raise ValueError(
                f"checkpoint design layout {md.get('design_layout')} does "
                f"not match this fit's {self._design_layout}; the brick "
                "packing depends on the mesh/tiling, so blocked-sparse "
                "checkpoints resume only onto the same "
                "(D, M, tile, row_block) layout")

    def _adapt_cols(self, arr):
        """Elastic re-map of a checkpointed feature vector onto this
        session's padded width.  Only the dense layout reaches here with a
        mismatch (bricks are layout-checked upstream), and its packed order
        is the identity with zero padding at the tail on BOTH sides, so
        truncating/zero-extending at ``p_model`` is exact — resuming a
        mesh whose M·T padding differs must not shift features across
        shards."""
        a = np.asarray(arr, np.float32)
        if a.shape[-1] == self._p_tot:
            return a
        out = np.zeros(a.shape[:-1] + (self._p_tot,), np.float32)
        m = min(a.shape[-1], self._p_tot)
        out[..., :m] = a[..., :m]
        return out

    def _adapt_rows(self, arr):
        """Row twin of ``_adapt_cols``: real rows lead, padding trails."""
        a = np.asarray(arr, np.float32)
        if a.shape[0] == self._n_tot:
            return a
        out = np.zeros((self._n_tot,), np.float32)
        m = min(a.shape[0], self._n_tot)
        out[:m] = a[:m]
        return out

    # ------------------------------------------------------------- fitting

    def training_margins(self) -> np.ndarray:
        """Host (n,) margins Xβ̂ over the TRAINING design at the current
        fitted state — no offset applied; the intercept is included when
        it was fitted (it is a design column).  In-memory sessions read
        the maintained margins; streaming sessions re-materialize them in
        one chunk pass."""
        if self._state is None:
            raise ValueError("no fitted state; call fit first")
        if not self._streaming:
            return self._host(self._state.xb)[: self._n_user]
        beta = self._state.beta
        out = np.empty((self._n_tot,), np.float32)
        rows = self._Xs.chunk_rows
        for i, Xc, _, _, _ in self._iter_row_chunks():
            lo = i * rows
            out[lo:lo + Xc.shape[0]] = np.asarray(Xc @ beta)
        return out[: self._n_user]

    def set_observations(self, *, y=None, sample_weight=None, offset=None):
        """Swap the observation model on the SAME compiled session.

        The compiled superstep is a pure function of the design layout and
        config — y, weights and offsets are runtime arguments — so
        replacing them costs zero recompiles.  This is the mechanism the
        class-cycling multinomial solver leans on: one logistic session
        per design, K offset swaps per epoch (glm/estimators.py).

        Any provided vector must be length ``n`` (original rows); padding
        is reapplied with the session's conventions (y → 1, weights → 0,
        offset → 0).  Warm-start state is cleared, since the objective
        changed under it.
        """
        n = self._n_user
        pad = self._n_tot - n
        if y is not None:
            y = np.asarray(y, np.float32)
            if y.shape != (n,):
                raise ValueError(f"y must be ({n},); got {y.shape}")
            self._ys = self._place_row(
                np.pad(y, (0, pad), constant_values=1.0))
        if sample_weight is not None:
            sw = np.asarray(sample_weight, np.float32)
            if sw.shape != (n,):
                raise ValueError(
                    f"sample_weight must be ({n},); got {sw.shape}")
            if (sw < 0).any():
                raise ValueError("sample_weight must be nonnegative")
            self._wobs_host = np.pad(sw, (0, pad))
            self._wobs = self._place_row(self._wobs_host)
        if offset is not None:
            off = np.asarray(offset, np.float32)
            if off.shape != (n,):
                raise ValueError(f"offset must be ({n},); got {off.shape}")
            self._offsets = self._place_row(np.pad(off, (0, pad)))
        self._state = None
        self._lmax = self._grad0 = None
        return self

    def fit(self, lam1: Optional[float] = None, lam2: Optional[float] = None,
            *, beta0=None, intercept0: float = 0.0, max_outer=None, tol=None,
            verbose=False, ckpt_manager=None, ckpt_every: int = 10,
            ckpt_every_chunks: Optional[int] = None) -> FitResult:
        """Fit one (λ1, λ2) point; defaults come from the session config.

        ``beta0`` (+ ``intercept0``) warm-starts from a host β in ORIGINAL
        feature order and scale (the margins are recomputed through the
        placed design).  Checkpointing matches the historical driver:
        superstep-boundary saves of (β, Xβ, μ), elastic resume onto this
        session's mesh.  Streaming sessions additionally accept
        ``ckpt_every_chunks``: the partial pass-1 accumulators are saved
        with a chunk cursor every k chunks, so a mid-epoch crash resumes at
        the right chunk (DESIGN.md §6).
        """
        cfg = self.config
        lam1 = cfg.lam1 if lam1 is None else float(lam1)
        lam2 = cfg.lam2 if lam2 is None else float(lam2)
        state = self._init_state(beta0, intercept0)
        state, history, n_iter, converged = self._run(
            state, lam1, lam2, max_outer=max_outer, tol=tol, verbose=verbose,
            ckpt_manager=ckpt_manager, ckpt_every=ckpt_every,
            ckpt_every_chunks=ckpt_every_chunks)
        self._state = state
        self.beta_, self.intercept_ = self._unpack_user(
            self._host(state.beta))
        return FitResult(self.beta_, history, n_iter, converged)

    def lambda_max(self) -> float:
        """Smallest λ1 for which every PENALIZED coordinate is zero:
        max_j |g_j| / pf_j over penalized columns, with the gradient taken
        at the NULL model — unpenalized coordinates (the intercept) are
        fitted first, since they are active at every λ.  Without
        unpenalized coordinates this is the classic ‖Xᵀ s(0)‖_∞ at zero
        margins (plus offsets)."""
        if self._lmax is None:
            pen = self._penf_host > _PF_EPS
            if not pen.any():
                raise ValueError(
                    "lambda_max undefined: every feature is unpenalized")
            state = self._init_state(None)
            if (~pen).any():
                # null fit: only the unpenalized coordinates move (λ is
                # irrelevant for them); same compiled superstep
                state, _, _, _ = self._run(
                    state, 0.0, 0.0, active=(~pen).astype(np.float32),
                    max_outer=50)
            g = self._grad_state(state)
            if not (~pen).any():
                # the gradient at β = 0, where every path starts
                self._grad0 = g
            self._lmax = float((np.abs(g)[pen] / self._penf_host[pen]).max())
        return self._lmax

    def _make_grid(self, lambdas, n_lambdas, lam_ratio):
        if lambdas is None:
            lmax = self.lambda_max()
            lambdas = np.logspace(np.log10(lmax),
                                  np.log10(lmax * lam_ratio), n_lambdas)
        lambdas = np.asarray(lambdas, np.float64)
        if len(lambdas) > 1 and not np.all(np.diff(lambdas) < 0):
            raise ValueError("fit_path expects a strictly decreasing λ1 "
                             "grid (warm starts go dense-ward)")
        return lambdas

    def _deviance(self, xb_dev, weights_dev) -> float:
        """Total weighted deviance of the maintained margins over the rows
        selected by ``weights_dev`` — evaluated in place on the placed
        row vectors (one scalar comes back per call; the distributed
        margins are never gathered to host)."""
        if self._dev_fn is None:
            fam = glm.get_family(self.config.family)
            ax_d = self.axis_data

            def dev(y, xb, w, off):
                d = fam.deviance(y, xb, weights=w, offset=off)
                return jax.lax.psum(d, ax_d) if ax_d is not None else d

            if self.mesh is None:
                self._dev_fn = jax.jit(dev)
            else:
                self._dev_fn = jax.jit(compat.shard_map(
                    dev, mesh=self.mesh,
                    in_specs=(self._row_spec,) * 4, out_specs=P(),
                    check_vma=False))
        return float(self._dev_fn(self._ys, xb_dev, weights_dev,
                                  self._offsets))

    def _deviance_state(self, state: FitState, weights) -> float:
        """Total weighted deviance at a fit state; the streaming variant
        accumulates it over re-materialized per-chunk margins (one scalar
        lives on device, the rows never do)."""
        if not self._streaming:
            return self._deviance(state.xb, weights)
        if self._dev_fn is None:
            fam = glm.get_family(self.config.family)

            @functools.partial(jax.jit, donate_argnums=(5,))
            def dev_chunk(Xc, yc, wc, oc, beta, d):
                return d + fam.deviance(yc, Xc @ beta, weights=wc, offset=oc)

            self._dev_fn = dev_chunk
        d = jnp.float32(0.0)
        for _, Xc, yc, wc, oc in self._iter_row_chunks(weights):
            d = self._dev_fn(Xc, yc, wc, oc, state.beta, d)
        return float(d)

    def _path_impl(self, lambdas: np.ndarray, lam2: float, *,
                   weights=None, eval_weights=None, screen=True,
                   kkt_slack=1e-4, max_outer=None, tol=None, verbose=False,
                   ckpt_manager=None):
        """Warm-started path driver over a fixed decreasing grid.

        ``weights``: placed row weights (None = session weights) — the CV
        fold mechanism.  ``eval_weights``: host row weights of a held-out
        set; when given, the mean validation deviance is recorded per λ
        (evaluated on device against the maintained margins).
        Returns (betas_packed, f, nnz, n_iters, converged, val_dev, state).
        """
        cfg = self.config
        K = len(lambdas)
        pf = self._penf_host
        unpen = pf <= _PF_EPS
        if eval_weights is not None:
            ew_dev = self._place_row(np.asarray(eval_weights, np.float32))
            ew_sum = float(np.asarray(eval_weights).sum())

        state = self._init_state(None)
        betas_packed = np.zeros((K, self._p_tot), np.float32)
        f = np.full((K,), np.nan)
        nnz = np.zeros((K,), np.int64)
        n_iters = np.zeros((K,), np.int64)
        converged = np.zeros((K,), bool)
        val_dev = np.full((K,), np.nan) if eval_weights is not None else None
        start_k = 0

        if ckpt_manager is not None and ckpt_manager.latest_step() is not None:
            md = ckpt_manager.read_metadata()
            if "path" not in md:
                raise ValueError(
                    "checkpoint was written by a single fit, not fit_path; "
                    "resume it with fit(ckpt_manager=...)")
            self._check_layout(md)
            saved, _ = ckpt_manager.restore(
                {"beta": state.beta, "xb": state.xb, "mu": state.mu,
                 "path_betas": betas_packed})
            pmd = md["path"]
            start_k = int(pmd["next_k"])
            saved_grid = np.asarray(pmd["lambdas"], np.float64)
            # the COMPLETED prefix must coincide (a longer tail is fine —
            # that is exactly the interrupted-mid-grid resume case)
            if start_k > K or float(pmd["lam2"]) != lam2 or \
                    not np.allclose(saved_grid[:start_k], lambdas[:start_k]):
                raise ValueError(
                    "path checkpoint was written for a different λ grid; "
                    "pass the same lambdas/lam2 to resume")
            state = state._replace(
                beta=self._place_feat(self._adapt_cols(
                    self._host(saved["beta"]))),
                xb=state.xb if self._streaming
                else self._place_row(self._adapt_rows(
                    self._host(saved["xb"]))),
                mu=self._place_scalar(np.asarray(saved["mu"]), np.float32))
            saved_betas = self._adapt_cols(saved["path_betas"])
            betas_packed[:start_k] = saved_betas[:start_k]
            for name, arr in (("f", f), ("nnz", nnz),
                              ("n_iters", n_iters), ("converged", converged)):
                arr[:start_k] = np.asarray(pmd[name])[:start_k]

        lam_prev = float(lambdas[start_k - 1]) if start_k else None
        g_warm = None           # gradient at the warm iterate, if known
        for k in range(start_k, K):
            lam1 = float(lambdas[k])
            with obs_trace.span("solver/lambda", args={"k": k, "lam1": lam1}):
                # fresh trust region per λ; warm β / margins carry over
                state = state._replace(
                    mu=self._place_scalar(cfg.mu_init, np.float32),
                    step=self._place_scalar(0, np.int32))
                if screen:
                    with obs_trace.span("solver/screen"):
                        # sequential strong rule (Tibshirani et al. 2012):
                        # |g_j| = |[Xᵀ s(β_{k-1})]_j| ≥ pf_j (2λ_k − λ_{k-1})
                        # — plus every currently-active and every
                        # unpenalized coordinate; the previous λ's final
                        # KKT gradient IS the gradient at this warm
                        # iterate, so reuse it
                        if g_warm is None and k == 0 and weights is None:
                            # every path starts at β = 0: one gradient
                            # there serves the session
                            if self._grad0 is None:
                                self._grad0 = self._grad_state(state)
                            g = self._grad0
                        elif g_warm is None:
                            g = self._grad_state(state, weights)
                        else:
                            g = g_warm
                        thresh = np.full_like(pf, 2.0 * lam1 - (
                            lam_prev if lam_prev is not None else lam1))
                        # a head/tail design's tail columns enter when they
                        # violate the KKT condition at the warm start: on a
                        # coarse grid (λ_{k-1} > 2 λ_k) the strong rule
                        # keeps every column, and the tail's superstep
                        # costs what its working set holds
                        thresh[self._tail_start:] = lam1
                        active = (np.abs(g) >= pf * thresh - 1e-12) | \
                            (self._host(state.beta) != 0.0) | unpen
                    it_k = 0
                    for r in range(8):
                        # convergence-stream context: where on the path we
                        # are, how hard the strong rule screened, and what
                        # the last KKT check found (None before the first)
                        self._conv_ctx = {
                            "lam_index": k,
                            "screened": int(active.size - active.sum()),
                            "kkt_violations": self._conv_ctx.get(
                                "kkt_violations")
                            if self._conv_ctx.get("lam_index") == k else None}
                        state, hist, it_round, conv_k = self._run(
                            state, lam1, lam2, kkt_round=r, weights=weights,
                            active=active, max_outer=max_outer, tol=tol,
                            verbose=verbose)
                        self.launch_stats["kkt_rounds"] += 1
                        it_k += it_round
                        with obs_trace.span("solver/kkt"):
                            # KKT post-check on the FULL gradient: a
                            # screened-out coordinate (β_j = 0) is truly
                            # optimal iff |g_j| ≤ λ1 pf_j
                            g = self._grad_state(state, weights)
                            viol = (~active) & (
                                np.abs(g) > pf * lam1 * (1.0 + kkt_slack)
                                + 1e-7)
                        self._conv_ctx["kkt_violations"] = int(viol.sum())
                        if not viol.any():
                            break
                        active |= viol
                    g_warm = g
                else:
                    self._conv_ctx = {"lam_index": k}
                    state, hist, it_k, conv_k = self._run(
                        state, lam1, lam2, kkt_round=0, weights=weights,
                        max_outer=max_outer, tol=tol, verbose=verbose)
                    self.launch_stats["kkt_rounds"] += 1
                betas_packed[k] = self._host(state.beta)
                if hist["f"]:
                    f[k] = hist["f"][-1]
                    nnz[k] = int(hist["nnz"][-1])
                n_iters[k] = it_k
                converged[k] = conv_k
                if val_dev is not None:
                    val_dev[k] = self._deviance_state(state, ew_dev) / ew_sum \
                        if ew_sum > 0 else np.nan
                lam_prev = lam1
                if verbose:
                    print(f"[path {k + 1}/{K}] lam1={lam1:.6g} f={f[k]:.8f} "
                          f"nnz={nnz[k]} iters={it_k}")
                if ckpt_manager is not None:
                    ckpt_manager.save(
                        k + 1,
                        {"beta": state.beta, "xb": state.xb, "mu": state.mu,
                         "path_betas": betas_packed},
                        metadata={"design_layout": self._design_layout,
                                  "path": {"next_k": k + 1,
                                           "lambdas": lambdas.tolist(),
                                           "lam2": lam2,
                                           "f": f[:k + 1].tolist(),
                                           "nnz": nnz[:k + 1].tolist(),
                                           "n_iters":
                                               n_iters[:k + 1].tolist(),
                                           "converged":
                                               converged[:k + 1].tolist()}})
            self.launch_stats["lambdas"] += 1
        if ckpt_manager is not None:
            ckpt_manager.wait()
        self._conv_ctx = {}
        return betas_packed, f, nnz, n_iters, converged, val_dev, state

    def _path_result(self, lambdas, lam2, betas_packed, f, nnz, n_iters,
                     converged) -> PathResult:
        K = len(lambdas)
        if K:
            pairs = [self._unpack_user(b) for b in betas_packed]
            betas = np.stack([b for b, _ in pairs])
            intercepts = np.asarray([b0 for _, b0 in pairs], np.float32)
        else:
            betas = np.zeros((0, self._p_user), np.float32)
            intercepts = np.zeros((0,), np.float32)
        return PathResult(lambdas, lam2, betas, f, nnz, n_iters, converged,
                          intercepts if self.fit_intercept else None)

    def fit_path(self, lambdas=None, *, n_lambdas: int = 100,
                 lam_ratio: float = 1e-3, lam2: Optional[float] = None,
                 screen: bool = True, kkt_slack: float = 1e-4,
                 max_outer=None, tol=None, verbose=False,
                 ckpt_manager=None) -> PathResult:
        """Warm-started fit over a decreasing λ1 grid.

        ``lambdas=None`` builds the standard GLMNET grid: ``n_lambdas``
        log-spaced points from λ_max = max_j |g_j(0)|/pf_j down to
        λ_max·``lam_ratio``.  Each λ warm-starts from the previous solution
        (β and the maintained margins Xβ stay on device); ``screen=True``
        freezes strong-rule-cold coordinates during the sweeps and verifies
        the KKT conditions on the full gradient afterwards, re-fitting with
        any violators unfrozen, so screening never changes the solution.

        ``ckpt_manager`` extends checkpointing to path state: after each λ
        the warm (β, Xβ, μ) plus the per-λ results so far are saved, and a
        later call with the same grid resumes mid-grid.
        """
        cfg = self.config
        lam2 = cfg.lam2 if lam2 is None else float(lam2)
        with obs_trace.span("solver/path"):
            lambdas = self._make_grid(lambdas, n_lambdas, lam_ratio)
            betas_packed, f, nnz, n_iters, converged, _, state = \
                self._path_impl(lambdas, lam2, screen=screen,
                                kkt_slack=kkt_slack, max_outer=max_outer,
                                tol=tol, verbose=verbose,
                                ckpt_manager=ckpt_manager)
            self._state = state
            result = self._path_result(lambdas, lam2, betas_packed, f, nnz,
                                       n_iters, converged)
        if len(lambdas):
            self.beta_ = result.betas[-1]
            self.intercept_ = float(result.intercepts[-1]) \
                if result.intercepts is not None else 0.0
        return result

    def fit_cv(self, n_folds: int = 5, *, lambdas=None,
               n_lambdas: int = 100, lam_ratio: float = 1e-3,
               lam2: Optional[float] = None, seed: int = 0,
               screen: bool = True, max_outer=None, tol=None,
               verbose=False) -> CVResult:
        """Mask-based K-fold cross-validation over the λ path — one
        compiled superstep for everything.

        Folds are runtime row masks on the one packed, mesh-placed design:
        fold f trains with weights ``w·[fold ≠ f]`` and validates on
        ``w·[fold = f]`` — no data movement, no recompilation (the weight
        vector is a superstep argument).  Every fold runs a warm-started
        path over the SAME full-data λ grid; λ is selected by mean
        validation deviance; the returned coefficients are the full-data
        path's solution at the selected λ (the refit on all rows).

        Protocol note: with ``standardize=True`` the column scaling is the
        SESSION's (computed once from all rows at construction) — folds are
        penalized in a shared scale rather than re-standardized per
        training fold as cv.glmnet does.  That is the price of the
        zero-data-movement design; with K-fold-sized validation sets the
        moment perturbation is O(1/K) and the selected λ is ordinarily
        unchanged (DESIGN.md §5).
        """
        if n_folds < 2:
            raise ValueError("fit_cv needs n_folds >= 2")
        cfg = self.config
        lam2 = cfg.lam2 if lam2 is None else float(lam2)
        lambdas = self._make_grid(lambdas, n_lambdas, lam_ratio)
        K = len(lambdas)
        n = self._n_user

        # full-data path: the λ grid anchor and the final refit
        betas_packed, f, nnz, n_iters, converged, _, state = self._path_impl(
            lambdas, lam2, screen=screen, max_outer=max_outer, tol=tol,
            verbose=verbose)
        full_path = self._path_result(lambdas, lam2, betas_packed, f, nnz,
                                      n_iters, converged)

        rng = np.random.default_rng(seed)
        fold_of = np.full((self._n_tot,), -1, np.int64)   # padding: no fold
        fold_of[:n] = rng.permuted(np.arange(n) % n_folds)

        dev_folds = np.full((n_folds, K), np.nan)
        for fold in range(n_folds):
            w_tr = self._wobs_host * (fold_of != fold)
            w_val = self._wobs_host * (fold_of == fold)
            if verbose:
                print(f"[cv fold {fold + 1}/{n_folds}] "
                      f"train w={w_tr.sum():.0f} val w={w_val.sum():.0f}")
            _, _, _, _, _, val_dev, _ = self._path_impl(
                lambdas, lam2, weights=self._place_row(w_tr),
                eval_weights=w_val, screen=screen, max_outer=max_outer,
                tol=tol, verbose=False)
            dev_folds[fold] = val_dev

        dev_mean = np.nanmean(dev_folds, axis=0)
        dev_se = np.nanstd(dev_folds, axis=0, ddof=1) / np.sqrt(n_folds)
        best = int(np.nanargmin(dev_mean))
        lam_best = float(lambdas[best])

        self._state = state
        self.beta_ = full_path.betas[best]
        self.intercept_ = float(full_path.intercepts[best]) \
            if full_path.intercepts is not None else 0.0
        return CVResult(lambdas, lam2, dev_folds, dev_mean, dev_se, best,
                        lam_best, full_path, self.beta_, self.intercept_)

    # ---------------------------------------------------------- evaluation

    def _serve_engine(self, beta: np.ndarray, intercept: float):
        """Serving engine over (β, b₀) — the SparseCOO prediction path
        (DESIGN.md §7): sparse rows are scored by the active-set-compacted
        gather-dot-link launch instead of a host matvec.  Cached on the
        coefficient bytes so repeated predicts reuse the compacted table
        and its compiled programs."""
        from repro.serve.artifact import ServableModel
        from repro.serve.engine import ScoringEngine
        key = (beta.tobytes(), float(intercept))
        if self._serve_cache is None or self._serve_cache[0] != key:
            model = ServableModel(
                betas=np.array(beta[None, :], np.float32),
                intercepts=np.asarray([intercept], np.float32),
                family=self.config.family)
            self._serve_cache = (key, ScoringEngine(model))
        return self._serve_cache[1]

    def save(self, path, *, quantize=None, path_result=None):
        """Export the fitted model as a versioned serving artifact
        (``repro.serve.artifact``).  ``path_result`` exports a whole
        fitted λ-path as a multi-output artifact; ``quantize="int8"``
        writes the shared-scale quantized weight table."""
        from repro.serve import artifact
        return artifact.export(self, path, quantize=quantize,
                               path_result=path_result)

    def predict(self, X_new, *, beta=None, intercept=None, offset=None,
                kind: str = "response"):
        """Predict on new rows with the last fitted (β, intercept) — or a
        given one — plus an optional per-row ``offset``.

        ``kind="link"`` returns raw margins Xβ + b₀ + o; ``"response"``
        applies the family's inverse link (probabilities for
        logistic/probit, means for squared/poisson).  ``SparseCOO`` inputs
        route through the serving engine's fused sparse scoring (gather +
        dot + link over the compacted active set) rather than a host-side
        matvec; ``SparseRows`` are scored by a gather-sum over their
        pairs.
        """
        beta = self.beta_ if beta is None else np.asarray(beta, np.float32)
        if beta is None:
            raise ValueError("no fitted coefficients; call fit/fit_path "
                             "first or pass beta=...")
        intercept = self.intercept_ if intercept is None else float(intercept)
        if kind not in ("link", "response"):
            raise ValueError(f"unknown kind {kind!r}; use 'link' or "
                             "'response'")
        if isinstance(X_new, SparseCOO):
            eng = self._serve_engine(beta, intercept)
            return eng.score_coo(X_new, kind=kind, offset=offset)[:, 0]
        if isinstance(X_new, SparseRows):
            m = np.asarray(X_new.matvec(beta)) + intercept
        else:
            m = np.asarray(X_new, np.float32) @ beta + intercept
        if offset is not None:
            m = m + np.asarray(offset, np.float32)
        if kind == "link":
            return m
        fam = glm.get_family(self.config.family)
        return np.asarray(fam.predict(jnp.asarray(m)))

    def score(self, X_new, y_new, *, beta=None, intercept=None,
              offset=None) -> float:
        """Family-appropriate goodness of fit on held-out rows
        (``glm.margin_score``): accuracy for the binary families (labels
        in {-1, +1}), R² for squared loss, and mean negative loss (higher
        is better) for poisson."""
        m = self.predict(X_new, beta=beta, intercept=intercept,
                         offset=offset, kind="link")
        return glm.margin_score(self.config.family,
                                np.asarray(y_new, np.float32), m)
