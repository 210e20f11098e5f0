"""Public jit'd entry points for the kernels package.

Each op dispatches between:
  * the Pallas kernel, compiled (TPU) or interpret mode (CPU validation),
  * the pure-jnp oracle in ref.py (``backend="ref"``) — also the path used
    inside shard_map'd distributed code where the vectors are already tiled
    by the partitioner and XLA fusion is adequate.

The default is chosen per jax backend; tests exercise both and assert they
agree.  On a TPU every dispatch that lands on the oracle anyway (a caller's
``backend="ref"``, the ``REPRO_KERNEL_BACKEND`` override, a family without a
Pallas body, a layout the fused kernels do not take) is counted while an
``oracle_trace()`` is active, so a run that meant to exercise the kernels
can list what did not.
"""
from __future__ import annotations

import collections
import contextlib
import os

import jax
import jax.numpy as jnp

from repro.core import glm as glm_lib
from repro.kernels import ref
from repro.kernels.alpha_search import alpha_search_pallas
from repro.kernels.cd_tile_solve import cd_tile_solve_pallas
from repro.kernels.glm_stats import _STATS as _PALLAS_STATS
from repro.kernels.glm_stats import glm_stats_pallas
from repro.kernels.predict_tile import _LINKS as _PALLAS_LINKS
from repro.kernels.predict_tile import predict_tile_pallas
from repro.kernels.superstep_tile import gram_blocks_mirrored
from repro.kernels.superstep_tile import margin_ls_pallas
from repro.kernels.superstep_tile import stats_gram_solve_pallas
from repro.kernels.tile_gram import tile_gram_pallas

_LANES = 128

# Rows of the dense tile-major operand are padded to a multiple of this once,
# when the design is built (data/design.py), so the fused kernels never copy
# it per call: 1024 examples are one (8, 128) tile of the packed vectors.
DENSE_ROW_BLOCK = 1024
# VMEM budget of one block of the scoring kernel's weight table
_TABLE_BLOCK_BYTES = 4 << 20

# --- trace-time launch accounting (repro.analysis.audit) -------------------
# Every public dispatch entry below records a logical launch event while a
# ``launch_trace()`` is active.  Events fire at trace time — under jit that
# is once per compile, not once per step — so the auditor can pin the
# per-superstep launch structure (fused = 2, unfused = 5) without running
# the kernels or needing a TPU.
_LAUNCH_EVENTS = None


@contextlib.contextmanager
def launch_trace():
    """Collect ops-level launch events during a trace; yields the live list."""
    global _LAUNCH_EVENTS
    prev = _LAUNCH_EVENTS
    _LAUNCH_EVENTS = events = []
    try:
        yield events
    finally:
        _LAUNCH_EVENTS = prev


def record_launch(name):
    """Record one logical device launch (no-op outside ``launch_trace()``)."""
    if _LAUNCH_EVENTS is not None:
        _LAUNCH_EVENTS.append(name)


# --- oracle dispatches on a TPU -------------------------------------------
# Like the launch events: recorded at trace time while ``oracle_trace()`` is
# active, so a run meant to exercise the kernels can list what did not.
_ORACLE_EVENTS = None


@contextlib.contextmanager
def oracle_trace():
    """Collect, on a TPU, the dispatches that resolve to the jnp oracle
    while tracing; yields a live ``Counter`` of ``(op, reason)``."""
    global _ORACLE_EVENTS
    prev = _ORACLE_EVENTS
    _ORACLE_EVENTS = events = collections.Counter()
    try:
        yield events
    finally:
        _ORACLE_EVENTS = prev


def default_backend() -> str:
    env = os.environ.get("REPRO_KERNEL_BACKEND")
    if env:
        return env
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _resolve(op, backend, *, fallback=None):
    """The backend one dispatch of ``op`` runs on.  ``fallback`` names why
    this call cannot take the Pallas kernel (None when it can); on a TPU a
    dispatch that lands on the oracle is recorded with its reason."""
    if backend is not None:
        why = "backend='ref' requested"
    else:
        backend = default_backend()
        why = "REPRO_KERNEL_BACKEND=ref"
    if backend != "ref" and fallback is not None:
        backend, why = "ref", fallback
    if backend == "ref" and _ORACLE_EVENTS is not None and \
            jax.default_backend() == "tpu":
        _ORACLE_EVENTS[(op, why)] += 1
    return backend


def _no_pallas_body(fname):
    return None if fname in _PALLAS_STATS else \
        f"family {fname!r} has no Pallas stats body"


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pack_2d(*vecs, block_rows):
    """Pad 1-D vectors to (R, 128) with R % block_rows == 0, plus a mask."""
    n = vecs[0].shape[0]
    per_block = block_rows * _LANES
    n_pad = (-n) % per_block
    total = n + n_pad
    mask = jnp.concatenate([jnp.ones((n,), jnp.float32),
                            jnp.zeros((n_pad,), jnp.float32)])
    packed = [jnp.concatenate([v.astype(jnp.float32),
                               jnp.zeros((n_pad,), jnp.float32)]).reshape(-1, _LANES)
              for v in vecs]
    return packed, mask.reshape(-1, _LANES), total


# ---------------------------------------------------------------------------


def cd_tile_solve(G, g, h, beta_t, dbeta_t, mu, nu, lam1, lam2, *,
                  penf=None, backend=None):
    """Exact sequential tile solve; see kernels/cd_tile_solve.py.

    ``penf``: optional (T,) per-coordinate penalty factors — coordinate j is
    solved under (lam1·penf_j, lam2·penf_j); 0 = unpenalized (intercept).
    """
    record_launch("cd_tile_solve")
    if _resolve("cd_tile_solve", backend) == "ref":
        return ref.cd_tile_solve(G, g, h, beta_t, dbeta_t, mu, nu, lam1,
                                 lam2, penf=penf)
    if penf is None:
        penf = jnp.ones_like(g)
    return cd_tile_solve_pallas(G, g, h, beta_t, dbeta_t,
                                _params(mu, nu, lam1, lam2), penf,
                                interpret=_interpret())


def tile_gram(bricks, rows, n_valid, w2, r2, *, backend=None):
    """Brick-gather Gram/gradient for one feature tile (DESIGN.md §2).

    bricks (K, rb, T), rows (K,) i32, n_valid () i32, w2/r2
    (n_row_blocks, rb).  Returns (G (T, T), g (T,)); empty-brick slots are
    skipped (predicated off in the Pallas kernel).
    """
    record_launch("tile_gram")
    if _resolve("tile_gram", backend) == "ref":
        return ref.tile_gram(bricks, rows, n_valid, w2, r2)
    return tile_gram_pallas(bricks, rows, n_valid, w2, r2,
                            interpret=_interpret())


def _family_name(family):
    return family if isinstance(family, str) else family.name


def glm_stats(y, xb, family, *, weights=None, offset=None, backend=None,
              block_rows=256):
    """(loss_i, s_i, w_i) per example. 1-D in, 1-D out.

    ``weights`` is the combined per-example observation weight (sample
    weight × CV fold mask × row-padding mask — all the same multiply);
    ``offset`` shifts the margins (stats evaluated at ``xb + offset``).
    """
    record_launch("glm_stats")
    fname = _family_name(family)
    backend = _resolve("glm_stats", backend, fallback=_no_pallas_body(fname))
    n = y.shape[0]
    if weights is None:
        weights = jnp.ones((n,), jnp.float32)
    if backend == "ref":
        return ref.glm_stats(y, xb, weights, family, offset=offset)
    if offset is not None:
        xb = xb + offset              # fold the offset into the margins
    packed, pad_mask, _ = _pack_2d(y, xb, weights, block_rows=block_rows)
    y2, xb2, w_user = packed
    mask2 = w_user * pad_mask  # combine observation weights + padding mask
    loss2, s2, w2 = glm_stats_pallas(y2, xb2, mask2, family=fname,
                                     block_rows=block_rows,
                                     interpret=_interpret())
    flat = lambda a: a.reshape(-1)[:n]
    return flat(loss2), flat(s2), flat(w2)


def predict_tile(slots, vals, table, b0, family, *, kind="link",
                 backend=None, block_b=8):
    """Fused sparse scoring: gather + dot + inverse link in one launch.

    slots/vals: (B, J) padded request rows (slots index the compacted weight
    table; padding and inactive features point at the trailing all-zero
    row); table: (A+1, L) f32; b0: (L,) or (1, L) intercepts.  Returns
    (B, L) margins (``kind="link"``) or family responses (``"response"``).
    Families without a Pallas link body fall back to the jnp oracle, as
    does any non-TPU backend by default (kernels/predict_tile.py).
    """
    record_launch("predict_tile")
    fname = _family_name(family)
    backend = _resolve(
        "predict_tile", backend,
        fallback=None if fname in _PALLAS_LINKS
        else f"family {fname!r} has no Pallas link body")
    b0 = jnp.asarray(b0, jnp.float32).reshape(1, -1)
    if backend == "ref":
        return ref.predict_tile(slots, vals, table, b0, fname, kind=kind)
    # TPU tiling: the request block is ``block_b`` rows, the table's lanes
    # are padded to 128 and its rows to whole ``table_rows`` blocks (a
    # multiple of 8 sublanes) of at most _TABLE_BLOCK_BYTES.  Padding is inert by construction: extra
    # request rows point at the trailing all-zero row with value 0, extra
    # table rows/columns are 0.  Slots live in SMEM, so J needs no padding.
    B, J = slots.shape
    A1, L = table.shape
    zero_row = A1 - 1
    L_pad = L + (-L) % _LANES
    table_rows = min(A1 + (-A1) % 8,
                     max(8, _TABLE_BLOCK_BYTES // (4 * L_pad) // 8 * 8))
    pad_b = (-B) % block_b
    pad_a, pad_l = (-A1) % table_rows, L_pad - L
    if pad_b:
        slots = jnp.pad(slots, ((0, pad_b), (0, 0)),
                        constant_values=zero_row)
        vals = jnp.pad(vals, ((0, pad_b), (0, 0)))
    if pad_a or pad_l:
        table = jnp.pad(table, ((0, pad_a), (0, pad_l)))
    if pad_l:
        b0 = jnp.pad(b0, ((0, 0), (0, pad_l)))
    out = predict_tile_pallas(slots, vals, table, b0, family=fname,
                              kind=kind, block_b=block_b,
                              table_rows=table_rows, interpret=_interpret())
    return out[:B, :L]


# ---------------------------------------------------------------------------
# Fused superstep ops (DESIGN.md §8).  ``design`` is duck-typed to avoid a
# circular import with repro.data.design: DenseDesign exposes ``tiles3()``
# (tile-major (nt, n, T) operand), BlockSparseDesign exposes
# ``gather_all_tiles()`` (batched brick layout).
# ---------------------------------------------------------------------------


def _pad_rows(n_pad, *vecs):
    """Zero-extend (n,) row vectors to the design's padded row count (the
    padded rows carry observation weight 0, so they are inert)."""
    return [v if v is None or v.shape[0] == n_pad
            else jnp.pad(v, (0, n_pad - v.shape[0])) for v in vecs]


def _fused_fallback(design, fname):
    if not hasattr(design, "tiles3"):
        return ("fused superstep on a non-dense design composes the jnp "
                "oracle")
    return _no_pallas_body(fname)


def fused_gram_blocks_mirrored(design, family, backend=None):
    """Gram blocks per live tile that ``fused_stats_sweep`` on ``design``
    mirrors rather than contracts: the Pallas kernel's, none where the
    launch composes the jnp oracle."""
    if (backend or default_backend()) == "ref" or \
            _fused_fallback(design, _family_name(family)):
        return 0
    return gram_blocks_mirrored(design.tile_size)


def _params(mu, nu, lam1, lam2):
    return jnp.stack([jnp.asarray(v, jnp.float32)
                      for v in (mu, nu, lam1, lam2)])


def fused_stats_sweep(design, y, xb, beta, family, *, mu, nu, lam1, lam2,
                      weights=None, offset=None, penf=None, tile_live=None,
                      precision="fp32", backend=None):
    """Fused launch 1 of the superstep: link stats + every tile's Gram and
    gradient + the per-tile Jacobi CD solve, in one pass over the rows.

    Returns (loss_i, s, w, dbeta (p,), G_all (nt, T, T), g_all (nt, T)).
    ``tile_live`` (nt,) bool shapes the launch to the active set: dead tiles
    cost no Gram/solve work and get dbeta = 0; their G_all/g_all rows are
    unspecified (zero on shaped paths, possibly populated on the unshaped
    fallback) — callers must not read them.

    Backend choice: the Pallas two-launch pipeline needs the dense
    tile-major layout, whose rows ``dense_design`` already padded to a
    multiple of ``DENSE_ROW_BLOCK``; BlockSparseDesign and non-TPU backends use the
    jnp oracle composition in ref.py (same batched-matmul shaping, same
    active-set compaction, XLA-fused on CPU).
    """
    record_launch("fused_stats_sweep")
    fname = _family_name(family)
    backend = _resolve("fused_stats_sweep", backend,
                       fallback=_fused_fallback(design, fname))
    n = y.shape[0]
    T = design.tile_size
    nt = beta.shape[0] // T
    if weights is None:
        weights = jnp.ones((n,), jnp.float32)
    penf_r = (jnp.ones((nt, T), jnp.float32) if penf is None
              else penf.reshape(nt, T))
    beta_r = beta.reshape(nt, T)

    if backend == "ref":
        if hasattr(design, "tiles3"):
            Xt3 = design.tiles3()
            y_p, xb_p, w_p, off_p = _pad_rows(Xt3.shape[1], y, xb, weights,
                                              offset)
            loss_i, s, w, G_all, g_all = ref.fused_stats_gram_dense(
                Xt3, y_p, xb_p, w_p, fname, offset=off_p,
                tile_live=tile_live, precision=precision)
            loss_i, s, w = loss_i[:n], s[:n], w[:n]
        elif hasattr(design, "gather_all_tiles"):
            b3, rows, valid = design.gather_all_tiles()
            loss_i, s, w, G_all, g_all = ref.fused_stats_gram_bricks(
                b3, rows, valid, y, xb, weights, fname, offset=offset,
                tile_live=tile_live, precision=precision)
        else:
            loss_i, s, w = ref.glm_stats(y, xb, weights, fname,
                                         offset=offset)
            G_all, g_all = design.all_tile_grams(w, s, backend="ref")
        h_all = jnp.diagonal(G_all, axis1=1, axis2=2)
        solve = jax.vmap(lambda Gt, gt, ht, bt, pt: ref.cd_tile_solve(
            Gt, gt, ht, bt, jnp.zeros_like(gt), mu, nu, lam1, lam2, penf=pt))
        dbeta_r = solve(G_all, g_all, h_all, beta_r, penf_r)
    else:
        Xt3 = design.tiles3()
        if offset is not None:
            xb = xb + offset
        (y2, xb2, w_user), pad_mask = _pack_rows(Xt3, y, xb, weights)
        if tile_live is None:
            sel = jnp.concatenate([jnp.arange(nt, dtype=jnp.int32),
                                   jnp.full((1,), nt, jnp.int32)])
        else:
            live_i = tile_live.astype(jnp.int32)
            order = jnp.argsort(1 - live_i, stable=True).astype(jnp.int32)
            sel = jnp.concatenate([order, jnp.sum(live_i)[None]])
        loss2, s2, w2, G_all, g_all, dbeta_r = stats_gram_solve_pallas(
            sel, Xt3, y2, xb2, w_user * pad_mask, beta_r, penf_r,
            _params(mu, nu, lam1, lam2), family=fname,
            block_n=DENSE_ROW_BLOCK, precision=precision,
            interpret=_interpret())
        flat = lambda a: a.reshape(-1)[:n]
        loss_i, s, w = flat(loss2), flat(s2), flat(w2)
    if tile_live is not None:
        dbeta_r = jnp.where(tile_live[:, None], dbeta_r, 0.0)
    return loss_i, s, w, dbeta_r.reshape(-1), G_all, g_all


def _pack_rows(Xt3, *vecs):
    """Pack row vectors for the fused kernels: (R, 128) blocks plus the
    padding mask, with R·128 equal to the tile-major operand's row count
    (padded once, by ``data.design.dense_design``)."""
    packed, pad_mask, total = _pack_2d(
        *vecs, block_rows=DENSE_ROW_BLOCK // _LANES)
    if total != Xt3.shape[1]:
        raise ValueError(
            f"tile-major operand has {Xt3.shape[1]} rows; the fused kernels "
            f"need {total} ({vecs[0].shape[0]} rows padded to "
            f"{DENSE_ROW_BLOCK}) — build the design with "
            "data.design.dense_design")
    return packed, pad_mask


def fused_ls(design, y, xb, dbeta, alphas, family, *, weights=None,
             offset=None, precision="fp32", backend=None, xdb_base=None,
             relative=False):
    """Fused launch 2 of the superstep: margin delta xdb = X·Δβ plus every
    line-search candidate's loss in one pass.  Returns (xdb (n,),
    losses (K,)).  ``xdb_base`` (n,), when given, is a margin delta made
    elsewhere (a head/tail design's tail) that the launch adds to X·Δβ
    before it scores the candidates; ``relative`` makes each candidate's
    number its CHANGE of the loss, summed row by row.  Non-dense designs
    and non-TPU backends compose the design's matvec with the alpha_search
    oracle instead (the margin vector round-trips once, which XLA fusion
    absorbs on CPU)."""
    record_launch("fused_ls")
    fname = _family_name(family)
    backend = _resolve("fused_ls", backend,
                       fallback=_fused_fallback(design, fname))
    n = y.shape[0]
    if weights is None:
        weights = jnp.ones((n,), jnp.float32)
    if backend == "ref":
        if hasattr(design, "tiles3"):
            Xt3 = design.tiles3()
            y_p, xb_p, w_p, off_p, base_p = _pad_rows(
                Xt3.shape[1], y, xb, weights, offset, xdb_base)
            xdb, losses = ref.fused_ls_dense(
                Xt3, y_p, xb_p, dbeta, w_p, alphas, fname, offset=off_p,
                precision=precision, xdb_base=base_p, relative=relative)
            xdb = xdb[:n]
        else:
            xdb = design.matvec(dbeta)
            if xdb_base is not None:
                xdb = xdb + xdb_base
            losses = ref.alpha_search(y, xb, xdb, weights, alphas, fname,
                                      offset=offset, relative=relative)
        return xdb, losses
    Xt3 = design.tiles3()
    T = design.tile_size
    nt = dbeta.shape[0] // T
    if offset is not None:
        xb = xb + offset
    if xdb_base is None:
        (y2, xb2, w_user), pad_mask = _pack_rows(Xt3, y, xb, weights)
        base = ()
    else:
        (y2, xb2, w_user, base2), pad_mask = _pack_rows(Xt3, y, xb, weights,
                                                        xdb_base)
        base = (base2,)
    xdb2, losses = margin_ls_pallas(
        Xt3, dbeta.reshape(nt, T), y2, xb2, w_user * pad_mask, alphas,
        *base, family=fname, block_n=DENSE_ROW_BLOCK, precision=precision,
        relative=relative, interpret=_interpret())
    return xdb2.reshape(-1)[:n], losses


def alpha_search(y, xb, xdb, alphas, family, *, weights=None, offset=None,
                 backend=None, block_rows=256):
    """losses[k] = sum_i weights_i * l(y_i, xb_i + o_i + alphas[k]*xdb_i)."""
    record_launch("alpha_search")
    fname = _family_name(family)
    backend = _resolve("alpha_search", backend,
                       fallback=_no_pallas_body(fname))
    n = y.shape[0]
    if weights is None:
        weights = jnp.ones((n,), jnp.float32)
    if backend == "ref":
        return ref.alpha_search(y, xb, xdb, weights, alphas, family,
                                offset=offset)
    if offset is not None:
        xb = xb + offset
    packed, pad_mask, _ = _pack_2d(y, xb, xdb, weights,
                                   block_rows=block_rows)
    y2, xb2, xdb2, w2 = packed
    mask2 = w2 * pad_mask
    return alpha_search_pallas(y2, xb2, xdb2, mask2, alphas, family=fname,
                               block_rows=block_rows, interpret=_interpret())
