"""Pallas TPU kernels: the fused d-GLMNET superstep fast path (DESIGN.md §8).

One outer iteration of Algorithm 4 is, unfused, a chain of 4+ launches with
full (n,)-vector HBM round-trips between them:

    glm_stats -> per-tile Gram/grad -> cd_tile_solve -> matvec -> alpha_search

The two kernels here collapse that chain to TWO launches:

* ``stats_gram_solve_pallas`` — grid ``(nt, nb)`` (tile-major).  For each
  live tile t it streams the row blocks of the tile-major operand
  ``Xt3 (nt, n, T)`` once, recomputing the link stats (loss_i, s, w) on the
  VPU per row block (idempotent (R,128) writes — stats are tile-independent,
  so every tile writes the same values) and accumulating the T×T Gram block
  and T-gradient in VMEM; at the tile's last row block it runs the
  sequential soft-threshold solve (same chain as cd_tile_solve.py) on the
  VMEM-resident Gram.  ``s`` and ``w`` never round-trip HBM between the
  stats and the Gram pass.  The Gram Xᵀ diag(w) X is symmetric, so each
  row block contracts only its upper block triangle: 128-column block b
  of X against the first (b + 1)·128 rows of the weighted Xᵀ, 3 of 4
  blocks' work at T = 256 and 10 of 16 at T = 512 (a tile 128 wide, or
  of a width 128 does not divide, is one block).  Before the chain, the
  tile's last row block writes each lower block as its mirror's
  transpose and keeps each diagonal block's upper triangle, so the chain
  and ``G_all`` see an exactly symmetric Gram.  At ``Precision.HIGHEST``
  the v5e MXU runs an f32 contraction as six bf16 passes, so the Gram's
  products, not the X stream, bound this kernel.

* ``margin_ls_pallas`` — grid ``(nb, nt)`` (row-major).  For each row block
  it accumulates the margin delta xdb = X·Δβ over tiles in a VMEM-resident
  block (from an optional additive base: the sparse tail's delta of a
  head/tail design), and at the last tile evaluates every line-search
  candidate's loss against that block — xdb never round-trips HBM between the margin apply
  and the candidate sweep.  The candidate losses accumulate element-wise:
  a VMEM scratch holds one (8, 128) tile per candidate (a sublane-reduced
  row past ``_LS_ACC_BUDGET``), each row block adds its masked losses into
  candidate k's tile with no cross-lane work, and
  the launch's last grid step reduces each tile once into lane k of the
  (1, K) output.  A per-block reduction per candidate (391 × 294 of them a
  superstep at 400,000 rows) is latency-bound and cost 4× the X read.

Active-set shaping (tentpole b): the first kernel takes a scalar-prefetch
remap ``sel = [live-first tile order..., n_live]``; grid steps with
``t >= n_live`` are predicated off entirely, so tiles whose coordinates are
all screened out cost no Gram/solve work — screening buys wall-clock, not
just FLOP count.  Dead tiles' G/g/Δβ outputs are written as zeros (the
caller masks Δβ by tile liveness regardless).

Mixed precision (tentpole c): ``precision="bf16"`` casts the Gram/margin
matmul INPUTS to bf16 with f32 accumulation (``preferred_element_type``);
the link stats, the solve chain, and the Armijo loss sums stay f32.
Mosaic lowers only DEFAULT and HIGHEST dot precision (HIGH raises), so
these two are the modes there are.

Shapes follow ops._pack_2d: vectors as (R, 128) with a mask folding weights
and padding; rows are padded to a multiple of ``block_n`` examples (1024 by
default, so a row block's vectors are one (8, 128) tile).  Inside a block,
example ``r·128 + l`` sits at (r, l) of the vector tile and at row
``r·128 + l`` of the design block; ``_lane_row`` lines the two up.  As with
the other kernels in this package, CPU/GPU runs use interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.cd_tile_solve import solve_chain
from repro.kernels.glm_stats import _LOSS, _STATS

MU, NU, LAM1, LAM2 = 0, 1, 2, 3  # params (4,) SMEM layout, as cd_tile_solve
_HIGHEST = jax.lax.Precision.HIGHEST
_MXU = 128             # the MXU's width: the Gram's square block
# scoped VMEM of the Gram kernel: at T = 512 its double-buffered design
# block, Gram block and the transposed operand need ~20 MiB, above Mosaic's
# 16 MiB default (a v5e core has 128 MiB of VMEM)
_GRAM_VMEM_LIMIT = 48 << 20
# the line search's candidate-loss accumulator holds a row block's whole
# (8, 128) tile per candidate up to this size (1.2 MB at the default 294
# candidates), else one sublane-reduced row per candidate
_LS_ACC_BUDGET = 64 << 20
_LS_UNROLL = 16        # candidates per iteration of the accumulation loop


def _matmul_inputs(precision, *xs):
    """Matmul operands and dot precision for the requested mode: bf16
    inputs (f32 accumulation) or full-f32 contraction."""
    if precision == "bf16":
        return tuple(x.astype(jnp.bfloat16) for x in xs), None
    return xs, _HIGHEST


def _lane_row(v):
    """(R, 128) → (1, R·128): row-major lane concatenation of the rows —
    example ``r·128 + l`` of the block lands in lane ``r·128 + l``."""
    return jnp.concatenate([v[r:r + 1, :] for r in range(v.shape[0])], axis=1)


def _gram_block(T):
    """Width of the Gram's square blocks: the MXU's 128 where it divides
    the tile, else the whole tile (one block, nothing to mirror)."""
    return _MXU if T % _MXU == 0 else T


def gram_blocks_mirrored(T):
    """Blocks of one tile's Gram that ``stats_gram_solve_pallas`` mirrors
    rather than contracts: the b(b − 1)/2 below the diagonal, for b
    blocks across the tile."""
    b = T // _gram_block(T)
    return b * (b - 1) // 2


def _mirror_upper(G_ref, c):
    """Complete the tile's Gram from its upper block triangle: each block
    below the diagonal is its mirror's transpose, and each diagonal block
    keeps its upper triangle, so the Gram is exactly symmetric."""
    T = G_ref.shape[-1]
    ii = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    for b in range(T // c):
        rows = slice(b * c, (b + 1) * c)
        D = G_ref[0, rows, rows]
        G_ref[0, rows, rows] = jnp.where(ii <= jj, D, D.T)
        for a in range(b):
            G_ref[0, rows, a * c:(a + 1) * c] = \
                G_ref[0, a * c:(a + 1) * c, rows].T


def _stats_gram_solve_kernel(sel_ref, Xt_ref, y_ref, xb_ref, mask_ref,
                             beta_ref, penf_ref, params_ref,
                             loss_ref, s_ref, w_ref, G_ref, g_ref, dbeta_ref,
                             *, family, precision):
    t = pl.program_id(0)
    i = pl.program_id(1)
    nb = pl.num_programs(1)
    n_live = sel_ref[sel_ref.shape[0] - 1]
    live = t < n_live

    # link stats for this row block — pure VPU, recomputed per (t, i) step so
    # s/w stay VMEM-resident for the Gram accumulation below; the (R, 128)
    # writes are idempotent across tiles (stats don't depend on t)
    loss, s, w = _STATS[family](y_ref[...], xb_ref[...])
    mask = mask_ref[...]
    loss_ref[...] = loss * mask
    s = s * mask
    w = w * mask
    s_ref[...] = s
    w_ref[...] = w

    @pl.when(i == 0)
    def _init():
        G_ref[...] = jnp.zeros_like(G_ref)
        g_ref[...] = jnp.zeros_like(g_ref)

    T = G_ref.shape[-1]
    c = _gram_block(T)

    @pl.when(live)
    def _accumulate():
        X = Xt_ref[0]                      # (block_n, T)
        # the row weights scale the lanes of Xᵀ: G += Xᵀ diag(w) X, by
        # column blocks, each against the rows of Xᵀ up to its diagonal
        # block: only the upper block triangle is contracted
        (wXt, Xc, sv), prec = _matmul_inputs(
            precision, X.T * _lane_row(w), X, _lane_row(s))
        for b in range(T // c):
            hi = (b + 1) * c
            G_ref[0, :hi, b * c:hi] += jnp.dot(
                wXt[:hi], Xc[:, b * c:hi], precision=prec,
                preferred_element_type=jnp.float32)
        g_ref[0] += jnp.dot(sv, Xc, precision=prec,
                            preferred_element_type=jnp.float32)

    @pl.when(i == nb - 1)
    def _solve():
        _mirror_upper(G_ref, c)
        ii = jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
        jj = jax.lax.broadcasted_iota(jnp.int32, (T, T), 1)
        h = jnp.sum(jnp.where(ii == jj, G_ref[0], 0.0), axis=0,
                    keepdims=True)                               # (1, T)
        # Jacobi across tiles: every tile's chain starts from dbeta = 0
        d = solve_chain(
            lambda j: G_ref[0, pl.ds(j, 1), :], g_ref[0], h, beta_ref[0],
            penf_ref[0], jnp.zeros_like(h), params_ref[MU], params_ref[NU],
            params_ref[LAM1], params_ref[LAM2])
        dbeta_ref[0] = jnp.where(live, d, 0.0)


@functools.partial(jax.jit, static_argnames=("family", "block_n", "precision",
                                             "interpret"))
def stats_gram_solve_pallas(sel, Xt3, y2, xb2, mask2, beta_r, penf_r, params,
                            *, family, block_n=1024, precision="fp32",
                            interpret=True):
    """Fused launch 1 of the superstep: stats + Gram + tile solve.

    sel: (nt + 1,) i32 — live-first tile order then n_live (active-set remap).
    Xt3: (nt, n_pad, T) tile-major operand, n_pad % block_n == 0,
    block_n % 1024 == 0 (the (8, 128) vector tile).
    y2/xb2/mask2: (R, 128) packed vectors, R * 128 == n_pad.
    beta_r/penf_r: (nt, T); params: (4,) f32 [mu, nu, lam1, lam2].
    Returns (loss2, s2, w2, G_all (nt,T,T), g_all (nt,T), dbeta_r (nt,T)).
    """
    nt, n_pad, T = Xt3.shape
    nb = n_pad // block_n
    br = block_n // 128
    R, C = y2.shape
    f32 = jnp.float32
    # index maps receive the grid indices first, then the prefetch ref.
    # Per-tile rows travel as (nt, 1, T) arrays: a (1, T) block spanning the
    # last two dims is the only row-vector block Mosaic tiles.  A dead tile
    # (t >= n_live) pins its design block to row block 0, so its predicated-
    # off steps issue no new DMA.
    vspec = pl.BlockSpec((br, C), lambda t, i, s: (i, 0))
    tspec = pl.BlockSpec((1, 1, T), lambda t, i, s: (s[t], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nt, nb),
        in_specs=[
            pl.BlockSpec((1, block_n, T), lambda t, i, s: (
                s[t], jnp.where(t < s[nt], i, 0), 0)),
            vspec, vspec, vspec,
            tspec, tspec,
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            vspec, vspec, vspec,
            pl.BlockSpec((1, T, T), lambda t, i, s: (s[t], 0, 0)),
            tspec, tspec,
        ],
    )
    out_shape = [
        jax.ShapeDtypeStruct((R, C), f32),
        jax.ShapeDtypeStruct((R, C), f32),
        jax.ShapeDtypeStruct((R, C), f32),
        jax.ShapeDtypeStruct((nt, T, T), f32),
        jax.ShapeDtypeStruct((nt, 1, T), f32),
        jax.ShapeDtypeStruct((nt, 1, T), f32),
    ]
    loss2, s2, w2, G_all, g_all, dbeta_r = pl.pallas_call(
        functools.partial(_stats_gram_solve_kernel, family=family,
                          precision=precision),
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_GRAM_VMEM_LIMIT),
        interpret=interpret,
    )(sel.astype(jnp.int32), Xt3.astype(f32), y2.astype(f32),
      xb2.astype(f32), mask2.astype(f32),
      beta_r.astype(f32).reshape(nt, 1, T),
      penf_r.astype(f32).reshape(nt, 1, T), params.astype(f32))
    return loss2, s2, w2, G_all, g_all[:, 0], dbeta_r[:, 0]


def _ls_acc_rows(K, br):
    """Sublane rows of the candidate-loss accumulator: a row block's whole
    (br, 128) tile per candidate while K of them fit ``_LS_ACC_BUDGET``,
    else one sublane-reduced row per candidate."""
    return br if K * br * 128 * 4 <= _LS_ACC_BUDGET else 1


def _accumulate_candidates(alphas_ref, acc_ref, y, xb, xdb, mask, *, family,
                           relative=False):
    """acc[k] += mask · l(y, xb + alphas[k]·xdb) for every candidate k:
    element-wise, no cross-lane reduction.  ``_LS_UNROLL`` candidates per
    loop iteration, so one candidate's exp/log overlaps the next's.  With
    ``relative`` each row adds its loss CHANGE, less mask · l(y, xb): the
    sums are then the candidates' changes of the loss, rounded at their
    own size rather than at the whole loss's."""
    loss = _LOSS[family]
    K, rows = alphas_ref.shape[0], acc_ref.shape[1]
    base = mask * loss(y, xb) if relative else None

    def one(k):
        lk = mask * loss(y, xb + alphas_ref[k] * xdb)
        if base is not None:
            lk = lk - base
        if rows != lk.shape[0]:
            lk = jnp.sum(lk, axis=0, keepdims=True)
        acc_ref[k] += lk

    def chunk(c, carry):
        for u in range(_LS_UNROLL):
            one(c * _LS_UNROLL + u)
        return carry

    jax.lax.fori_loop(0, K // _LS_UNROLL, chunk, 0)
    for k in range(K - K % _LS_UNROLL, K):
        one(k)


def _margin_ls_kernel(alphas_ref, Xt_ref, db_ref, y_ref, xb_ref, mask_ref,
                      *refs, family, precision, relative):
    """Grid step (i, t): add tile t's share of row block i's margin delta
    to xdb, which starts from zero or, with the optional base operand
    (``refs`` then leads with it), from the base's block; at the block's
    last tile add every candidate's masked loss element-wise into
    ``acc_ref`` (K, rows, 128), zeroed at step (0, 0); at the launch's
    last step reduce ``acc_ref`` into the (1, K) output, which is written
    only there."""
    base_ref = refs[0] if len(refs) == 4 else None
    xdb_ref, out_ref, acc_ref = refs[-3:]
    i = pl.program_id(0)
    t = pl.program_id(1)
    nb = pl.num_programs(0)
    nt = pl.num_programs(1)

    @pl.when((i == 0) & (t == 0))
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(t == 0)
    def _init_xdb():
        xdb_ref[...] = jnp.zeros_like(xdb_ref) if base_ref is None \
            else base_ref[...]

    # (1, T) · (block_n, T)ᵀ → (1, block_n): the margin delta as one lane
    # row, folded back into the (R, 128) vector block row by row
    (d, X), prec = _matmul_inputs(precision, db_ref[0], Xt_ref[0])
    contrib = jax.lax.dot_general(d, X, (((1,), (1,)), ((), ())),
                                  precision=prec,
                                  preferred_element_type=jnp.float32)
    for r in range(xdb_ref.shape[0]):
        xdb_ref[r:r + 1, :] += contrib[:, r * 128:(r + 1) * 128]

    @pl.when(t == nt - 1)
    def _linesearch():
        _accumulate_candidates(alphas_ref, acc_ref, y_ref[...], xb_ref[...],
                               xdb_ref[...], mask_ref[...], family=family,
                               relative=relative)

    # one cross-lane reduction per candidate for the whole launch: sublanes
    # on the VPU, then lanes on the MXU, as ones(1, 128) · sumsᵀ, which lays
    # candidate k's total in lane k of the (1, K) output row
    @pl.when((i == nb - 1) & (t == nt - 1))
    def _reduce():
        sums = jnp.sum(acc_ref[...], axis=1)                     # (K, 128)
        out_ref[...] = jax.lax.dot_general(
            jnp.ones((1, sums.shape[1]), jnp.float32), sums,
            (((1,), (1,)), ((), ())), precision=_HIGHEST,
            preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("family", "block_n", "precision",
                                             "relative", "interpret"))
def margin_ls_pallas(Xt3, dbeta_r, y2, xb2, mask2, alphas, base2=None, *,
                     family, block_n=1024, precision="fp32", relative=False,
                     interpret=True):
    """Fused launch 2 of the superstep: margin delta + candidate loss sweep.

    Xt3: (nt, n_pad, T); dbeta_r: (nt, T); y2/xb2/mask2: (R, 128) with
    R * 128 == n_pad; alphas: (K,) candidate step sizes; base2: optional
    (R, 128) margin delta added to X·Δβ before the candidates are scored
    (a seventh operand; without it the launch has six).  ``relative``
    returns each candidate's change of the loss (``_accumulate_candidates``).
    Returns (xdb2 (R, 128), losses (K,)).
    """
    nt, n_pad, T = Xt3.shape
    nb = n_pad // block_n
    br = block_n // 128
    R, C = y2.shape
    K = alphas.shape[0]
    f32 = jnp.float32
    rows = _ls_acc_rows(K, br)
    # scoped VMEM from the shapes: the double-buffered design block, its
    # transposed or bf16 copy, the accumulator, and room for the rest
    vmem = 3 * block_n * T * 4 + K * rows * 128 * 4 + (8 << 20)
    vspec = pl.BlockSpec((br, C), lambda i, t: (i, 0))
    out = pl.pallas_call(
        functools.partial(_margin_ls_kernel, family=family,
                          precision=precision, relative=relative),
        grid=(nb, nt),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_n, T), lambda i, t: (t, i, 0)),
            pl.BlockSpec((1, 1, T), lambda i, t: (t, 0, 0)),
            vspec, vspec, vspec,
        ] + ([] if base2 is None else [vspec]),
        out_specs=[vspec, pl.BlockSpec((1, K), lambda i, t: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((R, C), f32),
                   jax.ShapeDtypeStruct((1, K), f32)],
        scratch_shapes=[pltpu.VMEM((K, rows, C), f32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=max(vmem, 16 << 20)),
        interpret=interpret,
    )(alphas.astype(f32), Xt3.astype(f32),
      dbeta_r.astype(f32).reshape(nt, 1, T), y2.astype(f32),
      xb2.astype(f32), mask2.astype(f32),
      *(() if base2 is None else (base2.astype(f32),)))
    return out[0], out[1][0]
