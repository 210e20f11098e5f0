"""Pallas TPU kernel: K-candidate line-search objective sweep.

Evaluates losses[k] = sum_i l(y_i, xb_i + alpha_k * xdb_i) for a whole grid
of step sizes in ONE streaming pass over the examples.  The d-GLMNET line
search (Algorithm 3) needs f(beta + alpha*dbeta) at the alpha_init pre-search
grid and at every Armijo backtracking candidate; evaluating them together
turns O(K) HBM sweeps of the margin vectors into one.

Grid iterates over example blocks; the (1, K) output block is revisited by
every grid step and accumulated in VMEM (initialized at step 0).  The step
sizes sit in SMEM (a scalar read per candidate), and candidate k's loss sum
lands in lane k of the output row through a lane mask — Mosaic indexes
neither in-register vectors nor VMEM lanes dynamically.

This kernel reduces each candidate's loss to a scalar in every block.  The
fused superstep's ``superstep_tile.margin_ls_pallas`` does not: its
1024-row blocks are one (8, 128) vreg, so a reduction per candidate per
block was latency-bound, and it accumulates element-wise instead.  Here a
block is 256 × 128 (32 vregs per reduction), the reduction weighs 32 times
less, and this kernel serves only the unfused path (sparse bricks, sharded
meshes, non-Jacobi coupling).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.glm_stats import _STATS


def candidate_losses(alphas_ref, y, xb, xdb, mask, *, family):
    """(1, K) row: lane k holds Σ mask·l(y, xb + alphas[k]·xdb) over this
    block.  ``alphas_ref`` is the (K,) SMEM candidate array."""
    K = alphas_ref.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, K), 1)

    def per_alpha(k, acc):
        loss, _, _ = _STATS[family](y, xb + alphas_ref[k] * xdb)
        tot = jnp.sum(loss * mask, axis=(0, 1), keepdims=True)     # (1, 1)
        return acc + jnp.where(lane == k, tot, 0.0)

    return jax.lax.fori_loop(0, K, per_alpha, jnp.zeros((1, K), jnp.float32))


def _kernel(alphas_ref, y_ref, xb_ref, xdb_ref, mask_ref, out_ref, *,
            family):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += candidate_losses(alphas_ref, y_ref[...], xb_ref[...],
                                     xdb_ref[...], mask_ref[...],
                                     family=family)


@functools.partial(jax.jit, static_argnames=("family", "block_rows", "interpret"))
def alpha_search_pallas(y2, xb2, xdb2, mask2, alphas, *, family,
                        block_rows=256, interpret=True):
    """y2/xb2/xdb2/mask2: (R, 128); alphas: (K,). Returns (K,) losses."""
    R, C = y2.shape
    K = alphas.shape[0]
    grid = (R // block_rows,)
    dspec = pl.BlockSpec((block_rows, C), lambda i: (i, 0))
    f32 = jnp.float32
    out = pl.pallas_call(
        functools.partial(_kernel, family=family),
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  dspec, dspec, dspec, dspec],
        out_specs=pl.BlockSpec((1, K), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, K), f32),
        interpret=interpret,
    )(alphas.astype(f32), y2.astype(f32), xb2.astype(f32), xdb2.astype(f32),
      mask2.astype(f32))
    return out[0]
