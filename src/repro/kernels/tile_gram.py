"""Pallas TPU kernel: brick-gather Gram/gradient for one feature tile.

Input is the CSR-of-bricks layout of DESIGN.md §2 after the per-tile gather:
``bricks`` holds the (≤ K = max_bricks_per_tile) non-empty (row_block × T)
bricks of one feature tile, ``rows`` their row-block indices.  The kernel
accumulates

    G = Σ_k  b_kᵀ diag(w[rows[k]]) b_k        (T, T)
    g = Σ_k  b_kᵀ r[rows[k]]                  (T,)

over the brick list in VMEM.  Two things make this a kernel rather than a
jnp loop:

  * the row-block indices are **scalar-prefetched**: the BlockSpec index maps
    read ``rows[k]`` before grid step k runs, so the DMA engine fetches
    exactly the needed (1, row_block) row of w and r per brick — a gather
    expressed as block addressing, with no host-side densification;
  * empty-brick slots (k ≥ n_valid — every SPMD peer runs the same static K)
    are predicated off with ``pl.when`` and re-address the last live brick:
    no MXU work and no DMA is issued for them, so compute and traffic scale
    with the tile's actual brick population, i.e. with nnz structure rather
    than n·p.

VMEM footprint: K is only a grid bound — resident per step is one brick
(rb·T), two (1, rb) vectors, and the (T², T) accumulators.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HIGHEST = jax.lax.Precision.HIGHEST


def _kernel(scal_ref, brick_ref, w_ref, r_ref, G_ref, g_ref):
    k = pl.program_id(0)
    n_valid = scal_ref[0]

    @pl.when(k == 0)
    def _init():
        G_ref[...] = jnp.zeros_like(G_ref)
        g_ref[...] = jnp.zeros_like(g_ref)

    @pl.when(k < n_valid)
    def _accumulate():
        b = brick_ref[0]          # (rb, T)
        wv = w_ref[0]             # (1, rb) lane row
        rv = r_ref[0]             # (1, rb)
        # (T, T) += bᵀ diag(w) b: the row weights scale the lanes of bᵀ
        G_ref[...] += jnp.dot(b.T * wv, b, precision=_HIGHEST,
                              preferred_element_type=jnp.float32)
        g_ref[...] += jnp.dot(rv, b, precision=_HIGHEST,
                              preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def tile_gram_pallas(bricks, rows, n_valid, w2, r2, *, interpret=True):
    """bricks (K, rb, T) f32; rows (K,) i32 row-block ids; n_valid () i32;
    w2, r2 (n_row_blocks, rb) f32.  Returns (G (T, T), g (T,))."""
    K, rb, T = bricks.shape
    n_rb = w2.shape[0]
    scal = jnp.concatenate([jnp.asarray(n_valid, jnp.int32).reshape(1),
                            rows.astype(jnp.int32)])
    # an empty slot (k >= n_valid) re-addresses the last live brick, so
    # the pipeline issues no DMA for it
    live_k = lambda k, s: jnp.maximum(jnp.minimum(k, s[0] - 1), 0)
    # w/r as (n_rb, 1, rb): one row block is a (1, rb) block spanning the
    # array's last two dims, the only row-vector block Mosaic tiles
    vspec = pl.BlockSpec((1, 1, rb), lambda k, s: (s[1 + live_k(k, s)], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(K,),
        in_specs=[
            pl.BlockSpec((1, rb, T), lambda k, s: (live_k(k, s), 0, 0)),
            vspec, vspec,
        ],
        out_specs=[
            pl.BlockSpec((T, T), lambda k, s: (0, 0)),
            pl.BlockSpec((1, T), lambda k, s: (0, 0)),
        ],
    )
    G, g = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((T, T), jnp.float32),
                   jax.ShapeDtypeStruct((1, T), jnp.float32)],
        interpret=interpret,
    )(scal, bricks.astype(jnp.float32),
      w2.astype(jnp.float32).reshape(n_rb, 1, rb),
      r2.astype(jnp.float32).reshape(n_rb, 1, rb))
    return G, g[0]
