"""Pallas TPU kernel: fused sparse scoring (gather + dot + link).

The serving hot path (DESIGN.md §7) scores SPARSE feature-list requests
against an active-set-compacted weight table: request row b carries
``nnz_max`` (slot, value) pairs where ``slot`` indexes the compacted table
(inactive / padding features point at a trailing all-zero row), and the
engine wants, per request and per output column l (one column per served
λ / model),

    margin[b, l] = Σ_j vals[b, j] · table[slots[b, j], l] + intercept[l]
    out[b, l]    = link(margin[b, l])            (kind = "response")

Fusing the gather, the dot and the inverse link into ONE kernel launch is
what keeps a micro-batched request batch at a single device round-trip:
three HBM sweeps (gather rows, accumulate, elementwise link) collapse into
one pass where each gathered table row is consumed from VMEM immediately.

Layout: requests stream through the grid in ``block_b``-row blocks whose
(slot, value) pairs sit in SMEM, so each gathered table row is a scalar
slot read followed by a dynamic sublane load from VMEM — Mosaic has no
vector gather.  The compacted table is tiled along its rows
(``table_rows`` per block, a second "arbitrary" grid axis): the active set
of an L1-regularized model is small by construction, so one block usually
holds it all, and a larger one streams through in blocks while each
request row accumulates the slots that fall inside the current block.

``ops.predict_tile`` wraps this with padding and dispatches to the
pure-jnp oracle (``ref.predict_tile``) on backends without Pallas support —
the kernel and the oracle are asserted to agree to ≤ 1e-5 on every family
(tests/test_serve.py, benchmarks/serving_bench.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_SQRT2 = 1.4142135623730951

# inverse links (margin -> family response); erfc-based probit matches the
# glm_stats kernel's tail-safe formulation
_LINKS = {
    "logistic": lambda m: jax.nn.sigmoid(m),
    "squared": lambda m: m,
    "probit": lambda m: 0.5 * jax.lax.erfc(-m / _SQRT2),
    "poisson": lambda m: jnp.exp(m),
}


def _kernel(slots_ref, vals_ref, table_ref, b0_ref, out_ref, *,
            family, kind, nnz):
    t = pl.program_id(1)
    a_blk, L = table_ref.shape
    lo = t * a_blk

    @pl.when(t == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    def per_row(b, carry):
        def per_slot(j, acc):
            local = slots_ref[b, j] - lo
            inside = (local >= 0) & (local < a_blk)
            row = table_ref[pl.ds(jnp.where(inside, local, 0), 1), :]
            return acc + jnp.where(inside, vals_ref[b, j], 0.0) * row

        acc = jax.lax.fori_loop(0, nnz, per_slot,
                                jnp.zeros((1, L), jnp.float32))
        out_ref[pl.ds(b, 1), :] += acc
        return carry

    jax.lax.fori_loop(0, out_ref.shape[0], per_row, 0)

    @pl.when(t == pl.num_programs(1) - 1)
    def _link():
        m = out_ref[...] + b0_ref[...]      # (1, L) intercept broadcast
        out_ref[...] = _LINKS[family](m) if kind == "response" else m


@functools.partial(jax.jit, static_argnames=("family", "kind", "block_b",
                                             "table_rows", "interpret"))
def predict_tile_pallas(slots, vals, table, b0, *, family, kind="link",
                        block_b=8, table_rows=None, interpret=True):
    """slots/vals: (B, J) with B % block_b == 0; table: (A1, L) f32 whose
    LAST row is all-zero (the padding target), A1 % table_rows == 0;
    b0: (1, L).  Returns (B, L) margins (``kind="link"``) or family
    responses (``kind="response"``)."""
    B, J = slots.shape
    A1, L = table.shape
    table_rows = A1 if table_rows is None else table_rows
    req_spec = pl.BlockSpec((block_b, J), lambda i, t: (i, 0),
                            memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(_kernel, family=family, kind=kind, nnz=J),
        grid=(B // block_b, A1 // table_rows),
        in_specs=[req_spec, req_spec,
                  pl.BlockSpec((table_rows, L), lambda i, t: (t, 0)),
                  pl.BlockSpec((1, L), lambda i, t: (0, 0))],
        out_specs=pl.BlockSpec((block_b, L), lambda i, t: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, L), jnp.float32),
        interpret=interpret,
    )(slots.astype(jnp.int32), vals.astype(jnp.float32),
      table.astype(jnp.float32), b0.astype(jnp.float32))
