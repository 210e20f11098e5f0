"""DesignMatrix operator abstraction (DESIGN.md §2).

The solve stack — ``cd.py``'s tile sweeps and ``dglmnet.py``'s drivers —
consumes the design matrix exclusively through the operator interface defined
here, never through raw ``(n, p)`` arrays.  Two concrete layouts:

  * ``DenseDesign`` — a feature-padded dense block.  This is the historical
    behavior; every operator method lowers to the same MXU matmuls the sweeps
    used to emit inline.
  * ``BlockSparseDesign`` — CSR-of-bricks blocked densification.  The matrix
    is cut into (row-block × feature-tile) bricks; only non-empty bricks are
    stored, as a flat ``(B, row_block, tile_size)`` array sorted tile-major
    with a CSR ``tile_ptr`` over feature tiles.  Per-tile Gram blocks and
    gradients are produced by the brick-gather ``ops.tile_gram`` kernel
    (Pallas on TPU), which skips empty bricks; memory scales with the number
    of non-empty bricks, not ``n·p``.

Both classes are registered jax pytrees, so a design can be passed straight
through ``jit`` and ``shard_map``: array leaves get sharded/localized by the
partitioner while the tiling geometry rides along as static aux data.  For
the sharded brick layout the leaves carry two leading mesh axes ``(D, M)``
(data × model); ``localize()`` strips them inside the mapped function.

Host-side builders (``build_block_sparse``, ``build_block_sparse_sharded``)
pack a ``SparseCOO`` into bricks **without ever materializing the dense
(n, p) matrix**: features are frequency-sorted so hot features share tiles
(maximizing brick occupancy, DESIGN.md §2), then whole tiles are dealt
round-robin across feature shards so per-shard nnz stays balanced.

``HeadTailDesign`` (DESIGN.md §2) packs hashed multi-field rows
(``SparseRows``) on the device: its most frequent features as a dense head
the fused kernels take, the rest as a sparse tail whose bytes scale with
its nonzeros.

A fourth layout, ``StreamingDesign`` (DESIGN.md §6), keeps the rows out of
device memory entirely: the matrix is a host array or a chunk-producing
callable (a pure function of the chunk index, à la ``data/pipeline.py``),
and every operator method is an accumulation loop over fixed-size row
chunks with double-buffered host→device transfer.  Its methods run at the
HOST level (they drive jit'd per-chunk kernels; they cannot themselves be
traced), which is why the solver session owns a dedicated streaming outer
loop (``core/solver.py``) built from the same kernels as the in-memory
superstep.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.sparse import SparseCOO, SparseRows
from repro.kernels import ops


class DesignMatrix:
    """Operator interface the CD sweeps run against.

    All methods operate on the LOCAL shard (inside shard_map the partitioner
    has already placed the leaves); partial row reductions are psum'd by the
    caller.  ``shape`` is the padded local shape ``(n_rows, n_tiles * T)``.
    """

    tile_size: int

    @property
    def shape(self):
        raise NotImplementedError

    @property
    def n_tiles(self) -> int:
        raise NotImplementedError

    def localize(self) -> "DesignMatrix":
        """Strip leading mesh axes from the leaves (no-op when local)."""
        return self

    def tile_gram(self, tid, w, r, *, backend=None):
        """(G, g) for feature tile ``tid``: G = X_tᵀ diag(w) X_t  (T, T),
        g = X_tᵀ r  (T,).  Local partials — caller psums over the data axis."""
        raise NotImplementedError

    def tile_matvec(self, tid, v_t):
        """X_t @ v_t → (n_rows,) for a single feature tile."""
        raise NotImplementedError

    def all_tile_grams(self, w, r, *, backend=None):
        """Stacked (G_all (n_tiles, T, T), g_all (n_tiles, T)) — the fused
        Jacobi form: every tile's Gram/gradient from the same iterate."""
        raise NotImplementedError

    def matvec(self, v):
        """X @ v → (n_rows,) over the whole local feature block."""
        raise NotImplementedError

    def rmatvec(self, r):
        """Xᵀ @ r → (n_tiles * T,) in packed column order.  Local partial —
        caller psums over the data axis.  Powers λ_max and the λ-path
        KKT/strong-rule screening (solver.py)."""
        raise NotImplementedError

    def col_moments(self, weights):
        """Weighted first/second column moments in packed column order:
        (Σ_i w_i x_ij, Σ_i w_i x_ij²), both (n_tiles * T,).  Local partials —
        caller psums over the data axis and divides by Σw.  Powers
        ``GLMSolver(standardize=True)`` (weighted column means/norms)."""
        raise NotImplementedError

    def scale_columns(self, scale, center=None):
        """Return a NEW design whose packed column j holds
        ``(x_j - center_j) * scale_j`` (center None = 0).  Centering is only
        supported by dense layouts — it would densify a brick layout — and
        padded rows pick up ``-center_j``, which is inert because every
        consumer weights rows by the observation-weight vector (0 on
        padding)."""
        raise NotImplementedError

    def to_dense(self):
        """Materialize the local block (tests/debugging only)."""
        raise NotImplementedError


# ---------------------------------------------------------------------------
# dense
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnums=1)
def _tile_major(data, tile_size: int):
    """(n, nt·T) → (nt, n_pad, T): the fused kernels' tile-major operand,
    rows zero-padded to a multiple of ``ops.DENSE_ROW_BLOCK`` (one fused
    transpose-and-pad, so building it holds no third copy of the design)."""
    n = data.shape[0]
    data_t = jnp.swapaxes(data.reshape(n, -1, tile_size), 0, 1)
    pad = (-n) % ops.DENSE_ROW_BLOCK
    return jnp.pad(data_t, ((0, 0), (0, pad), (0, 0))) if pad else data_t


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DenseDesign(DesignMatrix):
    """Feature-padded dense design: ``data`` is (n_rows, n_tiles * T).

    ``data_t`` is an OPTIONAL cached tile-major transposed copy
    ``(n_tiles, n_pad, T)`` built by ``dense_design`` — the layout the fused
    superstep kernels (DESIGN.md §8) grid over: tile t's rows are one
    contiguous (n, T) block, so the per-tile Gram is a single batched matmul
    instead of an einsum re-gather.  Its rows are zero-padded to
    ``n_pad``, a multiple of ``ops.DENSE_ROW_BLOCK``, so the kernels never
    re-pad it per call.  It doubles the design's memory; sessions that never
    take the fused path can pass ``None``.
    """

    data: jnp.ndarray
    tile_size: int
    data_t: Optional[jnp.ndarray] = None

    def tree_flatten(self):
        return (self.data, self.data_t), (self.tile_size,)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(leaves[0], aux[0], leaves[1])

    def tiles3(self):
        """(n_tiles, n_pad, T) row-padded tile-major view — the cached
        ``data_t`` when present, else built in-trace (correct but
        re-materialized per call; a session caches it once)."""
        if self.data_t is not None:
            return self.data_t
        return _tile_major(self.data, self.tile_size)

    @property
    def shape(self):
        return self.data.shape

    @property
    def n_tiles(self) -> int:
        return self.data.shape[1] // self.tile_size

    def partition_specs(self, axis_data, axis_model):
        from jax.sharding import PartitionSpec as P
        return DenseDesign(P(axis_data, axis_model), self.tile_size,
                           None if self.data_t is None
                           else P(axis_model, axis_data, None))

    def tile_gram(self, tid, w, r, *, backend=None):
        T = self.tile_size
        n = self.data.shape[0]
        Xt = jax.lax.dynamic_slice(self.data, (0, tid * T), (n, T))
        G = (Xt * w[:, None]).T @ Xt
        g = Xt.T @ r
        return G, g

    def tile_matvec(self, tid, v_t):
        T = self.tile_size
        n = self.data.shape[0]
        Xt = jax.lax.dynamic_slice(self.data, (0, tid * T), (n, T))
        return Xt @ v_t

    def all_tile_grams(self, w, r, *, backend=None):
        n = self.data.shape[0]
        Xr = self.data.reshape(n, self.n_tiles, self.tile_size)
        G_all = jnp.einsum("nti,ntj->tij", Xr * w[:, None, None], Xr)
        g_all = (self.data.T @ r).reshape(self.n_tiles, self.tile_size)
        return G_all, g_all

    def matvec(self, v):
        return self.data @ v

    def rmatvec(self, r):
        return self.data.T @ r

    def col_moments(self, weights):
        return self.data.T @ weights, (self.data * self.data).T @ weights

    def scale_columns(self, scale, center=None):
        data = self.data if center is None else self.data - center[None, :]
        data = data * scale[None, :]
        out = DenseDesign(data, self.tile_size)
        if self.data_t is not None:     # rebuild the fused-layout cache
            out.data_t = _tile_major(data, self.tile_size)
        return out

    def to_dense(self):
        return self.data


# ---------------------------------------------------------------------------
# blocked-sparse (CSR-of-bricks)
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class BlockSparseDesign(DesignMatrix):
    """CSR-of-bricks blocked densification of a sparse design matrix.

    Leaves (local layout; with ``leading == 2`` each carries (D, M) mesh axes
    in front):

      bricks     (B, row_block, tile_size) f32 — non-empty bricks, tile-major
      brick_row  (B,) i32 — row-block index of each brick
      brick_tile (B,) i32 — feature-tile index of each brick
      tile_ptr   (n_tiles + 1,) i32 — CSR offsets: bricks of tile t live at
                 [tile_ptr[t], tile_ptr[t+1])

    Static geometry: ``n_rows`` (local, multiple of ``row_block``),
    ``n_tiles``, and ``max_bricks_per_tile`` — the static loop/grid bound all
    SPMD peers share (brick counts beyond a tile's actual population are
    predicated off inside ``ops.tile_gram``).
    """

    bricks: jnp.ndarray
    brick_row: jnp.ndarray
    brick_tile: jnp.ndarray
    tile_ptr: jnp.ndarray
    tile_size: int
    row_block: int
    n_rows: int
    _n_tiles: int
    max_bricks_per_tile: int
    leading: int = 0
    # static (host-checked at build): every tile holds exactly
    # max_bricks_per_tile bricks, stored tile-major contiguous — the fused
    # superstep's zero-copy (n_tiles, K·rb, T) reshape applies (DESIGN.md §8)
    uniform_K: bool = False

    def tree_flatten(self):
        leaves = (self.bricks, self.brick_row, self.brick_tile, self.tile_ptr)
        aux = (self.tile_size, self.row_block, self.n_rows, self._n_tiles,
               self.max_bricks_per_tile, self.leading, self.uniform_K)
        return leaves, aux

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves, *aux)

    @property
    def shape(self):
        return (self.n_rows, self._n_tiles * self.tile_size)

    @property
    def n_tiles(self) -> int:
        return self._n_tiles

    @property
    def n_row_blocks(self) -> int:
        return self.n_rows // self.row_block

    def localize(self) -> "BlockSparseDesign":
        if not self.leading:
            return self
        return BlockSparseDesign(
            self.bricks[0, 0], self.brick_row[0, 0], self.brick_tile[0, 0],
            self.tile_ptr[0, 0], self.tile_size, self.row_block, self.n_rows,
            self._n_tiles, self.max_bricks_per_tile, leading=0,
            uniform_K=self.uniform_K)

    def partition_specs(self, axis_data, axis_model):
        from jax.sharding import PartitionSpec as P
        assert self.leading == 2, "partition_specs needs the (D, M) layout"
        lead = (axis_data, axis_model)
        return BlockSparseDesign(
            P(*lead, None, None, None), P(*lead, None), P(*lead, None),
            P(*lead, None), self.tile_size, self.row_block, self.n_rows,
            self._n_tiles, self.max_bricks_per_tile, leading=2,
            uniform_K=self.uniform_K)

    # -- per-tile brick gather ------------------------------------------------

    def _gather_tile(self, tid):
        """(bricks (K, rb, T), rows (K,), n_valid, valid mask) for tile tid,
        K = max_bricks_per_tile.  Entries beyond n_valid are clamped gathers
        of in-range bricks; consumers mask them via n_valid/valid."""
        K = self.max_bricks_per_tile
        start = self.tile_ptr[tid]
        stop = self.tile_ptr[tid + 1]
        idx = start + jnp.arange(K, dtype=jnp.int32)
        valid = idx < stop
        safe = jnp.minimum(idx, self.bricks.shape[0] - 1)
        return self.bricks[safe], self.brick_row[safe], stop - start, valid

    def tile_gram(self, tid, w, r, *, backend=None):
        tb, rows, n_valid, _ = self._gather_tile(tid)
        w2 = w.reshape(self.n_row_blocks, self.row_block)
        r2 = r.reshape(self.n_row_blocks, self.row_block)
        return ops.tile_gram(tb, rows, n_valid, w2, r2, backend=backend)

    def tile_matvec(self, tid, v_t):
        tb, rows, _, valid = self._gather_tile(tid)
        contrib = jnp.einsum("kit,t->ki", tb, v_t) * valid[:, None]
        out2 = jax.ops.segment_sum(contrib, rows,
                                   num_segments=self.n_row_blocks)
        return out2.reshape(-1)

    def all_tile_grams(self, w, r, *, backend=None):
        w2 = w.reshape(self.n_row_blocks, self.row_block)
        r2 = r.reshape(self.n_row_blocks, self.row_block)

        def one(tid):
            tb, rows, n_valid, _ = self._gather_tile(tid)
            return ops.tile_gram(tb, rows, n_valid, w2, r2, backend=backend)

        return jax.lax.map(one, jnp.arange(self._n_tiles, dtype=jnp.int32))

    def gather_all_tiles(self):
        """Every tile's bricks as one batched layout for the fused superstep
        (DESIGN.md §8): (bricks3 (nt, K, rb, T), rows (nt, K), valid (nt, K)).

        With ``uniform_K`` (host-verified at build) this is a ZERO-COPY
        reshape of the tile-major brick array; otherwise a vmapped
        dynamic-slice gather bounded by K with a validity mask.
        """
        nt, K = self._n_tiles, self.max_bricks_per_tile
        rb, T = self.row_block, self.tile_size
        if self.uniform_K:
            b3 = self.bricks[:nt * K].reshape(nt, K, rb, T)
            rows = self.brick_row[:nt * K].reshape(nt, K)
            valid = jnp.ones((nt, K), jnp.float32)
            return b3, rows, valid
        B = self.bricks.shape[0]

        def one(start, stop):
            st = jnp.minimum(start, B - K)
            tb = jax.lax.dynamic_slice(self.bricks, (st, 0, 0), (K, rb, T))
            rw = jax.lax.dynamic_slice(self.brick_row, (st,), (K,))
            idx = st + jnp.arange(K, dtype=jnp.int32)
            return tb, rw, ((idx >= start) & (idx < stop)).astype(jnp.float32)

        return jax.vmap(one)(self.tile_ptr[:-1], self.tile_ptr[1:])

    def matvec(self, v):
        vt = v.reshape(self._n_tiles, self.tile_size)
        contrib = jnp.einsum("kit,kt->ki", self.bricks, vt[self.brick_tile])
        out2 = jax.ops.segment_sum(contrib, self.brick_row,
                                   num_segments=self.n_row_blocks)
        return out2.reshape(-1)

    def rmatvec(self, r):
        r2 = r.reshape(self.n_row_blocks, self.row_block)
        contrib = jnp.einsum("kit,ki->kt", self.bricks, r2[self.brick_row])
        out = jax.ops.segment_sum(contrib, self.brick_tile,
                                  num_segments=self._n_tiles)
        return out.reshape(-1)

    def col_moments(self, weights):
        w2 = weights.reshape(self.n_row_blocks, self.row_block)
        wk = w2[self.brick_row]                        # (B, rb)
        s1 = jax.ops.segment_sum(
            jnp.einsum("kit,ki->kt", self.bricks, wk), self.brick_tile,
            num_segments=self._n_tiles)
        s2 = jax.ops.segment_sum(
            jnp.einsum("kit,ki->kt", self.bricks * self.bricks, wk),
            self.brick_tile, num_segments=self._n_tiles)
        return s1.reshape(-1), s2.reshape(-1)

    def scale_columns(self, scale, center=None):
        """Per-column rescale of the brick values.  ``scale`` is (p_loc,)
        for a local design; with ``leading == 2`` it is (M, p_loc) — columns
        vary only over the model axis, so one scale row serves every data
        shard.  Centering is refused (it would densify the layout; callers
        fall back to scale-only standardization — DESIGN.md §5)."""
        if center is not None:
            raise ValueError(
                "BlockSparseDesign cannot center columns (centering fills "
                "every empty brick); use scale-only standardization")
        T = self.tile_size
        if self.leading == 0:
            scale2 = scale.reshape(self._n_tiles, T)
            sb = scale2[self.brick_tile]               # (B, T)
            bricks = self.bricks * sb[:, None, :]
        elif self.leading == 2:
            M = self.bricks.shape[1]
            scale2 = scale.reshape(M, self._n_tiles, T)
            # (D, M, B, T): per-brick column scales gathered by tile id
            sb = scale2[jnp.arange(M)[None, :, None], self.brick_tile]
            bricks = self.bricks * sb[:, :, :, None, :]
        else:
            raise ValueError(f"unsupported leading={self.leading}")
        return BlockSparseDesign(
            bricks, self.brick_row, self.brick_tile, self.tile_ptr,
            self.tile_size, self.row_block, self.n_rows, self._n_tiles,
            self.max_bricks_per_tile, leading=self.leading,
            uniform_K=self.uniform_K)

    def to_dense(self):
        rb, T = self.row_block, self.tile_size
        out = jnp.zeros((self.n_row_blocks, rb, self._n_tiles, T),
                        jnp.float32)
        out = out.at[self.brick_row, :, self.brick_tile, :].add(self.bricks)
        return out.reshape(self.n_rows, self._n_tiles * T)


# ---------------------------------------------------------------------------
# dense head + sparse tail (hashed one-hot rows)
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class HeadTailDesign(DesignMatrix):
    """A sparse design split by feature frequency (DESIGN.md §2): the
    ``head_width`` most frequent features as a dense ``DenseDesign`` (both
    its copies: tile-major for the fused superstep, row-major for the
    gradient), the rest as a sparse tail whose bytes scale with its
    nonzeros, where a brick layout's scale with the bricks they touch
    (hashed one-hot rows touch one brick per feature tile a row reaches).

    Packed columns: head columns ``[0, H)``, then tail columns
    ``[H, H + tail_cols)``; a tail column below is local to the tail (the
    packed column minus H).  Leaves:

      head       DenseDesign over (n_rows, H)
      tail_ids   (n_rows, K_tail) i32 — the tail by rows (ELL): each row's
      tail_vals  (n_rows, K_tail) f32   tail entries, padded with value 0
                 at column ``tail_cols`` (out of range, so a scatter drops
                 it); the whole-tail products (gradient, margins) read it
      ws_rows, ws_cols, ws_vals  (ws_capacity,) — the working set: every
                 entry of the tail columns a superstep may move, padded
                 with value 0 and out-of-range row and column
      ws_colset  (ws_capacity / 32,) i32 — the working set's columns,
                 padded with column ``tail_cols``
      ws_count   () i32 — its entries; above ``ws_capacity`` (or with more
                 columns than ``ws_colset`` holds) the superstep reads the
                 whole tail instead

    XLA gathers and scatters one element at a time on a TPU (7–9 ns each
    on a v5e), so the superstep's tail reads the working set, in chunks of
    ``ws_chunk`` entries, and the whole tail only where the working set
    does not fit.
    """

    head: DenseDesign
    tail_ids: jnp.ndarray
    tail_vals: jnp.ndarray
    ws_rows: jnp.ndarray
    ws_cols: jnp.ndarray
    ws_vals: jnp.ndarray
    ws_colset: jnp.ndarray
    ws_count: jnp.ndarray
    tile_size: int
    tail_cols: int
    ws_chunk: int

    def tree_flatten(self):
        return (self.head, self.tail_ids, self.tail_vals, self.ws_rows,
                self.ws_cols, self.ws_vals, self.ws_colset, self.ws_count), \
            (self.tile_size, self.tail_cols, self.ws_chunk)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves, *aux)

    @property
    def head_width(self) -> int:
        return self.head.data.shape[1]

    @property
    def shape(self):
        return (self.head.data.shape[0], self.head_width + self.tail_cols)

    @property
    def n_tiles(self) -> int:
        return self.shape[1] // self.tile_size

    @property
    def tail_width(self) -> int:
        return self.tail_ids.shape[1]

    @property
    def ws_capacity(self) -> int:
        return self.ws_rows.shape[0]

    def split(self, v):
        """(head part, tail part) of a packed-column vector."""
        return v[:self.head_width], v[self.head_width:]

    # -- the whole tail -------------------------------------------------------

    def tail_matvec(self, v_t):
        """X_tail @ v_t: a gather-sum over each row's tail entries."""
        return jnp.sum(self.tail_vals * jnp.asarray(v_t).at[
            self.tail_ids].get(mode="fill", fill_value=0.0), axis=1)

    def _tail_sum(self, per_entry):
        """Column sums over the tail of (n_rows, K_tail) per-entry values
        (one scatter; a multi-column one runs far slower on a TPU)."""
        return jnp.zeros((self.tail_cols,), jnp.float32).at[
            self.tail_ids.reshape(-1)].add(per_entry.reshape(-1), mode="drop")

    def tail_stats(self, s, w):
        """(X_tailᵀ s, diag X_tailᵀ W X_tail): each tail feature's gradient
        and diagonal Hessian."""
        v = self.tail_vals
        return self._tail_sum(v * s[:, None]), \
            self._tail_sum(v * v * w[:, None])

    # -- the working set ------------------------------------------------------

    def with_working_set(self, tail_counts, cols):
        """The same design with the working set of tail columns ``cols``
        (host, sorted), whose entries ``tail_counts`` (host, per tail
        column) counts.  A set beyond the capacities makes the superstep
        read the whole tail.  Returns (design, entries in the set)."""
        count = int(tail_counts[cols].sum())
        cap, col_cap = self.ws_capacity, self.ws_colset.shape[0]
        colset = np.full((col_cap,), self.tail_cols, np.int32)
        if count > cap or cols.shape[0] > col_cap:
            count = cap + 1
        else:
            colset[:cols.shape[0]] = cols
        ws = (self.ws_rows, self.ws_cols, self.ws_vals)
        if 0 < count <= cap:
            member = np.zeros((self.tail_cols,), bool)
            member[cols] = True
            ws = _gather_working_set(self.tail_ids, self.tail_vals,
                                     jnp.asarray(member), cap)
        return dataclasses.replace(
            self, ws_rows=ws[0], ws_cols=ws[1], ws_vals=ws[2],
            ws_colset=jnp.asarray(colset),
            ws_count=jnp.asarray(count, jnp.int32)), count

    def _ws_chunks(self, body, init):
        """Fold ``body(rows, cols, vals, carry)`` over the working set's
        entries, ``ws_chunk`` at a time, as many chunks as it fills."""
        C = self.ws_chunk
        n_chunks = (jnp.minimum(self.ws_count, self.ws_capacity) + C - 1) // C

        def step(c, carry):
            sl = lambda a: jax.lax.dynamic_slice(a, (c * C,), (C,))
            return body(sl(self.ws_rows), sl(self.ws_cols),
                        sl(self.ws_vals), carry)

        return jax.lax.fori_loop(0, n_chunks, step, init)

    def tail_stats_ws(self, s, w):
        """``tail_stats`` over the working set's columns, zero elsewhere;
        the whole tail's where the working set does not fit."""
        def body(rows, cols, vals, gh):
            sv = vals * s.at[rows].get(mode="fill", fill_value=0.0)
            hv = vals * vals * w.at[rows].get(mode="fill", fill_value=0.0)
            return (gh[0].at[cols].add(sv, mode="drop"),
                    gh[1].at[cols].add(hv, mode="drop"))

        zero = jnp.zeros((self.tail_cols,), jnp.float32)
        if not self.tail_cols:
            return zero, zero
        return jax.lax.cond(
            self.ws_count <= self.ws_capacity,
            lambda: self._ws_chunks(body, (zero, zero)),
            lambda: self.tail_stats(s, w))

    def over_working_set(self, fn, *tail_vectors):
        """``fn(*tail_vectors)`` for a reduction ``fn`` that is zero on the
        columns off the working set: evaluated on the set's columns alone
        where the set fits, else on the whole tail."""
        if not self.tail_cols:
            return fn(*tail_vectors)

        def at_set():
            return fn(*(v.at[self.ws_colset].get(mode="fill", fill_value=0.0)
                        for v in tail_vectors))

        return jax.lax.cond(self.ws_count <= self.ws_capacity, at_set,
                            lambda: fn(*tail_vectors))

    def tail_matvec_ws(self, v_t):
        """``tail_matvec`` for a ``v_t`` that is zero off the working set's
        columns."""
        def body(rows, cols, vals, out):
            return out.at[rows].add(
                vals * v_t.at[cols].get(mode="fill", fill_value=0.0),
                mode="drop")

        zero = jnp.zeros((self.shape[0],), jnp.float32)
        if not self.tail_cols:
            return zero
        return jax.lax.cond(
            self.ws_count <= self.ws_capacity,
            lambda: self._ws_chunks(body, zero),
            lambda: self.tail_matvec(v_t))

    # -- operators ------------------------------------------------------------

    def matvec(self, v):
        vh, vt = self.split(v)
        return self.head.matvec(vh) + self.tail_matvec(vt)

    def rmatvec(self, r):
        return jnp.concatenate([self.head.rmatvec(r),
                                self._tail_sum(self.tail_vals * r[:, None])])

    def col_moments(self, weights):
        h1, h2 = self.head.col_moments(weights)
        t1, t2 = self.tail_stats(weights, weights)
        return jnp.concatenate([h1, t1]), jnp.concatenate([h2, t2])

    def to_dense(self):
        n = self.shape[0]
        tail = jnp.zeros((n, self.tail_cols), jnp.float32).at[
            jnp.arange(n)[:, None], self.tail_ids].add(self.tail_vals,
                                                        mode="drop")
        return jnp.concatenate([self.head.data, tail], axis=1)


def _cumsum(x):
    """Inclusive running sum of a 1-D array, as a scan over rows of 1024
    plus each row's offset (a TPU compiles one flat scan of 10⁶ for half a
    minute)."""
    n, B = x.shape[0], 1024
    rows = jnp.cumsum(jnp.pad(x.astype(jnp.int32), (0, (-n) % B)).reshape(
        -1, B), axis=1)
    ends = rows[:, -1]
    return (rows + (jnp.cumsum(ends) - ends)[:, None]).reshape(-1)[:n]


@functools.partial(jax.jit, static_argnums=(3,))
def _gather_working_set(tail_ids, tail_vals, member, cap: int):
    """(rows, cols, vals) of the ELL entries in ``member`` columns, packed
    in row order into ``cap`` slots; padding past them has value 0 and
    out-of-range row and column, which the superstep's scatters drop."""
    n, K = tail_ids.shape
    ids = tail_ids.reshape(-1)
    keep = member.at[ids].get(mode="fill", fill_value=False)
    slot = jnp.where(keep, _cumsum(keep) - 1, cap)
    src = jnp.full((cap,), ids.shape[0], jnp.int32).at[slot].set(
        jnp.arange(ids.shape[0], dtype=jnp.int32), mode="drop")
    used = src < ids.shape[0]
    return (jnp.where(used, src // K, n),
            jnp.where(used, ids.at[src].get(mode="fill", fill_value=0),
                      member.shape[0]),
            jnp.where(used, tail_vals.reshape(-1).at[src].get(
                mode="fill", fill_value=0.0), 0.0))


@functools.partial(jax.jit, static_argnums=(2, 3))
def _frequency_columns(ids, vals, p: int, H: int):
    """Packed column of each feature: the H most frequent features (ties
    by feature id) ranked in columns [0, H), most frequent first, the rest
    after them in feature order.  The head is found by bisecting on the
    H-th largest count, so no sort runs over the features (a TPU sort of
    10⁶ keys takes half a minute to compile for a TPU)."""
    counts = jnp.zeros((p,), jnp.int32).at[ids.reshape(-1)].add(
        (vals != 0).reshape(-1).astype(jnp.int32))
    need = min(H, p)

    def halve(_, lohi):             # largest t with #(counts >= t) >= need
        lo, hi = lohi
        mid = (lo + hi + 1) // 2
        ok = jnp.sum(counts >= mid) >= need
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid - 1)

    t, _ = jax.lax.fori_loop(0, 32, halve, (jnp.int32(0), jnp.max(counts)))
    tie = counts == t
    head = (counts > t) | (tie & (_cumsum(tie) <= need - jnp.sum(
        counts > t)))
    j = jnp.arange(p, dtype=jnp.int32)
    head_ids = jnp.full((need,), p, jnp.int32).at[
        jnp.where(head, _cumsum(head) - 1, need)].set(j, mode="drop")
    ranked = head_ids[jnp.argsort(-counts[head_ids], stable=True)]
    tail_col = H + _cumsum(~head) - 1
    return tail_col.at[ranked].set(jnp.arange(need, dtype=jnp.int32))


@functools.partial(jax.jit, static_argnums=(3, 4))
def _pack_head(ids, vals, col, n_pad: int, H: int):
    """The head (n_pad, H): each row's pairs in head columns, added up,
    one field position at a time (a scatter into an array this size takes
    half a minute to compile for a TPU)."""
    pad = ((0, n_pad - ids.shape[0]), (0, 0))
    c = jnp.pad(col[ids], pad, constant_values=H)
    v = jnp.pad(vals, pad)
    h = jnp.arange(H, dtype=jnp.int32)[None, :]

    def add(k, head):
        return head + jnp.where(c[:, k, None] == h, v[:, k, None], 0.0)

    return jax.lax.fori_loop(0, ids.shape[1], add,
                             jnp.zeros((n_pad, H), jnp.float32))


@functools.partial(jax.jit, static_argnums=(3, 4))
def _merge_tail(ids, vals, col, H: int, tail_cols: int):
    """Each row's tail entries as (tail columns, values), both (n, K),
    sorted by column with a column's repeated pairs added into one entry,
    then padding (value 0, column ``tail_cols``, one past the tail's
    last); and each row's number of tail entries."""
    c = col[ids] - H
    key = jnp.where((c >= 0) & (vals != 0), c, tail_cols)
    key, v = jax.lax.sort((key, vals), dimension=1, num_keys=1)
    K = key.shape[1]
    k = jnp.arange(K)
    first = jnp.concatenate([jnp.ones_like(key[:, :1], bool),
                             key[:, 1:] != key[:, :-1]], axis=1)
    # the run of equal columns starting at a first entry ends before the
    # next first entry: its sum is a difference of running sums
    nxt = jnp.concatenate([jnp.where(first, k, K)[:, 1:],
                           jnp.full_like(key[:, :1], K)], axis=1)
    nxt = jax.lax.cummin(nxt, axis=1, reverse=True)
    cs = jnp.cumsum(v, axis=1)
    before = jnp.concatenate([jnp.zeros_like(v[:, :1]), cs[:, :-1]], axis=1)
    run = jnp.take_along_axis(cs, nxt - 1, axis=1) - before
    keep = first & (key < tail_cols) & (run != 0)
    key, v = jax.lax.sort((jnp.where(keep, key, tail_cols),
                           jnp.where(keep, run, 0.0)), dimension=1,
                          num_keys=1)
    return key, v, jnp.sum(keep, axis=1)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _pad_tail(key, v, n_pad: int, K_tail: int, tail_cols: int):
    """The ELL tail (n_pad, K_tail) from ``_merge_tail``'s rows, and the
    entries of each tail column."""
    pad = ((0, n_pad - key.shape[0]), (0, 0))
    ids = jnp.pad(key[:, :K_tail], pad,
                  constant_values=tail_cols).astype(jnp.int32)
    counts = jnp.zeros((tail_cols,), jnp.int32).at[ids.reshape(-1)].add(
        1, mode="drop")
    return ids, jnp.pad(v[:, :K_tail], pad).astype(jnp.float32), counts


# the working set of a HeadTailDesign: at most _WS_CAPACITY entries (12
# bytes each on the device), read _WS_CHUNK at a time
_WS_CAPACITY = 1 << 20
_WS_CHUNK = 1 << 13


def head_tail_design(rows, tile_size: int, head_features: int):
    """(HeadTailDesign, DesignInfo) from ``SparseRows``, packed on the
    device: the ``head_features`` most frequent features (a multiple of
    ``tile_size``) dense, the rest in the tail, whose row lists are as wide
    as the most tail nonzeros of any row.  Rows are padded to a multiple of
    ``ops.DENSE_ROW_BLOCK`` (the fused kernels' row block).  The working
    set holds at most ``_WS_CAPACITY`` entries (fewer where the tail has
    fewer) of at most a 32nd as many columns; it starts as the whole tail.
    The info's ``tail_counts`` (host) holds each tail column's entries,
    which size working sets."""
    ws_capacity, ws_chunk = _WS_CAPACITY, _WS_CHUNK
    H, T = int(head_features), int(tile_size)
    if H <= 0 or H % T:
        raise ValueError(f"head_features={H} must be a positive multiple "
                         f"of tile_size={T}")
    n, p = rows.shape
    ids = jnp.asarray(rows.ids, jnp.int32)
    vals = jnp.asarray(rows.vals, jnp.float32)
    n_pad = n + (-n) % ops.DENSE_ROW_BLOCK
    tail_cols = max(p - H, 0)
    tail_cols += (-tail_cols) % T
    col = _frequency_columns(ids, vals, p, H)
    head = _pack_head(ids, vals, col, n_pad, H)
    key, v, tail_nnz = _merge_tail(ids, vals, col, H, tail_cols)
    K_tail, E = int(jnp.max(tail_nnz)), int(jnp.sum(tail_nnz))
    tail_ids, tail_vals, counts = _pad_tail(key, v, n_pad, K_tail, tail_cols)
    del key, v
    cap = max(ws_chunk, min(ws_capacity, E + (-E) % ws_chunk))
    # the empty working set, from the program that gathers every later
    # one, so that it compiles here, with the packing
    ws = _gather_working_set(tail_ids, tail_vals,
                             jnp.zeros((tail_cols,), bool), cap) \
        if tail_cols else (jnp.full((cap,), n_pad, jnp.int32),
                           jnp.zeros((cap,), jnp.int32),
                           jnp.zeros((cap,), jnp.float32))
    design = HeadTailDesign(
        DenseDesign(head, T, _tile_major(head, T)), tail_ids, tail_vals,
        *ws,
        jnp.full((max(1, min(cap // 32, tail_cols)),), tail_cols, jnp.int32),
        jnp.asarray(cap + 1, jnp.int32), T, tail_cols, ws_chunk)
    return design, DesignInfo(shape=(n, p),
                              col_of_feature=np.asarray(col, np.int64),
                              tail_counts=np.asarray(counts, np.int64))


# ---------------------------------------------------------------------------
# streaming (out-of-core row chunks)
# ---------------------------------------------------------------------------


class StreamingDesign(DesignMatrix):
    """Out-of-core row-chunked design: rows live on host (or are produced on
    demand), the device only ever sees one ``(chunk_rows, p_pad)`` buffer.

    The chunk source is ``chunk_fn(i) -> (rows_i, p_src)`` — a host callable
    returning chunk ``i``'s raw rows (``rows_i == chunk_rows`` except
    possibly the last chunk).  For an array input the builder
    (``streaming_design``) wraps a slicer; for synthetic / disk-backed data
    pass a pure function of the chunk index so a resumed run replays the
    exact byte stream without data-state checkpointing (the
    ``data/pipeline.py`` contract).

    Per-tile Gram/gradient statistics are sums over rows, so every operator
    method is an accumulation loop over chunks.  ``iter_chunks`` issues the
    NEXT chunk's host→device transfer before the caller dispatches compute
    on the current one (double buffering: with async dispatch the copy
    overlaps the in-flight compute).  These methods run at the host level —
    a ``StreamingDesign`` cannot cross a ``jit`` boundary (``localize``
    raises), which is why ``core/solver.py`` drives streaming fits with a
    dedicated chunked-statistics outer loop (DESIGN.md §6).

    Column transforms (standardization) are folded into chunk production:
    ``scale_columns`` returns a new design whose chunks come out as
    ``(x - center) * scale`` — centering is fine here (chunks are dense on
    device), exactly matching ``DenseDesign`` semantics including the inert
    ``-center`` rows in the padding (observation weights are 0 there).
    """

    def __init__(self, chunk_fn, *, n_rows: int, n_cols: int, chunk_rows: int,
                 tile_size: int, add_ones: bool = False, scale=None,
                 center=None, prefetch: bool = True):
        if chunk_rows <= 0:
            raise ValueError("chunk_rows must be positive")
        self._chunk_fn = chunk_fn
        self.prefetch = bool(prefetch)   # default for iter_chunks (benches
        #                                  flip it to measure overlap)
        self.n_rows_data = int(n_rows)          # true (unpadded) row count
        self.n_cols_src = int(n_cols)           # raw columns per chunk_fn
        self.chunk_rows = int(chunk_rows)
        self.tile_size = int(tile_size)
        self.add_ones = bool(add_ones)
        self.p_user = self.n_cols_src + (1 if add_ones else 0)
        self.p_pad = self.p_user + ((-self.p_user) % tile_size)
        self.n_chunks = -(-self.n_rows_data // self.chunk_rows)
        self._scale = None if scale is None else \
            np.asarray(scale, np.float32)
        self._center = None if center is None else \
            np.asarray(center, np.float32)

    @property
    def shape(self):
        return (self.n_chunks * self.chunk_rows, self.p_pad)

    @property
    def n_tiles(self) -> int:
        return self.p_pad // self.tile_size

    def localize(self):
        raise TypeError(
            "StreamingDesign cannot cross into jit/shard_map: its rows are "
            "host-resident and its operator methods are host-level chunk "
            "loops; use GLMSolver's streaming mode (core/solver.py)")

    def with_ones_column(self) -> "StreamingDesign":
        """New design whose chunks carry an appended all-ones column (the
        unpenalized intercept), placed before the tile padding."""
        if self.add_ones:
            raise ValueError("design already carries an intercept column")
        if self._scale is not None or self._center is not None:
            raise ValueError("append the intercept before scaling")
        return StreamingDesign(
            self._chunk_fn, n_rows=self.n_rows_data, n_cols=self.n_cols_src,
            chunk_rows=self.chunk_rows, tile_size=self.tile_size,
            add_ones=True, prefetch=self.prefetch)

    def scale_columns(self, scale, center=None):
        scale = np.asarray(scale, np.float32)
        new_center = np.zeros((self.p_pad,), np.float32) if center is None \
            else np.asarray(center, np.float32)
        old_scale = np.ones((self.p_pad,), np.float32) if self._scale is None \
            else self._scale
        old_center = np.zeros((self.p_pad,), np.float32) \
            if self._center is None else self._center
        # compose: ((x - c0)·s0 - c1)·s1 = (x - (c0 + c1/s0)) · (s0·s1)
        safe = np.where(old_scale != 0, old_scale, 1.0)
        out = StreamingDesign(
            self._chunk_fn, n_rows=self.n_rows_data, n_cols=self.n_cols_src,
            chunk_rows=self.chunk_rows, tile_size=self.tile_size,
            add_ones=self.add_ones, prefetch=self.prefetch,
            scale=old_scale * scale, center=old_center + new_center / safe)
        return out

    # -- chunk production ----------------------------------------------------

    def _host_chunk(self, i: int) -> np.ndarray:
        """(chunk_rows, p_pad) f32 host buffer for chunk ``i``: raw rows →
        optional ones column → zero row/column padding → (x - center)·scale
        (applied to padded rows too, matching ``DenseDesign.scale_columns``;
        inert because observation weights are 0 on padding)."""
        lo = i * self.chunk_rows
        rows = min(self.chunk_rows, self.n_rows_data - lo)
        if rows <= 0:
            raise IndexError(f"chunk {i} out of range ({self.n_chunks})")
        raw = np.asarray(self._chunk_fn(i), np.float32)
        if raw.shape != (rows, self.n_cols_src):
            raise ValueError(
                f"chunk_fn({i}) returned {raw.shape}; expected "
                f"({rows}, {self.n_cols_src})")
        out = np.zeros((self.chunk_rows, self.p_pad), np.float32)
        out[:rows, :self.n_cols_src] = raw
        if self.add_ones:
            out[:rows, self.n_cols_src] = 1.0
        if self._center is not None:
            out = out - self._center[None, :]
        if self._scale is not None:
            out = out * self._scale[None, :]
        return out

    def iter_chunks(self, start: int = 0,
                    *, prefetch: Optional[bool] = None):
        """Yield ``(i, device_chunk)`` for chunks ``[start, n_chunks)``.

        With ``prefetch`` (the default) chunk i+1's host materialization and
        host→device copy are issued while the consumer's compute on chunk i
        is still in flight (jax dispatch is async) — the double-buffering
        the benchmarks measure.  ``prefetch=False`` is the serial baseline;
        ``None`` falls back to the design's ``prefetch`` attribute.
        """
        prefetch = self.prefetch if prefetch is None else prefetch
        if start >= self.n_chunks:
            return
        if not prefetch:
            for i in range(start, self.n_chunks):
                # StreamingDesign is process-local by contract (mesh=None)
                # lint: allow DIST001 — chunks go to the default local device
                yield i, jax.device_put(self._host_chunk(i))
            return
        # lint: allow DIST001 — process-local prefetch, same contract
        nxt = jax.device_put(self._host_chunk(start))
        for i in range(start, self.n_chunks):
            cur = nxt
            if i + 1 < self.n_chunks:
                # lint: allow DIST001 — process-local prefetch
                nxt = jax.device_put(self._host_chunk(i + 1))
            yield i, cur

    def row_slice(self, i: int) -> slice:
        """Row range of chunk ``i`` in the padded (n_tot,) coordinates."""
        return slice(i * self.chunk_rows, (i + 1) * self.chunk_rows)

    def process_slice(self, process_id: Optional[int] = None,
                      num_processes: Optional[int] = None):
        """Per-process chunk sharding (DESIGN.md §9): the contiguous chunk
        range process ``process_id`` of ``num_processes`` owns, as its own
        ``StreamingDesign``, plus the matching global row slice for the
        caller's (y, weights, offset) host vectors.

        This is the beyond-host-memory data model for multi-process runs:
        rather than every process replicating the full row stream, each
        walks only its own chunks (``chunk_fn`` is a pure function of the
        GLOBAL chunk index, so no data moves).  Defaults come from the
        active ``repro.dist.bootstrap`` context.

        Returns ``(design, rows)`` where ``rows`` is a slice in the
        UNPADDED global row coordinates.
        """
        if process_id is None or num_processes is None:
            from repro.dist import bootstrap as _boot
            ctx = _boot.context()
            process_id = ctx.process_id if process_id is None else process_id
            num_processes = ctx.num_processes if num_processes is None \
                else num_processes
        if not 0 <= process_id < num_processes:
            raise ValueError(
                f"process_id {process_id} out of range for "
                f"{num_processes} processes")
        if num_processes > self.n_chunks:
            raise ValueError(
                f"{num_processes} processes but only {self.n_chunks} "
                "chunks; lower chunk_rows so every process owns work")
        base, rem = divmod(self.n_chunks, num_processes)
        lo = process_id * base + min(process_id, rem)
        hi = lo + base + (1 if process_id < rem else 0)
        row_lo = lo * self.chunk_rows
        row_hi = min(hi * self.chunk_rows, self.n_rows_data)
        design = StreamingDesign(
            lambda j, _lo=lo: self._chunk_fn(_lo + j),
            n_rows=row_hi - row_lo, n_cols=self.n_cols_src,
            chunk_rows=self.chunk_rows, tile_size=self.tile_size,
            add_ones=self.add_ones, prefetch=self.prefetch,
            scale=self._scale, center=self._center)
        return design, slice(row_lo, row_hi)

    # -- operator interface (host-level accumulation loops) ------------------

    def _row_chunks(self, *vecs):
        """Zip chunks with the matching slices of caller row vectors.

        Accepts vectors in either the PADDED coordinates
        (``n_chunks * chunk_rows``) or the true unpadded ``n_rows_data``;
        unpadded vectors are zero-extended so the final ragged chunk's
        padding rows carry weight/residual 0 — the ``data/pipeline.py``
        chunk contract (before this normalization an unpadded vector
        silently produced a short final slice and a shape error deep in
        the einsum).
        """
        n_pad = self.n_chunks * self.chunk_rows
        host = []
        for v in vecs:
            a = np.asarray(v, np.float32)
            if a.shape[0] == self.n_rows_data and a.shape[0] != n_pad:
                a = np.pad(a, (0, n_pad - a.shape[0]))
            elif a.shape[0] != n_pad:
                raise ValueError(
                    f"row vector has length {a.shape[0]}; expected the "
                    f"unpadded {self.n_rows_data} or padded {n_pad}")
            host.append(a)
        for i, Xc in self.iter_chunks():
            sl = self.row_slice(i)
            yield Xc, tuple(jnp.asarray(a[sl]) for a in host)

    def tile_gram(self, tid, w, r, *, backend=None):
        T = self.tile_size
        G = jnp.zeros((T, T), jnp.float32)
        g = jnp.zeros((T,), jnp.float32)
        c0 = int(tid) * T
        for Xc, (wc, rc) in self._row_chunks(w, r):
            Xt = Xc[:, c0:c0 + T]
            G = G + (Xt * wc[:, None]).T @ Xt
            g = g + Xt.T @ rc
        return G, g

    def tile_matvec(self, tid, v_t):
        T = self.tile_size
        c0 = int(tid) * T
        parts = [Xc[:, c0:c0 + T] @ jnp.asarray(v_t)
                 for _, Xc in self.iter_chunks()]
        return jnp.concatenate(parts)

    def all_tile_grams(self, w, r, *, backend=None):
        nt, T = self.n_tiles, self.tile_size
        G_all = jnp.zeros((nt, T, T), jnp.float32)
        g_all = jnp.zeros((nt, T), jnp.float32)
        for Xc, (wc, rc) in self._row_chunks(w, r):
            Xr = Xc.reshape(self.chunk_rows, nt, T)
            G_all = G_all + jnp.einsum("nti,ntj->tij", Xr * wc[:, None, None],
                                       Xr)
            g_all = g_all + (Xc.T @ rc).reshape(nt, T)
        return G_all, g_all

    def full_gram(self, w, r):
        """(XᵀWX (p_pad, p_pad), Xᵀr (p_pad,)) accumulated over chunks — the
        chunked-statistics form the streaming solver consumes (the full
        Gram carries the cross-tile coupling the Gauss-Seidel sweep needs;
        device footprint is p_pad², the streaming contract's n ≫ p regime)."""
        p = self.p_pad
        G = jnp.zeros((p, p), jnp.float32)
        g = jnp.zeros((p,), jnp.float32)
        for Xc, (wc, rc) in self._row_chunks(w, r):
            G = G + (Xc * wc[:, None]).T @ Xc
            g = g + Xc.T @ rc
        return G, g

    def matvec(self, v):
        v = jnp.asarray(v)
        return jnp.concatenate([Xc @ v for _, Xc in self.iter_chunks()])

    def rmatvec(self, r):
        out = jnp.zeros((self.p_pad,), jnp.float32)
        for Xc, (rc,) in self._row_chunks(r):
            out = out + Xc.T @ rc
        return out

    def col_moments(self, weights):
        s1 = jnp.zeros((self.p_pad,), jnp.float32)
        s2 = jnp.zeros((self.p_pad,), jnp.float32)
        for Xc, (wc,) in self._row_chunks(weights):
            s1 = s1 + Xc.T @ wc
            s2 = s2 + (Xc * Xc).T @ wc
        return s1, s2

    def to_dense(self):
        """Materialize ALL chunks (tests / tiny data only)."""
        return jnp.concatenate([Xc for _, Xc in self.iter_chunks()], axis=0)


def streaming_design(X, tile_size: int, *, chunk_rows: int,
                     n_rows: Optional[int] = None,
                     n_cols: Optional[int] = None):
    """(StreamingDesign, DesignInfo) from an (n, p) host array-like or a
    chunk-producing callable.

    Array input: chunks are host slices (zero host copies beyond the chunk
    staging buffer).  Callable input: ``X(i)`` must return chunk ``i``'s raw
    rows — a pure function of ``i`` so resumes replay identically — and
    ``n_rows``/``n_cols`` are required.  The column layout is the identity
    (features keep their order; tile padding trails), so no column map is
    needed to unpack β.
    """
    if isinstance(X, SparseCOO):
        raise ValueError(
            "StreamingDesign chunks are dense device buffers; stream a "
            "sparse source by passing a callable that densifies chunk i "
            "(rows beyond device memory amortize the densification)")
    if callable(X) and not hasattr(X, "shape"):
        if n_rows is None or n_cols is None:
            raise ValueError(
                "callable chunk sources need explicit n_rows/n_cols")
        design = StreamingDesign(X, n_rows=n_rows, n_cols=n_cols,
                                 chunk_rows=chunk_rows, tile_size=tile_size)
        return design, DesignInfo(shape=(n_rows, n_cols))
    Xh = np.asarray(X, np.float32)
    n, p = Xh.shape
    design = StreamingDesign(
        lambda i, _X=Xh, _cr=chunk_rows: _X[i * _cr:(i + 1) * _cr],
        n_rows=n, n_cols=p, chunk_rows=chunk_rows, tile_size=tile_size)
    return design, DesignInfo(shape=(n, p))


# ---------------------------------------------------------------------------
# host-side builders
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class DesignInfo:
    """Build metadata the drivers need to map results back.

    col_of_feature[j] = packed-layout column of original feature j (None when
    the layout is the identity).  ``occupancy`` is the non-empty-brick
    fraction — the efficiency figure deciding bricks-vs-dense (DESIGN.md §2).
    """
    shape: tuple
    col_of_feature: Optional[np.ndarray] = None
    occupancy: float = 1.0
    n_bricks: int = 0
    tail_counts: Optional[np.ndarray] = None   # HeadTailDesign's per column

    def unpack_beta(self, beta_packed: np.ndarray) -> np.ndarray:
        p = self.shape[1]
        if self.col_of_feature is None:
            return np.asarray(beta_packed)[:p]
        return np.asarray(beta_packed)[self.col_of_feature]

    def pack_beta(self, beta: np.ndarray, p_padded: int) -> np.ndarray:
        return self.pack_cols(beta, p_padded, fill=0.0)

    def pack_cols(self, values: np.ndarray, p_padded: int,
                  fill: float = 0.0) -> np.ndarray:
        """Scatter a per-original-feature vector into packed column order;
        padding columns get ``fill`` (0 for β, 1 for penalty factors /
        scales)."""
        out = np.full((p_padded,), fill, np.float32)
        if self.col_of_feature is None:
            out[:len(values)] = values
        else:
            out[self.col_of_feature] = values
        return out


def _shard_bricks(rows, cols, vals, n_loc, p_loc, tile_size, row_block):
    """Brick arrays for ONE shard's COO triplet (already in local coords)."""
    n_rb = n_loc // row_block
    n_tiles = p_loc // tile_size
    rb_ids = rows // row_block
    tile_ids = cols // tile_size
    key = tile_ids.astype(np.int64) * n_rb + rb_ids
    order = np.argsort(key, kind="stable")
    key, rows, cols, vals = key[order], rows[order], cols[order], vals[order]
    ukeys, inv = np.unique(key, return_inverse=True)
    B = max(len(ukeys), 1)
    bricks = np.zeros((B, row_block, tile_size), np.float32)
    if len(ukeys):
        bricks[inv, rows % row_block, cols % tile_size] = vals
    brick_tile = (ukeys // n_rb).astype(np.int32)
    brick_row = (ukeys % n_rb).astype(np.int32)
    if not len(ukeys):
        brick_tile = np.zeros((1,), np.int32)
        brick_row = np.zeros((1,), np.int32)
    tile_ptr = np.searchsorted(brick_tile, np.arange(n_tiles + 1)) \
        .astype(np.int32)
    if not len(ukeys):
        tile_ptr[:] = 0
    return bricks, brick_row, brick_tile, tile_ptr, len(ukeys)


def _pack_layout(coo: SparseCOO, M: int, tile_size: int, reorder: bool):
    """Global column layout: frequency-sort features into tiles, then deal
    whole tiles round-robin over the M feature shards (load balance).

    Returns (col_of_feature (p,), packed_cols for every nnz, p_loc)."""
    p = coo.shape[1]
    p_pad = p + ((-p) % (M * tile_size))
    n_tiles_g = p_pad // tile_size
    p_loc = p_pad // M
    freq = coo.col_frequency_order() if reorder else np.arange(p)
    # freq[c] = original feature at frequency-rank c
    rank_of = np.empty(p, np.int64)
    rank_of[freq] = np.arange(p)
    ranks = np.arange(p_pad, dtype=np.int64)
    tile_g = ranks // tile_size
    # tile g -> shard g % M, local tile g // M  (round-robin deal)
    pos = (tile_g % M) * p_loc + (tile_g // M) * tile_size + ranks % tile_size
    col_of_feature = pos[rank_of]
    return col_of_feature.astype(np.int64), p_loc


def build_block_sparse_sharded(coo: SparseCOO, *, D: int, M: int,
                               tile_size: int, row_block: int = 256,
                               reorder: bool = True):
    """Pack a host SparseCOO into the (D, M)-sharded brick layout.

    Never materializes the dense (n, p) matrix: per-shard COO triplets are
    bricked independently; shards are padded to a common brick count B and a
    common per-tile bound K (the static SPMD bounds) and stacked into
    (D, M, ...) host arrays ready for ``jax.device_put`` with a
    ``P(axis_data, axis_model, None, ...)`` sharding.

    Returns (BlockSparseDesign with leading=2, DesignInfo).
    """
    coo = coo.dedupe()
    n, p = coo.shape
    col_of_feature, p_loc = _pack_layout(coo, M, tile_size, reorder)
    n_loc = -(-n // (D * row_block)) * row_block
    n_tiles_local = p_loc // tile_size

    packed_cols = col_of_feature[coo.cols]
    shard_m = packed_cols // p_loc
    shard_d = coo.rows // n_loc

    parts = []
    for d in range(D):
        for m in range(M):
            sel = (shard_d == d) & (shard_m == m)
            parts.append(_shard_bricks(
                coo.rows[sel] - d * n_loc, packed_cols[sel] - m * p_loc,
                coo.vals[sel].astype(np.float32),
                n_loc, p_loc, tile_size, row_block))

    B = max(pt[0].shape[0] for pt in parts)
    K = max(int(np.diff(pt[3]).max(initial=0)) for pt in parts)
    K = max(K, 1)
    total_bricks = sum(pt[4] for pt in parts)
    # uniform occupancy (host-static): every tile of every shard holds
    # exactly K tile-major-contiguous bricks — the fused superstep's
    # zero-copy batched layout applies (DESIGN.md §8)
    uniform = all(pt[4] == n_tiles_local * K
                  and np.all(np.diff(pt[3]) == K) for pt in parts)

    def pad_stack(i, fill=0):
        arrs = []
        for pt in parts:
            a = pt[i]
            pad = B - a.shape[0]
            if pad:
                a = np.concatenate(
                    [a, np.full((pad,) + a.shape[1:], fill, a.dtype)])
            arrs.append(a)
        return np.stack(arrs).reshape((D, M) + arrs[0].shape)

    bricks = pad_stack(0)
    brick_row = pad_stack(1)
    brick_tile = pad_stack(2)
    tile_ptr = np.stack([pt[3] for pt in parts]).reshape(D, M, -1)

    design = BlockSparseDesign(
        jnp.asarray(bricks), jnp.asarray(brick_row),
        jnp.asarray(brick_tile), jnp.asarray(tile_ptr),
        tile_size, row_block, n_loc, n_tiles_local, K, leading=2,
        uniform_K=uniform)
    n_rb_total = (n_loc // row_block) * D
    occ = total_bricks / max(n_rb_total * n_tiles_local * M, 1)
    info = DesignInfo(shape=(n, p), col_of_feature=col_of_feature,
                      occupancy=occ, n_bricks=total_bricks)
    return design, info


def build_block_sparse(coo: SparseCOO, tile_size: int, *,
                       row_block: int = 256, reorder: bool = True):
    """Single-shard brick packing: (BlockSparseDesign leading=0, DesignInfo)."""
    design, info = build_block_sparse_sharded(
        coo, D=1, M=1, tile_size=tile_size, row_block=row_block,
        reorder=reorder)
    return design.localize(), info


def brick_occupancy(coo: SparseCOO, tile_size: int, *, row_block: int = 256,
                    reorder: bool = True) -> float:
    """Non-empty-brick fraction of the packed layout, from the COO keys
    alone — no brick values are materialized (cheap stats/reporting)."""
    coo = coo.dedupe()
    col_of_feature, p_loc = _pack_layout(coo, 1, tile_size, reorder)
    n_rb = -(-coo.shape[0] // row_block)
    n_tiles = p_loc // tile_size
    keys = (col_of_feature[coo.cols] // tile_size) * n_rb \
        + coo.rows // row_block
    return len(np.unique(keys)) / max(n_rb * n_tiles, 1)


def dense_design(X, tile_size: int):
    """(DenseDesign, DesignInfo) from an (n, p) array; pads features with
    inert zero columns to a tile multiple.  Device-resident inputs stay on
    device (jnp ops only — no host round-trip)."""
    Xj = jnp.asarray(X, jnp.float32)
    n, p = Xj.shape
    pad = (-p) % tile_size
    if pad:
        Xj = jnp.pad(Xj, ((0, 0), (0, pad)))
    # row-padded tile-major cache for the fused superstep (materialized
    # eagerly, once per session — DenseDesign.tiles3)
    data_t = _tile_major(Xj, tile_size)
    return DenseDesign(Xj, tile_size, data_t), DesignInfo(shape=(n, p))


def as_design(X, tile_size: int, *, row_block: int = 256,
              reorder: bool = True, info: Optional[DesignInfo] = None,
              head_features: Optional[int] = None):
    """Coerce any supported input into (DesignMatrix, DesignInfo).

    ``SparseRows`` pack into a ``HeadTailDesign`` with ``head_features``
    dense columns, which they require: a brick build of hashed rows on the
    host would not fit.

    A pre-built ``BlockSparseDesign`` must come with the ``DesignInfo`` its
    builder returned — the brick layout permutes columns (frequency packing
    + tile dealing), so without it β could not be mapped back to the
    original feature order.
    """
    if isinstance(X, BlockSparseDesign):
        if X.leading != 0:
            raise ValueError(
                "mesh-sharded BlockSparseDesign (leading mesh axes) passed "
                "to the single-device path; use fit_sharded, or build with "
                "build_block_sparse for one device")
        if info is None:
            raise ValueError(
                "pre-built BlockSparseDesign requires the DesignInfo "
                "returned by its builder (pass design_info=...); the brick "
                "layout reorders columns and beta must be unpacked with it")
        return X, info
    if isinstance(X, StreamingDesign):
        # The identity column layout makes the info canonical, so ALWAYS
        # rebuild it from the design: a caller-supplied info can be stale —
        # fit_intercept appends a ones column via with_ones_column() AFTER
        # the builder returned its info, and honoring the old shape would
        # silently treat the last real feature as the intercept.
        return X, DesignInfo(shape=(X.n_rows_data, X.p_user))
    if isinstance(X, DesignMatrix):
        if info is None:
            raise ValueError(
                "pre-built designs require the DesignInfo returned by their "
                "builder (pass design_info=...) so beta can be mapped back "
                "to the original feature count/order")
        return X, info
    if isinstance(X, SparseRows):
        if head_features is None:
            raise ValueError(
                "SparseRows input needs DGLMNETConfig.head_features: the "
                "number of most frequent features packed dense (a multiple "
                "of tile_size); the rest form the sparse tail")
        return head_tail_design(X, tile_size, head_features)
    if isinstance(X, SparseCOO):
        return build_block_sparse(X, tile_size, row_block=row_block,
                                  reorder=reorder)
    return dense_design(X, tile_size)


def as_local_design(X, tile_size: int) -> DesignMatrix:
    """Inside jit/shard_map: wrap a raw local array, or localize a design."""
    if isinstance(X, DesignMatrix):
        return X.localize()
    return DenseDesign(X, tile_size)
