"""Sparse design-matrix support.

The paper streams CSC columns on CPUs.  On TPU the equivalent is *blocked
densification* (DESIGN.md §2): the matrix is cut into (row-block × feature-
tile) bricks; empty bricks are skipped, non-empty ones are densified into
VMEM-shaped tiles.  This module provides:

  * ``SparseCOO`` — host container with exact matvec/rmatvec (reference),
    row/col slicing, and densification into the brick layout.
  * ``SparseRows`` — fixed-width (id, value) rows, the input of the
    dense-head / sparse-tail layout for hashed multi-field data.
  * ``to_dense_blocks`` — the (features-sorted-by-frequency) brick packing
    used by the distributed driver, plus occupancy stats for the roofline
    model (occupancy is what decides whether densified bricks beat pure
    gather on TPU — reported in EXPERIMENTS.md).
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class SparseCOO:
    rows: np.ndarray
    cols: np.ndarray
    vals: np.ndarray
    shape: tuple

    def dedupe(self) -> "SparseCOO":
        """Sum duplicate (row, col) entries."""
        key = self.rows.astype(np.int64) * self.shape[1] + self.cols
        order = np.argsort(key, kind="stable")
        key, rows, cols, vals = key[order], self.rows[order], \
            self.cols[order], self.vals[order]
        uniq, start = np.unique(key, return_index=True)
        sums = np.add.reduceat(vals, start)
        return SparseCOO(rows[start], cols[start], sums.astype(self.vals.dtype),
                         self.shape)

    @property
    def nnz(self) -> int:
        return int(self.vals.shape[0])

    def matvec(self, beta: np.ndarray) -> np.ndarray:
        out = np.zeros(self.shape[0], np.float64)
        np.add.at(out, self.rows, self.vals * beta[self.cols])
        return out.astype(np.float32)

    def rmatvec(self, v: np.ndarray) -> np.ndarray:
        out = np.zeros(self.shape[1], np.float64)
        np.add.at(out, self.cols, self.vals * v[self.rows])
        return out.astype(np.float32)

    def take_rows(self, idx: np.ndarray) -> "SparseCOO":
        remap = -np.ones(self.shape[0], np.int64)
        remap[idx] = np.arange(len(idx))
        keep = remap[self.rows] >= 0
        return SparseCOO(remap[self.rows[keep]], self.cols[keep],
                         self.vals[keep], (len(idx), self.shape[1]))

    def to_dense(self) -> np.ndarray:
        out = np.zeros(self.shape, np.float32)
        out[self.rows, self.cols] = self.vals
        return out

    def col_frequency_order(self) -> np.ndarray:
        """Feature permutation: most frequent first. Packing hot features
        into the same tiles maximizes brick occupancy (DESIGN.md §2)."""
        counts = np.bincount(self.cols, minlength=self.shape[1])
        return np.argsort(-counts, kind="stable")

    def permute_cols(self, perm: np.ndarray) -> "SparseCOO":
        inv = np.empty_like(perm)
        inv[perm] = np.arange(len(perm))
        return SparseCOO(self.rows, inv[self.cols], self.vals, self.shape)


@dataclasses.dataclass
class SparseRows:
    """Rows with a fixed number K of (feature id, value) pairs each — the
    LIBSVM layout of hashed multi-field data such as Criteo's (K = 39).

    ``ids`` (n, K) int32 and ``vals`` (n, K) float32, host (numpy) or
    device (jax) arrays; a pair with value 0 is padding, whatever its id.
    Repeated ids within a row add up.  ``GLMSolver`` packs this input on
    the device into a ``HeadTailDesign`` (``data/design.py``)."""
    ids: object
    vals: object
    n_features: int

    @property
    def shape(self):
        return (int(self.ids.shape[0]), int(self.n_features))

    def matvec(self, beta):
        """X @ beta as a gather-sum over each row's pairs (jnp, so device
        inputs stay on the device)."""
        import jax.numpy as jnp
        beta = jnp.asarray(beta, jnp.float32)
        return jnp.sum(jnp.asarray(self.vals, jnp.float32)
                       * beta[jnp.asarray(self.ids)], axis=1)

    def to_coo(self) -> SparseCOO:
        """Host ``SparseCOO`` of the nonzero pairs (duplicates summed)."""
        ids, vals = np.asarray(self.ids), np.asarray(self.vals, np.float32)
        r, k = np.nonzero(vals != 0)
        return SparseCOO(r.astype(np.int64), ids[r, k].astype(np.int64),
                         vals[r, k], self.shape).dedupe()


def to_dense_blocks(X: SparseCOO, tile_size: int, *, reorder: bool = True):
    """Densify into the feature-tiled layout used by the CD sweep.

    Returns (dense (n, p_pad) float32, perm, occupancy) where ``occupancy``
    is the fraction of non-empty (row-block×tile) bricks that carry at least
    one nonzero — the efficiency figure for the densified TPU path.
    """
    perm = X.col_frequency_order() if reorder else np.arange(X.shape[1])
    Xp = X.permute_cols(perm)
    p_pad = X.shape[1] + ((-X.shape[1]) % tile_size)
    dense = np.zeros((X.shape[0], p_pad), np.float32)
    dense[Xp.rows, Xp.cols] = Xp.vals
    rb = 256
    n_rb = (X.shape[0] + rb - 1) // rb
    n_tb = p_pad // tile_size
    brick = np.zeros((n_rb, n_tb), bool)
    brick[Xp.rows // rb, Xp.cols // tile_size] = True
    occupancy = float(brick.mean())
    return dense, perm, occupancy
