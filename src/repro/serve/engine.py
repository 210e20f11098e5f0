"""Batched GLM scoring over a loaded artifact (DESIGN.md §7).

The engine turns an immutable ``ServableModel`` into the serving compute
path.  Two ideas carry it:

**Active-set compaction.**  An L1-regularized model's coefficient table is
mostly zeros — that is what the penalty bought.  At construction the K
output columns are scanned once for their JOINT support A = {j : any
column has β_j ≠ 0}; the table is compacted to (A+1, K) with a trailing
all-zero row, and a (p+1,)-entry feature→slot lookup maps original feature
ids onto it (unknown / inactive / padding features → the zero row, so
scoring needs no predication anywhere).  Dense rows are sliced to the
active columns before the dot; sparse requests are remapped through the
lookup on host (O(nnz) int gather) and scored by the fused
gather-dot-link kernel (``kernels/predict_tile.py`` via
``ops.predict_tile``) in ONE device launch — gather, dot, intercept and
inverse link fused, all K outputs (several λs / several stacked models)
per launch for A/B and path-selection traffic.

**Bounded shape set.**  Every jitted program is keyed on (batch rows,
padded nnz, kind); callers that pad to a fixed bucket grid (the
micro-batcher, ``serve/batcher.py``) therefore re-jit only on the first
visit to each bucket and never in steady state.  ``compile_count`` exposes
the number of distinct compiled shapes for tests and the benchmark.

Engines are cheap to build and stateless after construction (all mutable
state is the jit cache), so one engine serves concurrent callers.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.data.sparse import SparseCOO
from repro.kernels import ops
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.serve.artifact import ServableModel


def _as_request(r):
    """Coerce one sparse request to (idx i64, val f32) arrays; a length
    mismatch is rejected here — numpy would otherwise BROADCAST a short
    value vector into every slot and score silent garbage."""
    idx, val = r
    idx = np.asarray(idx, np.int64).ravel()
    val = np.asarray(val, np.float32).ravel()
    if idx.shape != val.shape:
        raise ValueError(
            f"request feature ids and values disagree: {idx.shape} vs "
            f"{val.shape}")
    return idx, val


def coo_to_requests(X: SparseCOO):
    """Split a SparseCOO into per-row (idx, val) feature-list requests."""
    order = np.argsort(X.rows, kind="stable")
    rows, cols = X.rows[order], X.cols[order]
    vals = np.asarray(X.vals, np.float32)[order]
    starts = np.searchsorted(rows, np.arange(X.shape[0]))
    ends = np.searchsorted(rows, np.arange(X.shape[0]), side="right")
    return [(cols[s:e], vals[s:e]) for s, e in zip(starts, ends)]


class ScoringEngine:
    """Scores dense rows and sparse feature-list requests against one
    active-set-compacted weight table.

    Args:
      model: loaded ``ServableModel`` (or anything shaped like one).
      outputs: optional column subset to serve (indices into the model's K
        outputs) — e.g. the CV-selected λ plus a challenger.
      backend: kernel backend override (None = per-jax-backend default,
        "ref" = jnp oracle — the automatic fallback off-TPU).
    """

    def __init__(self, model: ServableModel, *, outputs=None, backend=None):
        compile_cache.init()
        self.model = model
        self.family = model.family
        W = np.asarray(model.betas, np.float32)          # (K, p)
        b0 = np.asarray(model.intercepts, np.float32)    # (K,)
        if outputs is not None:
            sel = np.atleast_1d(np.asarray(outputs, np.int64))
            W, b0 = W[sel], b0[sel]
        self.n_outputs = int(W.shape[0])
        self.n_features = int(W.shape[1])
        self._backend = backend

        # joint support across the served columns; slot p.. = zero row
        active = np.flatnonzero(np.any(W != 0.0, axis=0))
        self.active = active
        self.n_active = int(active.size)
        table = np.zeros((self.n_active + 1, self.n_outputs), np.float32)
        table[:-1] = W[:, active].T
        self._table = jnp.asarray(table)
        self._b0 = jnp.asarray(b0.reshape(1, -1))
        slot = np.full((self.n_features + 1,), self.n_active, np.int64)
        slot[active] = np.arange(self.n_active)
        self._slot = slot          # host lookup: feature id -> table row
        self._dense_fn = None
        self._packed_fns: dict = {}

    # ------------------------------------------------------------- plumbing

    @property
    def compile_count(self) -> int:
        """Number of distinct compiled sparse-scoring shapes so far — the
        batcher's bounded-bucket contract is asserted against this."""
        return len(self._packed_fns)

    def _check_kind(self, kind):
        if kind not in ("link", "response"):
            raise ValueError(f"unknown kind {kind!r}; use 'link' or "
                             "'response'")

    def map_slots(self, idx: np.ndarray) -> np.ndarray:
        """Original feature ids → compacted table rows (inactive or
        out-of-range ids → the zero row)."""
        idx = np.asarray(idx, np.int64)
        safe = np.where((idx >= 0) & (idx < self.n_features), idx,
                        self.n_features)
        return self._slot[safe]

    def pack_requests(self, requests: Sequence, nnz_pad: Optional[int] = None):
        """Pad sparse requests to one (B, J) slot/value pair of arrays.

        ``nnz_pad``: target J (≥ the max request nnz; the batcher passes a
        bucket size so the compiled-shape set stays bounded).  Slots pad
        with the zero row, values with 0 — padding scores exactly 0.
        """
        reqs = [_as_request(r) for r in requests]
        max_nnz = max((len(i) for i, _ in reqs), default=0)
        J = max(max_nnz, 1) if nnz_pad is None else int(nnz_pad)
        if max_nnz > J:
            raise ValueError(f"request nnz {max_nnz} exceeds nnz_pad {J}")
        B = len(reqs)
        slots = np.full((B, J), self.n_active, np.int32)
        vals = np.zeros((B, J), np.float32)
        for b, (idx, val) in enumerate(reqs):
            slots[b, :len(idx)] = self.map_slots(idx)
            vals[b, :len(idx)] = val
        return slots, vals

    # -------------------------------------------------------------- scoring

    def _packed_fn(self, shape, kind):
        key = (shape, kind)
        fn = self._packed_fns.get(key)
        if fn is None:
            fam, backend = self.family, self._backend

            def run(slots, vals, table, b0):
                return ops.predict_tile(slots, vals, table, b0, fam,
                                        kind=kind, backend=backend)

            fn = self._packed_fns[key] = jax.jit(run)
            # every new compiled shape is a steady-state smell: the
            # counter (and the trace instant) makes bucket leaks visible
            obs_metrics.counter("serve.compiled_shapes").inc()
            obs_trace.instant("serve/compile",
                              args={"shape": list(shape), "kind": kind})
        return fn

    def lower_packed(self, batch: int, nnz: int, *,
                     kind: str = "response"):
        """``jax.stages.Lowered`` of the scoring program for (batch, nnz)
        packed requests — the one launch ``score_packed`` runs."""
        self._check_kind(kind)
        slots = jax.ShapeDtypeStruct((batch, nnz), jnp.int32)
        vals = jax.ShapeDtypeStruct((batch, nnz), jnp.float32)
        return self._packed_fn((batch, nnz), kind).lower(
            slots, vals, self._table, self._b0)

    def score_packed(self, slots, vals, *, kind: str = "response"):
        """Score pre-packed (B, J) slot/value arrays → (B, K) np.float32.
        THE one device launch of the sparse path; everything else routes
        here."""
        self._check_kind(kind)
        fn = self._packed_fn(tuple(slots.shape), kind)
        out = fn(jnp.asarray(slots), jnp.asarray(vals), self._table,
                 self._b0)
        return np.asarray(out)

    def score_sparse(self, requests: Sequence, *, kind: str = "response",
                     nnz_pad: Optional[int] = None, offset=None):
        """Score a batch of (idx, val) feature-list requests → (B, K).
        Without an offset the inverse link is fused into the kernel
        launch; with one, margins come back and the link applies after the
        offset."""
        self._check_kind(kind)
        slots, vals = self.pack_requests(requests, nnz_pad)
        if offset is None:
            return self.score_packed(slots, vals, kind=kind)
        return self._finish(self.score_packed(slots, vals, kind="link"),
                            kind, offset)

    def score_coo(self, X: SparseCOO, *, kind: str = "response",
                  offset=None, chunk_rows: int = 4096,
                  launch_budget: int = 1 << 22):
        """Score the rows of a SparseCOO without densifying: split into
        feature-list requests, remap to the active set, fused launches.

        Rows are processed in windows of at most ``chunk_rows``, each
        padded to ITS OWN max nnz (rounded up to a power of two so
        repeated calls reuse compiled shapes), with the window ALSO
        capped so ``rows × padded_nnz × outputs ≤ launch_budget``
        elements: a near-dense row lands in a small window of its own
        instead of widening thousands of neighbours — the memory of one
        launch (and of the oracle backend's (B, J, K) gather) stays
        bounded regardless of row-size skew, and total work stays
        O(Σ padded nnz) like the host matvec this replaces.
        """
        if X.shape[1] > self.n_features:
            raise ValueError(
                f"request has {X.shape[1]} features; model serves "
                f"{self.n_features}")
        reqs = coo_to_requests(X)
        off = None if offset is None else \
            np.asarray(offset, np.float32).reshape(-1)
        K = max(self.n_outputs, 1)

        def pow2(x):
            return 1 << max(int(x) - 1, 0).bit_length()

        outs = []
        empty = (np.zeros((0,), np.int64), np.zeros((0,), np.float32))
        s = 0
        while s < len(reqs):
            J = pow2(max(len(reqs[s][0]), 1))
            e = s + 1
            while e < len(reqs) and e - s < chunk_rows:
                J_new = max(J, pow2(max(len(reqs[e][0]), 1)))
                if (e - s + 1) * J_new * K > launch_budget:
                    break
                J = J_new
                e += 1
            n = e - s
            B = min(pow2(n), chunk_rows)
            chunk = reqs[s:e] + [empty] * (B - n)
            off_c = None
            if off is not None:
                off_c = np.zeros((B,), np.float32)
                off_c[:n] = off[s:e]
            outs.append(self.score_sparse(chunk, kind=kind, nnz_pad=J,
                                          offset=off_c)[:n])
            s = e
        if not outs:
            return np.zeros((0, self.n_outputs), np.float32)
        return np.concatenate(outs, axis=0)

    def score_dense(self, X, *, kind: str = "response", offset=None):
        """Score dense rows (n, p) → (n, K), compacted to the active
        columns before the dot (identical results to the full-β product —
        the inactive columns multiply exact zeros)."""
        self._check_kind(kind)
        X = np.asarray(X, np.float32)
        if self._dense_fn is None:
            def dense(xa, table, b0):
                # table is (A+1, K) with a zero last row; slice it off
                return xa @ table[:-1] + b0

            self._dense_fn = jax.jit(dense)
        m = np.asarray(self._dense_fn(jnp.asarray(X[:, self.active]),
                                      self._table, self._b0))
        return self._finish(m, kind, offset)

    def score(self, X, *, kind: str = "response", offset=None):
        """Polymorphic entry: SparseCOO → fused sparse path, list of
        (idx, val) requests → sparse path, array → dense path."""
        if isinstance(X, SparseCOO):
            return self.score_coo(X, kind=kind, offset=offset)
        if isinstance(X, (list, tuple)):
            return self.score_sparse(X, kind=kind, offset=offset)
        return self.score_dense(X, kind=kind, offset=offset)

    def _finish(self, m: np.ndarray, kind: str, offset):
        """Apply a per-row margin offset (broadcast over outputs), then the
        inverse link when asked for responses."""
        if offset is not None:
            m = m + np.asarray(offset, np.float32).reshape(-1, 1)
        if kind == "link":
            return m
        from repro.core import glm
        fam = glm.resolve_family(self.family)
        return np.asarray(fam.predict(jnp.asarray(m)))
