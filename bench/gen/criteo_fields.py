"""Hashed multi-field click rows made on the device: the layout of the
LIBSVM ``criteo`` set, every row one value of each of its fields, each value
hashed into one feature space.

Row i holds, for field f, the value v = floor(x) - 1 of a draw x from the
power law of exponent ``zipf_exponent`` on [1, card_f + 1) (a continuous
Zipf, so value 0 is the most popular); its feature is a 32-bit mix of
(f, v) modulo ``features``.  Two values may hash to one feature, in one row
too, where their ones add up.  Integer fields are binned to
``integer_bins`` values, categorical fields take their published
cardinality.  Every nonzero is 1.0.

Labels come from a planted logistic model: each of a field's
``effect_values`` most popular values carries, with probability
``effect_share``, a normal(0, ``effect_sd``) effect; the intercept is set by
bisection so that a ``click_rate`` share of labels is positive for the
labels' own uniform draws.

The dataset is one fixed set of rows, made from the configuration's
``data_seed``, as a deployment fits one dataset: ``--seed`` does not change
it.  ``margins`` and ``rmatvec`` are the reference's float64 products over
the generator's own copy of the pairs.

Where the solver fits an unpenalized intercept (``fit_intercept``), the
coefficients it returns leave the intercept out, and ``margins`` adds to
X β the intercept that minimizes the logistic loss at X β, found in
float64 (the profile of the intercept).  The reference's KKT residual and
objective are then those of the problem with its intercept: at a solution
the intercept's own condition holds exactly, and the other coordinates'
gradients are those at the optimal intercept.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.sparse import SparseRows

_REF_BLOCK = 1 << 16          # rows per block of the float64 products
_PROFILE_STEPS = 100          # safeguarded Newton steps of the intercept


def seed_key(seed: int):
    """A JAX key from a seed of any size (more than 32 bits fold in)."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def cardinalities(data: dict) -> np.ndarray:
    return np.asarray([int(data["integer_bins"])] * int(data["integer_fields"])
                      + [int(c) for c in data["categorical_cardinalities"]],
                      np.int64)


def _mix(field, value):
    """32-bit hash of (field, value) (murmur3's finaliser)."""
    h = value.astype(jnp.uint32) * jnp.uint32(0x9E3779B1) ^ (
        field.astype(jnp.uint32) * jnp.uint32(0x85EBCA6B)
        + jnp.uint32(0xC2B2AE35))
    h = (h ^ (h >> 16)) * jnp.uint32(0x7FEB352D)
    h = (h ^ (h >> 15)) * jnp.uint32(0x846CA68B)
    return h ^ (h >> 16)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6, 7, 8))
def _make(key, card, n: int, p: int, zipf: float, n_eff: int,
          eff_share: float, eff_sd: float, click_rate: float):
    """(ids (n, F) i32, labels (n,) ±1, intercept)."""
    F = card.shape[0]
    kv, ke, km, ky = jax.random.split(key, 4)
    u = jax.random.uniform(kv, (n, F))
    top = (card.astype(jnp.float32) + 1.0) ** (1.0 - zipf)
    x = (1.0 - u * (1.0 - top)) ** (1.0 / (1.0 - zipf))
    value = jnp.clip(jnp.floor(x).astype(jnp.int32) - 1, 0, card - 1)
    field = jnp.arange(F, dtype=jnp.int32)[None, :]
    ids = (_mix(field, value) % jnp.uint32(p)).astype(jnp.int32)
    effects = jnp.where(jax.random.uniform(km, (F, n_eff)) < eff_share,
                        eff_sd * jax.random.normal(ke, (F, n_eff)), 0.0)
    hit = value < n_eff
    margin = jnp.sum(jnp.where(hit, effects[field, jnp.where(hit, value, 0)],
                               0.0), axis=1)
    uy = jax.random.uniform(ky, (n,))

    def rate(b0):
        return jnp.mean((uy < jax.nn.sigmoid(margin + b0)).astype(jnp.float32))

    def halve(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        low = rate(mid) < click_rate
        return jnp.where(low, mid, lo), jnp.where(low, hi, mid)

    lo, hi = jax.lax.fori_loop(0, 60, halve, (jnp.float32(-30.0),
                                              jnp.float32(30.0)))
    b0 = 0.5 * (lo + hi)
    y = jnp.where(uy < jax.nn.sigmoid(margin + b0), 1.0, -1.0)
    return ids, y.astype(jnp.float32), b0


@dataclasses.dataclass
class SparseRowsProblem:
    X: object              # SparseRows on the device, until released
    y: np.ndarray          # (n,) float32 in {-1, +1}
    shape: tuple
    make: tuple            # the arguments of ``_make`` that made the rows
    head_features: int
    intercept: float       # the planted model's
    fit_intercept: bool = False
    kind: str = "sparse_rows"
    _host: tuple = None    # (ids, vals) on the host, made on first use
    _counts: tuple = None  # (mean head-tile sum of squared row nnz, tail nnz)

    def release(self):
        """Drop the device copy of the pairs (the program holds its own)."""
        self.X = None

    def pairs(self):
        """(ids (n, F) int64, vals (n, F) float64) on the host."""
        if self._host is None:
            ids = np.asarray(_make(*self.make)[0], np.int64)
            self._host = (ids, np.ones(ids.shape, np.float64))
        return self._host

    def _nnz_counts(self, tile_size):
        """The mean over head tiles of the sum over rows of the squared
        nonzeros a row has in the tile, and the tail's nonzeros, with the
        head the ``head_features`` most frequent features (ties by id)."""
        if self._counts is None:
            ids, vals = self.pairs()
            n, p = self.shape
            if self.fit_intercept:
                # the solver's intercept: one more feature, in every row
                ids = np.concatenate([ids, np.full((n, 1), p)], axis=1)
                vals = np.concatenate([vals, np.ones((n, 1))], axis=1)
                p += 1
            H = self.head_features
            counts = np.bincount(ids.ravel(), weights=(vals != 0).ravel(),
                                 minlength=p)
            rank = np.empty(p, np.int64)
            rank[np.argsort(-counts, kind="stable")] = np.arange(p)
            r = rank[ids]
            head = (r < H) & (vals != 0)
            nt = H // tile_size
            cell = np.bincount((np.arange(n)[:, None] * nt
                                + r // tile_size)[head], minlength=n * nt)
            self._counts = (float(np.sum(cell.astype(np.float64) ** 2)) / nt,
                            float(np.sum((~head) & (vals != 0))))
        return self._counts

    def superstep_flops(self, tile_size, live_tiles):
        """Operations one superstep's mathematics needs on these rows: the
        Grams of ``live_tiles`` head tiles (2 Σ_i nnz_i,t² each, at the
        head tiles' mean), the tail's gradient and diagonal Hessian and its
        margin delta (4 per tail nonzero), and the head's gradient and
        margin delta (2 n H each)."""
        gram, tail = self._nnz_counts(tile_size)
        n = self.shape[0]
        return float(2.0 * gram * min(float(live_tiles),
                                       self.head_features // tile_size)
                     + 4.0 * tail + 4.0 * n * self.head_features)

    def _blocks(self):
        ids, vals = self.pairs()
        for r0 in range(0, ids.shape[0], _REF_BLOCK):
            yield r0, ids[r0:r0 + _REF_BLOCK], vals[r0:r0 + _REF_BLOCK]

    def margins(self, betas):
        """X @ betas for a (p, m) stack of coefficient vectors, float64,
        plus each one's optimal intercept where the solver fits one."""
        B = np.asarray(betas, np.float64)
        M = np.concatenate([np.einsum("ik,ikm->im", v, B[i])
                            for _, i, v in self._blocks()])
        return M + self.profile_intercepts(M) if self.fit_intercept else M

    def profile_intercepts(self, M):
        """(m,) float64: for each column of the (n, m) margins, the b
        that minimizes Σ_i log(1 + exp(-y_i (M_i + b))), where the
        derivative Σ_i (σ(M_i + b) − [y_i > 0]) is zero.  The derivative
        grows with b, so Newton steps kept inside a shrinking bracket
        converge."""
        t = (np.asarray(self.y) > 0).astype(np.float64)[:, None]
        lo = np.full(M.shape[1], -50.0)
        hi = np.full(M.shape[1], 50.0)
        b = np.zeros(M.shape[1])
        for _ in range(_PROFILE_STEPS):
            q = 0.5 * (1.0 + np.tanh(0.5 * (M + b)))     # σ(M + b)
            g = np.sum(q - t, axis=0)
            h = np.sum(q * (1.0 - q), axis=0)
            lo = np.where(g < 0, b, lo)
            hi = np.where(g > 0, b, hi)
            step = b - g / np.maximum(h, 1e-300)
            nxt = np.where((step > lo) & (step < hi), step, 0.5 * (lo + hi))
            if np.all(np.abs(nxt - b) <= 1e-13 * np.maximum(1.0, np.abs(b))):
                return nxt
            b = nxt
        return b

    def rmatvec(self, S):
        """X.T @ S for an (n, m) stack of row vectors, float64."""
        S = np.asarray(S, np.float64)
        out = np.zeros((self.shape[1], S.shape[1]))
        for r0, i, v in self._blocks():
            s = S[r0:r0 + i.shape[0]]
            for j in range(S.shape[1]):
                out[:, j] += np.bincount(i.ravel(),
                                         weights=(v * s[:, j:j + 1]).ravel(),
                                         minlength=self.shape[1])
        return out


def generate(config: dict, traffic: dict, seed: int, mesh_shape):
    """The cell's rows on the device; the same for every ``seed``."""
    d = config["data"]
    if tuple(mesh_shape) != (1, 1):
        raise ValueError("the hashed rows are made for one chip")
    n, p = int(d["rows"]), int(d["features"])
    card = cardinalities(d)
    make = (seed_key(int(d["data_seed"])), jnp.asarray(card, jnp.int32), n,
            p, float(d["zipf_exponent"]), int(d["effect_values"]),
            float(d["effect_share"]), float(d["effect_sd"]),
            float(d["click_rate"]))
    fit_intercept = bool(config["solver"].get("fit_intercept", False))
    if fit_intercept and config["family"] != "logistic":
        raise ValueError("the intercept's profile is the logistic loss's")
    ids, y, b0 = _make(*make)
    X = SparseRows(ids, jnp.ones(ids.shape, jnp.float32), p)
    return SparseRowsProblem(X, np.asarray(y), (n, p), make,
                             int(config["solver"]["head_features"]),
                             float(b0), fit_intercept)
