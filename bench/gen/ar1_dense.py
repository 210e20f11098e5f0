"""Dense correlated design made on the device: AR(1) features, labels from
a planted sparse logistic model (the law of ``make_dense`` in the package's
``data/synthetic.py``).

Row i is ``z_i @ A`` with ``z_i`` standard normal and ``A`` the AR(1)
factor (x_0 = z_0, x_j = rho x_{j-1} + sqrt(1 - rho^2) z_j), so every
feature has unit variance and neighbouring features correlate by rho.

The dataset is one fixed set of rows, made from the configuration's
``data_seed``, as a deployment fits one dataset: ``--seed`` does not change
it.  (A seeded order of its rows would: the solver's stopping test sits at
float32 resolution of the objective, so a reordering changes rounding and
with it the supersteps a path takes, by up to 4 % on one v5e.)  The rows
are made in blocks, each from its own key, so the reference can make any
block again after the program has freed its copy.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

_HIGHEST = jax.lax.Precision.HIGHEST


def seed_key(seed: int):
    """A JAX key from a seed of any size (more than 32 bits fold in)."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def ar1_factor(p: int, rho: float):
    j = jnp.arange(p)
    lag = j[None, :] - j[:, None]                     # [k, j] = j - k
    coef = jnp.where(j[:, None] == 0, 1.0, jnp.sqrt(1.0 - rho * rho))
    return jnp.where(lag >= 0, coef * rho ** jnp.maximum(lag, 0), 0.0
                     ).astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _planted(key, p: int, k_true: int):
    """The planted coefficients: ``k_true`` normal(0, 2) at random
    features."""
    kb, kv = jax.random.split(key)
    support = jax.random.choice(kb, p, (k_true,), replace=False)
    return jnp.zeros((p,), jnp.float32).at[support].set(
        2.0 * jax.random.normal(kv, (k_true,), jnp.float32))


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _block(kx, ky, beta, index, block_rows: int, p: int, rho: float):
    """Row block ``index`` of the dataset: features and labels."""
    z = jax.random.normal(jax.random.fold_in(kx, index), (block_rows, p),
                          jnp.float32)
    X = jnp.dot(z, ar1_factor(p, rho), precision=_HIGHEST)
    margin = jnp.dot(X, beta, precision=_HIGHEST)
    u = jax.random.uniform(jax.random.fold_in(ky, index), (block_rows,))
    y = jnp.where(u < jax.nn.sigmoid(margin), 1.0, -1.0).astype(jnp.float32)
    return X, y


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _make(kx, ky, beta, n_blocks: int, block_rows: int, p: int, rho: float):
    """(X, y): every row block made into one buffer in one program, which
    holds no more than X and one block on the device."""
    def fill(i, xy):
        Xb, yb = _block(kx, ky, beta, i, block_rows, p, rho)
        r0 = i * block_rows
        return (jax.lax.dynamic_update_slice(xy[0], Xb, (r0, 0)),
                jax.lax.dynamic_update_slice(xy[1], yb, (r0,)))
    n = n_blocks * block_rows
    return jax.lax.fori_loop(0, n_blocks, fill,
                             (jnp.zeros((n, p), jnp.float32),
                              jnp.zeros((n,), jnp.float32)))


@dataclasses.dataclass
class DenseProblem:
    X: object             # (n, p) f32 on the device, until released
    y: np.ndarray         # (n,) float32 in {-1, +1}
    shape: tuple
    keys: tuple           # (features, labels) keys of the dataset
    beta: object          # the planted coefficients
    block_rows: int
    rho: float
    kind: str = "dense"

    def superstep_flops(self, tile_size, live_tiles):
        """Operations one superstep's mathematics needs: the Grams of
        ``live_tiles`` tiles (2 n T^2 each; the narrower last tile counted
        last), the gradient and the margin update (2 n p each)."""
        n, p = self.shape
        widths = np.minimum(tile_size, p - np.arange(0, p, tile_size))
        per_tile = 2.0 * n * widths.astype(np.float64) ** 2
        whole = int(live_tiles)
        gram = per_tile[:whole].sum()
        if whole < len(per_tile):
            gram += (live_tiles - whole) * per_tile[whole]
        return float(gram + 4.0 * n * p)

    def release(self):
        """Drop this copy of X (the program holds its own)."""
        self.X = None

    def block(self, i: int):
        """(features, labels) of row block ``i``."""
        return _block(*self.keys, self.beta, i, self.block_rows,
                      self.shape[1], self.rho)

    def blocks(self):
        for i in range(self.shape[0] // self.block_rows):
            yield i * self.block_rows, self.block(i)[0]

    def margins(self, betas):
        """X @ betas for a (p, m) stack of coefficient vectors."""
        B = jnp.asarray(betas, jnp.float32)
        return np.concatenate([np.asarray(jnp.dot(Xb, B, precision=_HIGHEST))
                               for _, Xb in self.blocks()]).astype(np.float64)

    def rmatvec(self, S):
        """X.T @ S for an (n, m) stack of row vectors."""
        S = jnp.asarray(S, jnp.float32)
        out = 0.0
        for r0, Xb in self.blocks():
            out = out + jnp.dot(Xb.T, S[r0:r0 + Xb.shape[0]],
                                precision=_HIGHEST)
        return np.asarray(out, np.float64)


def generate(config: dict, traffic: dict, seed: int, mesh_shape):
    """The cell's dataset on the device; the same for every ``seed``."""
    d = config["data"]
    n = int(d["rows"]) * mesh_shape[0]
    p = int(d["features"]) * mesh_shape[1]
    rb, rho = int(d["block_rows"]), float(d["rho"])
    if n % rb:
        raise ValueError(f"rows {n} are not a multiple of block_rows {rb}")
    kx, ky, kb = jax.random.split(seed_key(int(d["data_seed"])), 3)
    beta = _planted(kb, p, int(d["k_true"]))
    X, y = _make(kx, ky, beta, n // rb, rb, p, rho)
    return DenseProblem(X, np.asarray(y), (n, p), (kx, ky), beta, rb, rho)
