"""Pallas TPU kernel: fused GLM link statistics.

One streaming pass over the example dimension computing, per example,
(loss_i, s_i = -dl/dm, w_i = d2l/dm2) from (y_i, margin_i).  Fusing the three
outputs into one VMEM pass replaces three separate HBM sweeps; on TPU this is
purely VPU work on (8k, 128) tiles.

Inputs are reshaped by ops.py to (R, 128) with a mask carrying the padding.
``_LOSS`` holds each family's loss alone, the same values as the first output
of ``_STATS``, for kernels that need no derivatives (the line search).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.glm import POISSON_W_CLIP

_SQRT2 = 1.4142135623730951
_LOG_SQRT_2PI = 0.9189385332046727


# erfc(z) = t·exp(-z² + P(t)), t = 1 / (1 + z/2), for z >= 0 (Numerical
# Recipes' Chebyshev fit, relative error under 1.2e-7): the TPU kernel
# compiler lowers exp and division but not erfc
_ERFC_COEFFS = (-1.26551223, 1.00002368, 0.37409196, 0.09678418, -0.18628806,
                0.27886807, -1.13520398, 1.48851587, -0.82215223, 0.17087277)


def _erfc(x):
    z = jnp.abs(x)
    t = 1.0 / (1.0 + 0.5 * z)
    p = _ERFC_COEFFS[-1]
    for c in _ERFC_COEFFS[-2::-1]:
        p = p * t + c
    r = t * jnp.exp(p - z * z)
    return jnp.where(x < 0.0, 2.0 - r, r)


def _logistic_loss(y, m):
    return jnp.logaddexp(0.0, -y * m)


def _logistic(y, m):
    sig = jax.nn.sigmoid(-y * m)
    return _logistic_loss(y, m), y * sig, sig * (1.0 - sig)


def _squared_loss(y, m):
    r = y - m
    return 0.5 * r * r


def _squared(y, m):
    return _squared_loss(y, m), y - m, jnp.ones_like(m)


def _probit_log_cdf(t):
    # log Phi(t) via erfc for the left tail: Phi(t) = 0.5*erfc(-t/sqrt2)
    log_cdf = jnp.log(jnp.maximum(0.5 * _erfc(-t / _SQRT2), 1e-300))
    # asymptotic guard deep in the tail where erfc underflows:
    tail = -0.5 * t * t - _LOG_SQRT_2PI - jnp.log(jnp.maximum(-t, 1.0))
    return jnp.where(t < -12.0, tail, log_cdf)


def _probit_loss(y, m):
    return -_probit_log_cdf(y * m)


def _probit(y, m):
    t = y * m
    log_cdf = _probit_log_cdf(t)
    log_pdf = -0.5 * t * t - _LOG_SQRT_2PI
    ratio = jnp.exp(log_pdf - log_cdf)
    return -log_cdf, y * ratio, jnp.maximum(ratio * (ratio + t), 0.0)


def _poisson_loss(y, m):
    return jnp.exp(m) - y * m


def _poisson(y, m):
    # curvature clipped at POISSON_W_CLIP (glm.py): the effective curvature
    # bound of the unbounded poisson family; loss/gradient stay exact
    mu = jnp.exp(m)
    return mu - y * m, y - mu, jnp.minimum(mu, POISSON_W_CLIP)


_STATS = {"logistic": _logistic, "squared": _squared,
          "probit": _probit, "poisson": _poisson}
_LOSS = {"logistic": _logistic_loss, "squared": _squared_loss,
         "probit": _probit_loss, "poisson": _poisson_loss}


def _kernel(y_ref, xb_ref, mask_ref, loss_ref, s_ref, w_ref, *, family):
    # mask carries the full per-example observation weight (sample weight ×
    # fold mask × row padding) — weighting and masking are the same multiply
    y = y_ref[...]
    m = xb_ref[...]
    mask = mask_ref[...]
    loss, s, w = _STATS[family](y, m)
    loss_ref[...] = loss * mask
    s_ref[...] = s * mask
    w_ref[...] = w * mask


@functools.partial(jax.jit, static_argnames=("family", "block_rows", "interpret"))
def glm_stats_pallas(y2, xb2, mask2, *, family, block_rows=256, interpret=True):
    """y2/xb2/mask2: (R, 128) f32, R % block_rows == 0. Returns (loss, s, w)."""
    R, C = y2.shape
    grid = (R // block_rows,)
    spec = pl.BlockSpec((block_rows, C), lambda i: (i, 0))
    out_shape = [jax.ShapeDtypeStruct((R, C), jnp.float32)] * 3
    return pl.pallas_call(
        functools.partial(_kernel, family=family),
        grid=grid,
        in_specs=[spec, spec, spec],
        out_specs=[spec, spec, spec],
        out_shape=out_shape,
        interpret=interpret,
    )(y2.astype(jnp.float32), xb2.astype(jnp.float32), mask2.astype(jnp.float32))
