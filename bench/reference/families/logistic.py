"""Logistic loss for labels in {-1, +1}: l(y, m) = log(1 + exp(-y m))."""
import numpy as np


def loss_and_score(y, m):
    """(per-row loss, -dl/dm), float64."""
    ym = y * m
    return np.logaddexp(0.0, -ym), y / (1.0 + np.exp(ym))
