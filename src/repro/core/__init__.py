"""repro.core — d-GLMNET: distributed coordinate descent for regularized GLMs.

Public API:
  GLMSolver, PathResult, lambda_max   — session API: warm-started λ-path
                                        fitting over a reusable sharded design
  DGLMNETConfig                       — algorithm hyperparameters (λ defaults)
  fit, fit_sharded                    — DEPRECATED one-shot drivers (thin
                                        wrappers over a GLMSolver session)
  glm.FAMILIES                        — logistic / squared / probit / poisson
"""
from repro.core import glm  # noqa: F401

# Solver/driver symbols resolve lazily (PEP 562).  ``glm`` is the only
# eager import: the kernels layer pulls ``repro.core.glm`` at module
# level, and an eager solver import here would re-enter
# ``repro.data.design`` while it is still initializing
# (design -> kernels.ops -> repro.core -> solver -> design).
_LAZY = {
    "DGLMNETConfig": "repro.core.dglmnet",
    "FitResult": "repro.core.dglmnet",
    "fit": "repro.core.dglmnet",
    "fit_sharded": "repro.core.dglmnet",
    "GLMSolver": "repro.core.solver",
    "PathResult": "repro.core.solver",
    "lambda_max": "repro.core.solver",
}


def __getattr__(name):
    modname = _LAZY.get(name)
    if modname is None:
        raise AttributeError(f"module 'repro.core' has no attribute {name!r}")
    import importlib

    obj = getattr(importlib.import_module(modname), name)
    globals()[name] = obj
    return obj


def __dir__():
    return sorted(set(globals()) | set(_LAZY))
