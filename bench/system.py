"""The one place the benchmark talks to the system under test: it builds a
``GLMSolver`` session from a configuration and a generated problem through
the package's normal front door, runs λ-paths on it, and reads the
session's own counters."""
from __future__ import annotations

import dataclasses

import numpy as np


def _solver_config(solver: dict):
    from repro.core.dglmnet import DGLMNETConfig
    fields = {f.name for f in dataclasses.fields(DGLMNETConfig)}
    return DGLMNETConfig(**{k: v for k, v in solver.items() if k in fields})


def _mesh(shape):
    if shape is None:
        return None
    import jax
    from repro.sharding import compat
    need = int(np.prod(shape))
    if len(jax.devices()) < need:
        raise RuntimeError(f"mesh {shape} needs {need} devices, "
                           f"found {len(jax.devices())}")
    return compat.make_mesh(tuple(shape), ("data", "model"))


class Session:
    """One ``GLMSolver`` over the cell's problem."""

    def __init__(self, config: dict, traffic: dict, problem, *, solver=None):
        from repro.core.solver import GLMSolver
        solver = dict(config["solver"] if solver is None else solver)
        self.lam2 = float(solver.get("lam2", 0.0))
        self.path = config["path"]
        kwargs = {}
        if "row_block" in solver:
            kwargs["row_block"] = int(solver["row_block"])
        self.solver = GLMSolver(
            problem.X, problem.y, config=_solver_config(solver),
            mesh=_mesh(traffic.get("mesh")),
            **kwargs)

    def grid(self):
        """The cell's λ grid: ``n_lambdas`` log-spaced points from the
        session's λ_max down to ``lam_ratio`` of it."""
        lmax = self.solver.lambda_max()
        return np.logspace(np.log10(lmax),
                           np.log10(lmax * self.path["lam_ratio"]),
                           int(self.path["n_lambdas"]))

    def fit(self, lambdas):
        """One warm-started path; returns (lambdas, betas, f, n_iters) on
        the host."""
        res = self.solver.fit_path(lambdas=np.asarray(lambdas),
                                   lam2=self.lam2)
        return res.lambdas, np.asarray(res.betas), np.asarray(res.f), \
            np.asarray(res.n_iters)

    @staticmethod
    def mirror_spans(on: bool):
        """Turn the program's own spans (``repro.obs.trace``, kept in
        memory) on or off; while the profiler runs they are mirrored into
        its trace, so idle gaps can be attributed to them."""
        from repro.obs import trace
        if on:
            trace.enable(None)
        else:
            trace.disable()

    def margins(self) -> np.ndarray:
        """The margins X beta the session maintains at its last fitted
        state, per row, float64 on the host."""
        return np.asarray(self.solver.training_margins(), np.float64)

    def programs(self) -> list:
        """HLO texts of the compiled programs whose Pallas calls a trace
        attributes: the session's superstep, and the gradient program that
        λ_max, screening and the KKT checks run."""
        s = self.solver
        texts = [s.lower_superstep().compile().as_text()]
        grad = getattr(s, "_grad_fn", None)
        if grad is not None and hasattr(grad, "lower"):
            texts.append(grad.lower(s._Xs, s._ys, s._wobs, s._offsets,
                                    s._wobs).compile().as_text())
        return texts

    def counters(self) -> dict:
        return dict(self.solver.launch_stats)

    def device_bytes(self) -> dict:
        return self.solver.device_bytes()

    def close(self):
        self.solver = None
