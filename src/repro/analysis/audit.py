"""Compiled-artifact auditor: checks the invariants the AST can't see.

Where the lint rules (repro.analysis.rules) read source, this module
*traces* the registered entry points and inspects the jaxpr / launch
events / compile counters:

  * **launch structure** — the fused superstep must stay at exactly 2
    device launches (2 ``pallas_call`` eqns: stats_gram_solve +
    margin_ls), the unfused superstep at 5 logical launches (4 kernels +
    the xdb merge matvec), matching
    ``roofline.hlo.superstep_launch_targets``.  Counted two ways: ops-level
    launch events recorded at trace time (``kernels.ops.launch_trace``)
    and ``pallas_call`` primitives in the jaxpr.
  * **collective sequence** — the distributed superstep's ordered
    collective signature must be deterministic and must contain no
    collective under a ``cond`` branch (the compiled analog of lint rule
    DIST002: SPMD programs deadlock when shards disagree on whether a
    collective runs).
  * **VMEM footprint** — every traced kernel's BlockSpec-derived block
    bytes × pipeline buffers must fit the backend budget
    (``roofline.hlo.VMEM_BUDGET_BYTES``).
  * **zero steady-state recompiles** — a warm λ-path on a ``GLMSolver``
    session must trace the superstep exactly once (the PR 2 one-compile
    contract, generalizing ``serve.batcher.compile_count``).

Pure-trace: nothing here executes kernels, so the audit runs on the CPU CI
container in seconds.
"""
from __future__ import annotations

import dataclasses
from typing import Iterator, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dglmnet
from repro.core.dglmnet import DGLMNETConfig, FitState
from repro.kernels import ops
from repro.roofline import hlo as hlo_lib

COLLECTIVE_PRIMS = {
    "psum", "pmax", "pmin", "ppermute", "all_gather", "all_to_all",
    "reduce_scatter", "pgather", "pbroadcast",
}

# ops-level events that are one fused HBM pass in the launch model: the
# per-tile Gram accumulation feeds the tile solve without a round-trip.
_GRAM_SOLVE_EVENTS = {"tile_gram", "all_tile_grams", "cd_tile_solve"}


@dataclasses.dataclass
class AuditResult:
    name: str
    status: str          # "ok" | "fail" | "skip"
    details: dict

    def render(self) -> str:
        kv = ", ".join(f"{k}={v}" for k, v in self.details.items())
        return f"audit[{self.name}]: {self.status.upper()} ({kv})"


# --- jaxpr walking ---------------------------------------------------------


def _param_jaxprs(eqn) -> Iterator:
    for v in eqn.params.values():
        for item in (v if isinstance(v, (list, tuple)) else [v]):
            inner = getattr(item, "jaxpr", item)
            if hasattr(inner, "eqns"):
                yield inner


def iter_eqns(jaxpr) -> Iterator:
    """All equations, recursing through pjit/scan/cond/while sub-jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _param_jaxprs(eqn):
            yield from iter_eqns(sub)


def count_primitive(jaxpr, name: str) -> int:
    return sum(1 for e in iter_eqns(jaxpr) if e.primitive.name == name)


def pallas_kernels(jaxpr) -> List[dict]:
    """(name, grid, block bytes, VMEM footprint) per traced pallas_call."""
    out = []
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name != "pallas_call":
            continue
        gm = eqn.params.get("grid_mapping")
        nsi = eqn.params.get("name_and_src_info")
        name = getattr(nsi, "name", None) or eqn.params.get("name") \
            or "<pallas>"
        bms = list(getattr(gm, "block_mappings", ()) or ())
        block_bytes = hlo_lib.pallas_block_bytes(bms)
        out.append({
            "name": str(name).lstrip("_"),
            "grid": tuple(getattr(gm, "grid", ()) or ()),
            "block_bytes": block_bytes,
            "vmem_bytes": hlo_lib.pallas_vmem_footprint(bms),
        })
    return out


def collective_signature(jaxpr) -> List[str]:
    return [e.primitive.name for e in iter_eqns(jaxpr)
            if e.primitive.name in COLLECTIVE_PRIMS]


def collectives_under_cond(jaxpr) -> List[str]:
    """Collective primitives reachable inside a cond branch — branch
    divergence between shards turns these into deadlocks."""
    hits: List[str] = []
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name != "cond":
            continue
        for sub in _param_jaxprs(eqn):
            hits.extend(collective_signature(sub))
    return hits


def coalesce_launch_events(events: List[str]) -> List[str]:
    """Map ops-level events onto the launch-model units: adjacent Gram/
    solve events are one fused pass (``gram_solve``)."""
    units: List[str] = []
    for ev in events:
        if ev in _GRAM_SOLVE_EVENTS:
            if units and units[-1] == "gram_solve":
                continue
            units.append("gram_solve")
        else:
            units.append(ev)
    return units


# --- entry-point builders --------------------------------------------------


def _toy_args(n: int, p: int, T: int):
    st = FitState(beta=jnp.zeros((p,), jnp.float32),
                  xb=jnp.zeros((n,), jnp.float32),
                  mu=jnp.asarray(1.0, jnp.float32),
                  cursor=jnp.zeros((1,), jnp.int32),
                  step=jnp.asarray(0, jnp.int32))
    return (jnp.zeros((n, p), jnp.float32),          # X
            jnp.zeros((n,), jnp.float32),            # y
            jnp.ones((n,), jnp.float32),             # weights
            jnp.zeros((n,), jnp.float32),            # offset
            jnp.asarray([p // T], jnp.int32),        # budget
            jnp.asarray([0.1, 0.01], jnp.float32),   # lams (runtime!)
            jnp.ones((p,), jnp.float32),             # active
            jnp.ones((p,), jnp.float32),             # penf
            st)


def _build_superstep(*, fused: bool, backend: str = "pallas",
                     n: int = 8, p: int = 16, T: int = 8):
    cfg = DGLMNETConfig(lam1=0.1, lam2=0.01, tile_size=T, coupling="jacobi",
                        fuse_superstep=fused, kernel_backend=backend)
    step = dglmnet.make_superstep(cfg, n_tiles_local=p // T)
    return step, _toy_args(n, p, T)


def trace_superstep(*, fused: bool, backend: str = "pallas",
                    n: int = 8, p: int = 16, T: int = 8):
    """Returns (launch-model units, jaxpr) for one superstep trace."""
    step, args = _build_superstep(fused=fused, backend=backend, n=n, p=p,
                                  T=T)
    with ops.launch_trace() as events:
        jaxpr = jax.make_jaxpr(step)(*args)
    return coalesce_launch_events(events), jaxpr


# --- individual audits -----------------------------------------------------


def audit_superstep_launches() -> List[AuditResult]:
    """Pin the launch contract: fused = 2, unfused = 5 (DESIGN.md §8)."""
    out = []
    for fused in (True, False):
        target = hlo_lib.superstep_launch_targets(
            8, 16, 8, fused=fused)["n_launches"]
        units, jaxpr = trace_superstep(fused=fused)
        n_pallas = count_primitive(jaxpr.jaxpr, "pallas_call")
        # fused: every launch is a pallas_call.  unfused: 4 kernels + the
        # xdb merge matvec, which is a plain dot_general between launches.
        pallas_target = target if fused else target - 1
        ok = len(units) == target and n_pallas == pallas_target
        out.append(AuditResult(
            name=f"launches_{'fused' if fused else 'unfused'}",
            status="ok" if ok else "fail",
            details={"units": units, "n_units": len(units),
                     "target": target, "pallas_calls": n_pallas,
                     "pallas_target": pallas_target}))
    return out


def audit_kernel_vmem(budget_bytes: Optional[int] = None) -> AuditResult:
    """Every kernel block set (× pipeline buffers) must fit VMEM at
    production shapes (T=256 tiles, 512-row blocks)."""
    budget = budget_bytes or hlo_lib.VMEM_BUDGET_BYTES
    _, jaxpr = trace_superstep(fused=True, n=1024, p=512, T=256)
    kernels = pallas_kernels(jaxpr.jaxpr)
    over = [k for k in kernels if k["vmem_bytes"] > budget]
    return AuditResult(
        name="kernel_vmem",
        status="ok" if kernels and not over else "fail",
        details={"budget_mib": round(budget / 2 ** 20, 2),
                 "kernels": {k["name"]: round(k["vmem_bytes"] / 2 ** 20, 3)
                             for k in kernels},
                 "over_budget": [k["name"] for k in over]})


def audit_collective_sequence() -> AuditResult:
    """The sharded superstep's collective signature must be non-empty,
    deterministic across traces, and cond-free."""
    from jax.sharding import Mesh, PartitionSpec as P

    n, p, T = 8, 16, 8
    cfg = DGLMNETConfig(lam1=0.1, lam2=0.01, tile_size=T, coupling="jacobi",
                        fuse_superstep=False, kernel_backend="ref")
    step = dglmnet.make_superstep(cfg, axis_data="data", axis_model="model",
                                  n_tiles_local=p // T)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    st_spec = FitState(beta=P("model"), xb=P("data"), mu=P(), cursor=P(),
                      step=P())
    in_specs = (P("data", "model"), P("data"), P("data"), P("data"),
                P(), P(), P("model"), P("model"), st_spec)
    metric_keys = ("f", "f_before", "loss", "alpha", "mu", "nnz",
                   "accepted_unit", "tiles_done")

    def traced(*args):
        state, metrics = step(*args)
        return state, metrics

    sharded = jax.shard_map(traced, mesh=mesh, in_specs=in_specs,
                            out_specs=(st_spec, P()), check_vma=False)
    args = _toy_args(n, p, T)
    sigs = [collective_signature(jax.make_jaxpr(sharded)(*args).jaxpr)
            for _ in range(2)]
    under_cond = collectives_under_cond(
        jax.make_jaxpr(sharded)(*args).jaxpr)
    ok = bool(sigs[0]) and sigs[0] == sigs[1] and not under_cond
    return AuditResult(
        name="collective_sequence",
        status="ok" if ok else "fail",
        details={"signature": sigs[0], "deterministic": sigs[0] == sigs[1],
                 "under_cond": under_cond, "_keys": list(metric_keys)})


def audit_steady_state_recompiles() -> AuditResult:
    """A 3-λ warm path on one session must trace the superstep once: the
    λ points after the first are steady state and must add 0 traces."""
    from repro.core.solver import GLMSolver

    rng = np.random.default_rng(0)
    n, p, T = 48, 16, 8
    X = rng.normal(size=(n, p)).astype(np.float32)
    beta_true = np.zeros(p, np.float32)
    beta_true[:3] = 1.0
    y = (X @ beta_true + 0.1 * rng.normal(size=n)).astype(np.float32)
    cfg = DGLMNETConfig(family="squared", tile_size=T, max_outer=4,
                        tol=0.0)
    solver = GLMSolver(X, y, config=cfg, standardize=False,
                       fit_intercept=False)
    solver.fit(lam1=0.5, lam2=0.01)
    warm = solver.compile_count              # compiles paid on first fit
    solver.fit_path(lambdas=[0.5, 0.25, 0.1], lam2=0.01, screen=False)
    steady = solver.compile_count - warm
    return AuditResult(
        name="steady_state_recompiles",
        status="ok" if steady == 0 else "fail",
        details={"warm_compiles": warm, "steady_state_recompiles": steady,
                 "lambdas": 3})


def audit_scoring_entry_points() -> List[AuditResult]:
    """predict_tile and tile_gram stay single-launch; the streaming finish
    stage stays launch-free (selection only — no data pass)."""
    out = []

    def trace_pallas(name, fn, *args):
        with ops.launch_trace() as events:
            jaxpr = jax.make_jaxpr(fn)(*args)
        n_pallas = count_primitive(jaxpr.jaxpr, "pallas_call")
        return events, n_pallas, jaxpr

    slots = jnp.zeros((8, 128), jnp.int32)
    vals = jnp.zeros((8, 128), jnp.float32)
    table = jnp.zeros((9, 128), jnp.float32)
    b0 = jnp.zeros((128,), jnp.float32)
    ev, n_pallas, _ = trace_pallas(
        "predict_tile",
        lambda s, v, t, b: ops.predict_tile(s, v, t, b, "logistic",
                                            backend="pallas"),
        slots, vals, table, b0)
    out.append(AuditResult(
        name="predict_tile_single_launch",
        status="ok" if n_pallas == 1 and ev == ["predict_tile"] else "fail",
        details={"pallas_calls": n_pallas, "events": ev}))

    K, rb, T, nrb = 4, 8, 8, 2
    bricks = jnp.zeros((K, rb, T), jnp.float32)
    rows = jnp.zeros((K,), jnp.int32)
    n_valid = jnp.asarray(K, jnp.int32)
    w2 = jnp.ones((nrb, rb), jnp.float32)
    r2 = jnp.ones((nrb, rb), jnp.float32)
    ev, n_pallas, _ = trace_pallas(
        "tile_gram",
        lambda *a: ops.tile_gram(*a, backend="pallas"),
        bricks, rows, n_valid, w2, r2)
    out.append(AuditResult(
        name="tile_gram_single_launch",
        status="ok" if n_pallas == 1 and ev == ["tile_gram"] else "fail",
        details={"pallas_calls": n_pallas, "events": ev}))

    # streaming finish: Algorithm-3 selection over accumulated candidate
    # losses — feature-sized math only, no kernels, no design pass.
    n, p, T = 8, 16, 8
    cfg = DGLMNETConfig(lam1=0.1, lam2=0.01, tile_size=T,
                        coupling="jacobi", kernel_backend="ref")
    stream = dglmnet.make_streaming_superstep(cfg)
    st = _toy_args(n, p, T)[-1]
    lams = jnp.asarray([0.1, 0.01], jnp.float32)
    penf = jnp.ones((p,), jnp.float32)
    losses = jnp.zeros((stream.n_candidates,), jnp.float32)
    prep = {"dbeta": jnp.zeros((p,)), "cand": jnp.zeros(
                (stream.n_candidates,)),
            "loss": jnp.asarray(0.0), "f_cur": jnp.asarray(0.0),
            "R0": jnp.asarray(0.0), "grad_dot_dir": jnp.asarray(0.0),
            "quad_form": jnp.asarray(0.0),
            "tiles_done": jnp.asarray(0, jnp.int32)}
    with ops.launch_trace() as ev:
        jaxpr = jax.make_jaxpr(stream.finish)(losses, prep, st, lams, penf)
    n_pallas = count_primitive(jaxpr.jaxpr, "pallas_call")
    out.append(AuditResult(
        name="streaming_finish_launch_free",
        status="ok" if n_pallas == 0 and not ev else "fail",
        details={"pallas_calls": n_pallas, "events": list(ev)}))
    return out


# --- driver ----------------------------------------------------------------


def run_audit() -> List[AuditResult]:
    results: List[AuditResult] = []
    results.extend(audit_superstep_launches())
    results.append(audit_kernel_vmem())
    results.append(audit_collective_sequence())
    results.extend(audit_scoring_entry_points())
    results.append(audit_steady_state_recompiles())
    return results


def summary(results: List[AuditResult]) -> dict:
    return {r.name: {"status": r.status, **{
        k: v for k, v in r.details.items() if not k.startswith("_")
        and not isinstance(v, dict)}} for r in results}


def main() -> int:
    results = run_audit()
    for r in results:
        print(r.render())
    return 1 if any(r.status == "fail" for r in results) else 0


if __name__ == "__main__":
    raise SystemExit(main())
