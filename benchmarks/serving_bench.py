"""Serving throughput/latency benchmark (DESIGN.md §7).

    PYTHONPATH=src:. python benchmarks/serving_bench.py            # full
    PYTHONPATH=src:. python benchmarks/serving_bench.py --smoke    # CI

End to end: fit a λ-path on synthetic sparse logistic data, export fp32
and int8 artifacts, then measure

  * artifact size fp32 vs int8 (shared-scale quantization must be ≥ 2×
    smaller) and the max int8 margin error against the manifest's
    documented bound (scale/2 · ‖x‖₁ per request),
  * the fused ``kernels/predict_tile.py`` kernel against its jnp oracle
    (≤ 1e-5 on all four families, link and response),
  * sparse scoring throughput: HONEST batch-1 (one real engine dispatch
    per request through the same padding machinery — what a no-batching
    server does, not a strawman) vs micro-batched coalescing (must be
    ≥ 5× batch-1 rows/s), plus dense-batch scoring for reference.

Full mode writes ``results/benchmarks/serving_bench.json`` (committed;
``benchmarks/make_report.py`` renders it).  Smoke mode shrinks everything
and additionally round-trips the artifact through the CLI's entry point
(``serve_glm.main(["--artifact", ..., "--smoke"])``, in this process so
only one process ever claims the accelerator), asserting
the emitted JSON carries the p50 latency and rows/s fields — the CI
serving smoke.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

from repro.timing import percentiles  # noqa: E402

OUT = pathlib.Path(__file__).resolve().parents[1] / "results" / "benchmarks" \
    / "serving_bench.json"

FAMILIES = ("logistic", "squared", "probit", "poisson")


def fit_and_export(tmp, *, n, p, n_lambdas, seed=0):
    """Small sparse logistic fit → fp32 + int8 path artifacts."""
    from repro.core.dglmnet import DGLMNETConfig
    from repro.core.solver import GLMSolver
    from repro.serve import artifact

    rng = np.random.default_rng(seed)
    X = (rng.normal(size=(n, p)) * (rng.random((n, p)) < 0.1)) \
        .astype(np.float32)
    beta_true = np.zeros(p, np.float32)
    hot = rng.choice(p, size=max(p // 25, 4), replace=False)
    beta_true[hot] = rng.normal(size=hot.size) * 2.0
    y = np.where(X @ beta_true + 0.2 * rng.normal(size=n) > 0, 1.0,
                 -1.0).astype(np.float32)

    solver = GLMSolver(X, y, family="logistic",
                       config=DGLMNETConfig(tile_size=32, max_outer=60,
                                            tol=1e-8),
                       fit_intercept=True, standardize=True)
    path = solver.fit_path(n_lambdas=n_lambdas, lam_ratio=1e-2)
    fp32 = solver.save(tmp / "fp32", path_result=path)
    int8 = solver.save(tmp / "int8", path_result=path, quantize="int8")
    return solver, path, fp32, int8


def kernel_parity_rows():
    """Fused kernel vs jnp oracle, all four families, link + response."""
    import jax.numpy as jnp
    from repro.kernels import ops, ref

    rng = np.random.default_rng(2)
    A, L, B, J = 33, 5, 24, 17
    table = np.zeros((A + 1, L), np.float32)
    table[:-1] = rng.normal(size=(A, L))
    slots = rng.integers(0, A + 1, size=(B, J)).astype(np.int32)
    vals = rng.normal(size=(B, J)).astype(np.float32)
    b0 = rng.normal(size=L).astype(np.float32)
    rows = []
    for fam in FAMILIES:
        err = 0.0
        for kind in ("link", "response"):
            o = ref.predict_tile(jnp.asarray(slots), jnp.asarray(vals),
                                 jnp.asarray(table),
                                 jnp.asarray(b0).reshape(1, -1), fam,
                                 kind=kind)
            k = ops.predict_tile(jnp.asarray(slots), jnp.asarray(vals),
                                 jnp.asarray(table), b0, fam, kind=kind,
                                 backend="pallas")
            err = max(err, float(jnp.abs(o - k).max()))
        assert err <= 1e-5, f"{fam}: kernel vs oracle {err} > 1e-5"
        rows.append({"case": f"kernel_parity_{fam}", "mode": "kernel",
                     "max_abs_err_vs_oracle": err, "tol": 1e-5})
    return rows


# one traffic generator: the CLI and this benchmark must measure the
# SAME synthetic workload, not two drifting copies
from repro.launch import serve_glm  # noqa: E402
from repro.launch.serve_glm import synth_requests  # noqa: E402


def measure_batch1(engine, reqs, kind="response"):
    """One real engine dispatch per request — the no-coalescing server."""
    from repro.serve.batcher import MicroBatcher
    b = MicroBatcher(engine, batch_buckets=(1,), kind=kind)
    b.warmup()
    lat = []
    t0 = time.perf_counter()
    for idx, val in reqs:
        t1 = time.perf_counter()
        b.score_one(idx, val)
        lat.append(time.perf_counter() - t1)
    wall = time.perf_counter() - t0
    b.close()
    pct = percentiles([v * 1e3 for v in lat])
    return {"rows_per_s": len(reqs) / wall,
            "p50_ms": pct["p50"], "p99_ms": pct["p99"],
            "mean_batch": 1.0, "n_requests": len(reqs)}


def measure_coalesced(engine, reqs, *, max_delay_ms=2.0, kind="response"):
    from repro.serve.batcher import MicroBatcher
    with MicroBatcher(engine, max_delay_ms=max_delay_ms,
                      kind=kind) as b:
        b.warmup()
        handles = [b.submit(i, v) for i, v in reqs]
        for h in handles:
            h.get(timeout=120.0)
        st = b.stats()
    return {k: st[k] for k in ("rows_per_s", "p50_ms", "p99_ms",
                               "mean_batch", "n_requests",
                               "compiled_shapes")}


def run(smoke: bool, out_path):
    from repro.serve import ScoringEngine, artifact_bytes, load_artifact
    from repro.timing import timed

    n, p, K = (300, 160, 4) if smoke else (1200, 768, 8)
    n_req = 200 if smoke else 1500
    n_req_b1 = 100 if smoke else 400

    rows = kernel_parity_rows()
    print(f"[serving_bench] kernel parity ok on {FAMILIES}")

    tmp = pathlib.Path(tempfile.mkdtemp(prefix="serving_bench_"))
    solver, path, fp32_dir, int8_dir = fit_and_export(
        tmp, n=n, p=p, n_lambdas=K)
    m32 = load_artifact(fp32_dir)
    m8 = load_artifact(int8_dir)
    b32, b8 = artifact_bytes(fp32_dir), artifact_bytes(int8_dir)
    ratio = b32 / b8
    assert ratio >= 2.0, f"int8 artifact only {ratio:.2f}x smaller"

    eng32 = ScoringEngine(m32)
    eng8 = ScoringEngine(m8)
    rng = np.random.default_rng(3)
    reqs = synth_requests(rng, n_req, p, nnz=24)

    # int8 margins vs the documented shared-scale bound
    m_fp = eng32.score_sparse(reqs, kind="link")
    m_i8 = eng8.score_sparse(reqs, kind="link")
    err = np.abs(m_fp - m_i8).max(axis=1)                  # per request
    bounds = np.asarray([m8.margin_error_bound(np.abs(v).sum())
                         for _, v in reqs])
    assert (err <= bounds + 1e-6).all(), \
        f"int8 margin error {err.max():.3g} exceeds documented bound"
    rows.append({"case": "artifact_int8", "mode": "artifact",
                 "dtype": "int8", "artifact_bytes": b8,
                 "fp32_bytes": b32, "size_ratio_fp32_over_int8": ratio,
                 "max_margin_err": float(err.max()),
                 "max_err_bound": float(bounds.max()),
                 "n_outputs": m8.n_outputs, "n_active": eng8.n_active})
    print(f"[serving_bench] int8 {ratio:.2f}x smaller, margin err "
          f"{err.max():.3g} <= bound {bounds.max():.3g}")

    # sparse scoring: honest batch-1 vs coalesced (fp32 and int8 tables)
    b1 = measure_batch1(eng32, reqs[:n_req_b1])
    co = measure_coalesced(eng32, reqs)
    speedup = co["rows_per_s"] / b1["rows_per_s"]
    floor = 3.0 if smoke else 5.0
    assert speedup >= floor, \
        f"coalesced only {speedup:.1f}x batch-1 (need >= {floor})"
    rows.append({"case": "sparse_batch1", "mode": "batch1",
                 "dtype": "float32", **b1})
    rows.append({"case": "sparse_coalesced", "mode": "coalesced",
                 "dtype": "float32", **co,
                 "speedup_vs_batch1": speedup})
    co8 = measure_coalesced(eng8, reqs)
    rows.append({"case": "sparse_coalesced_int8", "mode": "coalesced",
                 "dtype": "int8", **co8,
                 "speedup_vs_batch1": co8["rows_per_s"] / b1["rows_per_s"]})
    print(f"[serving_bench] sparse rows/s: batch1 {b1['rows_per_s']:.0f} "
          f"-> coalesced {co['rows_per_s']:.0f} ({speedup:.1f}x)")

    # dense batch scoring reference (multi-output, one launch)
    Xd = rng.normal(size=(256, p)).astype(np.float32)
    eng32.score_dense(Xd)                                   # warm
    _, dt = timed(eng32.score_dense, Xd)
    rows.append({"case": "dense_batch256", "mode": "dense",
                 "dtype": "float32", "n_requests": 256,
                 "rows_per_s": 256 / dt,
                 "n_outputs": m32.n_outputs})

    # active-set compaction parity against the full-β product
    full = Xd @ np.asarray(m32.betas).T + np.asarray(m32.intercepts)
    compact = eng32.score_dense(Xd, kind="link")
    d = float(np.abs(full - compact).max())
    assert d <= 1e-4, f"compacted scoring deviates {d} from full beta"
    rows.append({"case": "active_set_parity", "mode": "dense",
                 "max_abs_err_vs_full_beta": d,
                 "n_active": eng32.n_active, "p": p})

    if smoke:
        # CLI round trip: export -> serve_glm --smoke -> assert fields,
        # in this process (a child would contend for the accelerator)
        out_json = tmp / "serve_glm.json"
        rc = serve_glm.main(["--artifact", str(fp32_dir), "--smoke",
                             "--json", str(out_json)])
        assert rc == 0, f"serve_glm --smoke exited {rc}"
        rec = json.loads(out_json.read_text())
        for field in ("p50_ms", "p99_ms", "rows_per_s"):
            assert isinstance(rec.get(field), float), \
                f"serve_glm JSON missing {field}: {rec}"
        print(f"[serving_bench] serve_glm smoke: p50={rec['p50_ms']:.2f}ms "
              f"rows/s={rec['rows_per_s']:.0f}")

    record = {"figure": "serving_bench", "rows": rows}
    if not smoke:
        out_path.parent.mkdir(parents=True, exist_ok=True)
        out_path.write_text(json.dumps(record, indent=1))
        print(f"[serving_bench] wrote {out_path}")
    else:
        print("[serving_bench] smoke ok")
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out", default=str(OUT))
    args = ap.parse_args()
    run(args.smoke, pathlib.Path(args.out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
