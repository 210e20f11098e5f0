"""repro.io ingestion benchmark: file → chunks → fitted GLM (DESIGN.md §10).

Sections (one row each in the committed JSON):

  * ``reader_*``    — raw LibsvmReader throughput: the one-off scan cost
    and a full sequential chunk pass (rows/s, nnz/s), gzip vs plain text;
  * ``hashed_pass`` — the same pass through ``FeatureHasher`` into a
    tile-aligned 2^k space (the unbounded-vocabulary path);
  * ``e2e_*``       — end-to-end out-of-core training rows/s from the
    gzip file, ingestion pipeline OFF (cold reparse of every chunk, every
    pass — the strict out-of-core floor) vs ON (``PrefetchingSource``
    background production queue + the reader's bounded decoded-chunk LRU,
    so only epoch one pays decompress+parse).  ``prefetch_speedup`` =
    wall_off / wall_on; >1.0 is the committed acceptance claim.  On a
    single-core host the queue alone cannot overlap (production and
    compute share the core), so the speedup is carried by the cache — the
    row records ``cpu_count`` so multi-core readings are interpretable;
  * ``multihost_*`` — the first multi-process out-of-core fit: the SAME
    gzip file trained through ``repro.launch.dist_run --data`` at
    ``--nprocs 1`` and ``--nprocs 2`` (per-process contiguous chunk
    ranges via ``StreamingDesign.process_slice``, per-superstep (Gram,
    gradient, loss) partials all-reduced across the process mesh).  The
    2-process fit must reproduce the 1-process β (``parity_ok``).

``--smoke`` builds a tiny corpus and asserts the correctness half
(round-trip, pipeline-on ≡ pipeline-off fit, multihost wiring untouched);
the committed full-size run is ``python -m benchmarks.ingest_bench``.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys
import tempfile
import time

import numpy as np

_REPO = pathlib.Path(__file__).resolve().parents[1]


def _make_corpus(path, *, n, p, density, seed=7, k_true=24):
    """Synthetic sparse logistic corpus written as libsvm(.gz)."""
    from repro.io.libsvm import write_libsvm

    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, p)).astype(np.float32)
    X[rng.random(size=X.shape) > density] = 0.0
    beta = np.zeros(p, np.float32)
    beta[:k_true] = rng.normal(size=k_true)
    y = np.where(rng.random(n) < 1.0 / (1.0 + np.exp(-(X @ beta))),
                 1.0, -1.0).astype(np.float32)
    write_libsvm(path, X, y)
    return X, y


def _reader_row(case, path, *, chunk_rows):
    from repro.io.libsvm import LibsvmReader
    from repro.timing import timed

    t0 = time.perf_counter()
    r = LibsvmReader(path, chunk_rows=chunk_rows)
    scan_s = time.perf_counter() - t0
    nnz = 0

    def full_pass():
        nonlocal nnz
        nnz = 0
        for i in range(r.n_chunks):
            cols, _ = r.chunk(i)
            nnz += int((cols >= 0).sum())

    _, pass_s = timed(full_pass)
    return {
        "case": case, "format": pathlib.Path(path).suffix.lstrip("."),
        "rows": r.n_rows, "features": r.n_features, "chunks": r.n_chunks,
        "nnz_total": nnz, "file_mb": round(os.path.getsize(path) / 2**20, 2),
        "scan_s": round(scan_s, 3), "pass_s": round(pass_s, 3),
        "rows_per_s": round(r.n_rows / max(pass_s, 1e-9)),
        "nnz_per_s": round(nnz / max(pass_s, 1e-9)),
    }


def _hashed_row(path, *, chunk_rows, hash_dim, tile):
    from repro.io.hashing import FeatureHasher
    from repro.io.libsvm import LibsvmReader
    from repro.timing import timed

    r = LibsvmReader(path, chunk_rows=chunk_rows)
    h = FeatureHasher(hash_dim, tile_size=tile)
    fn = r.hashed_chunk_fn(h)
    _, pass_s = timed(lambda: [fn(i) for i in range(r.n_chunks)])
    return {"case": "hashed_pass", "rows": r.n_rows, "chunks": r.n_chunks,
            "hash_dim": h.n_features, "pass_s": round(pass_s, 3),
            "rows_per_s": round(r.n_rows / max(pass_s, 1e-9))}


def _e2e_pair(path, *, chunk_rows, tile, steps, lam1):
    """Out-of-core fit from file, ingestion pipeline off vs on."""
    from repro.core.dglmnet import DGLMNETConfig
    from repro.core.solver import GLMSolver
    from repro.io.libsvm import LibsvmReader
    from repro.timing import timed

    def fit(tag, *, prefetch_chunks, cache_chunks):
        r = LibsvmReader(path, chunk_rows=chunk_rows,
                         cache_chunks=cache_chunks)
        sd = r.to_design(tile, prefetch=prefetch_chunks > 0,
                         prefetch_chunks=prefetch_chunks)
        cfg = DGLMNETConfig(tile_size=tile, max_outer=steps, tol=0.0)
        solver = GLMSolver(sd, r.labels(), config=cfg)
        solver.fit(lam1=lam1, max_outer=1)   # compile outside the window
        res, wall = timed(solver.fit, lam1=lam1)
        return {
            "case": f"e2e_{tag}", "rows": r.n_rows,
            "features": r.n_features, "chunks": r.n_chunks,
            "chunk_rows": chunk_rows, "supersteps": res.n_iter,
            "prefetch": prefetch_chunks > 0, "cache_chunks": cache_chunks,
            "wall_s": round(wall, 3),
            # two chunk passes per superstep
            "rows_per_s": round(r.n_rows * res.n_iter * 2 / max(wall, 1e-9)),
            "f_final": round(float(res.history["f"][-1]), 6),
            "nnz": int(res.history["nnz"][-1]),
        }, np.asarray(res.beta)

    off, beta_off = fit("pipeline_off", prefetch_chunks=0, cache_chunks=0)
    on, beta_on = fit("pipeline_on", prefetch_chunks=2, cache_chunks=2**30)
    # bounded in practice by the corpus (reported), unbounded by config so
    # the arm is "everything the budget allows"
    on["cache_chunks"] = min(on["chunks"], on["cache_chunks"])
    speedup = off["wall_s"] / max(on["wall_s"], 1e-9)
    on["prefetch_speedup"] = round(speedup, 3)
    off["prefetch_speedup"] = 1.0
    for r_ in (off, on):
        r_["cpu_count"] = os.cpu_count()
    beta_err = float(np.abs(beta_on - beta_off).max())
    return off, on, speedup, beta_err


def _dist_row(path, *, nprocs, chunk_rows, tile, steps, lam1):
    """One ``dist_run --data`` job; returns its coordinator JSON row.  The
    job is a multi-process CPU simulation: it runs on JAX_PLATFORMS=cpu."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(_REPO / "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    with tempfile.TemporaryDirectory() as td:
        out = pathlib.Path(td) / "row.json"
        cmd = [sys.executable, "-m", "repro.launch.dist_run",
               "--data", str(path), "--nprocs", str(nprocs),
               "--chunk-rows", str(chunk_rows), "--tile", str(tile),
               "--steps", str(steps), "--lam1", str(lam1),
               "--tol", "0.0", "--out", str(out)]
        proc = subprocess.run(cmd, cwd=_REPO, env=env, capture_output=True,
                              text=True, timeout=900)
        if proc.returncode != 0 or not out.exists():
            raise RuntimeError(
                f"dist_run nprocs={nprocs} failed:\n{proc.stdout}\n"
                f"{proc.stderr}")
        row = json.loads(out.read_text())
    row["case"] = f"multihost_{nprocs}proc"
    row["platform"] = "cpu (local worker processes)"
    print(f"[ingest_bench] {row['case']}: workers ran on JAX_PLATFORMS=cpu")
    return row


def _bench(*, n, p, density, chunk_rows, tile, steps, lam1=0.02,
           hash_dim=2048, with_multihost=True, workdir=None):
    td_ctx = tempfile.TemporaryDirectory() if workdir is None else None
    base = pathlib.Path(workdir or td_ctx.name)
    try:
        gz = base / "corpus.libsvm.gz"
        plain = base / "corpus.libsvm"
        X, y = _make_corpus(gz, n=n, p=p, density=density)
        _make_corpus(plain, n=n, p=p, density=density)

        rows = [_reader_row("reader_gz", gz, chunk_rows=chunk_rows),
                _reader_row("reader_plain", plain, chunk_rows=chunk_rows),
                _hashed_row(gz, chunk_rows=chunk_rows, hash_dim=hash_dim,
                            tile=tile)]
        off, on, speedup, beta_err = _e2e_pair(
            gz, chunk_rows=chunk_rows, tile=tile, steps=steps, lam1=lam1)
        rows += [off, on]

        parity = None
        if with_multihost:
            r1 = _dist_row(gz, nprocs=1, chunk_rows=chunk_rows, tile=tile,
                           steps=steps, lam1=lam1)
            r2 = _dist_row(gz, nprocs=2, chunk_rows=chunk_rows, tile=tile,
                           steps=steps, lam1=lam1)
            parity = float(np.abs(np.asarray(r1["beta_head"]) -
                                  np.asarray(r2["beta_head"])).max())
            r2["max_abs_beta_diff_vs_1proc"] = parity
            r2["parity_ok"] = bool(parity <= 1e-5)
            rows += [r1, r2]
        return rows, speedup, beta_err, parity
    finally:
        if td_ctx is not None:
            td_ctx.cleanup()


def run():
    """Full-size committed row set (benchmarks/run.py figure entry)."""
    rows, speedup, beta_err, parity = _bench(
        n=24576, p=1024, density=0.01, chunk_rows=4096, tile=128, steps=4)
    return {"figure": "ingest_bench",
            "prefetch_speedup": round(speedup, 3),
            "pipeline_beta_err": beta_err,
            "multihost_beta_err": parity,
            "rows": rows}


def smoke() -> int:
    rows, speedup, beta_err, _ = _bench(
        n=1536, p=64, density=0.05, chunk_rows=256, tile=16, steps=3,
        with_multihost=False)
    for r in rows:
        print(r)
    # pipeline on/off must be the SAME fit — identical chunk values reach
    # the same compiled superstep, so β agrees to float noise
    assert beta_err <= 1e-6, f"pipeline on/off diverged: {beta_err}"
    assert rows[0]["rows_per_s"] > 0 and rows[2]["rows_per_s"] > 0
    # plumbing only (tiny problem: wall is compile/dispatch noise);
    # the committed full-size run carries the >1.0x claim
    assert speedup > 0.3, speedup
    print(f"INGEST_BENCH_SMOKE_OK speedup={speedup:.2f}")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    if args.smoke:
        return smoke()
    res = run()
    for r in res["rows"]:
        print(r)
    out = _REPO / "results" / "benchmarks" / "ingest_bench.json"
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(res, indent=2))
    print(f"prefetch_speedup={res['prefetch_speedup']} "
          f"multihost_beta_err={res['multihost_beta_err']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
