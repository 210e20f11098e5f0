"""Generate the §Benchmarks section from the committed
results/benchmarks/*.json records (kernels, fig2_4_l1, path_bench,
cv_bench, ...) and the §Static analysis gate row.

    PYTHONPATH=src:. python -m benchmarks.make_report > /tmp/tables.md
"""
from __future__ import annotations

import json
import pathlib

BENCH_RESULTS = pathlib.Path(__file__).resolve().parents[1] / "results" \
    / "benchmarks"

# figure name -> (ordered columns, column header overrides); figures not
# listed fall back to the union of row keys in insertion order
BENCH_COLUMNS = {
    "kernels": ["name", "us_per_call", "derived"],
    "fig2_4_l1": ["dataset", "algo", "subopt", "subopt_at_10", "auprc",
                  "nnz", "iters", "wall_s"],
    "path_bench": ["case", "n_lambdas", "setup_s", "warm_path_s",
                   "warm_per_lambda_s", "cold_session_s", "cold_oneshot_s",
                   "speedup_vs_cold_session", "speedup_vs_cold_oneshot",
                   "warm_iters", "cold_iters", "compile_count"],
    "cv_bench": ["case", "n_folds", "n_lambdas", "setup_s", "cv_s",
                 "naive_s", "naive_setup_s", "wall_ratio_vs_naive",
                 "compiles_masked", "compiles_naive", "best_index",
                 "lam_best"],
    "streaming_bench": ["case", "n", "p", "chunk_rows", "n_chunks",
                        "total_row_mb", "chunk_buffer_mb", "buffer_ratio",
                        "transfer_s", "fit_s", "fit_serial_s",
                        "overlap_efficiency", "iters", "nnz",
                        "max_abs_beta_diff_vs_dense"],
    "straggler_bench": ["arm", "problem", "num_processes", "slow_factor",
                        "fault_spec", "phase_aware", "tile_cost_s",
                        "supersteps", "wall_s", "wall_per_superstep_s",
                        "recovery_vs_alb_off", "f_final", "nnz",
                        "final_budgets", "node_speeds", "compute_speeds"],
    "ingest_bench": ["case", "format", "rows", "features", "chunks",
                     "nnz_total", "file_mb", "scan_s", "pass_s",
                     "rows_per_s", "nnz_per_s", "hash_dim", "supersteps",
                     "prefetch", "cache_chunks", "wall_s",
                     "prefetch_speedup", "num_processes", "f_final",
                     "max_abs_beta_diff_vs_1proc", "parity_ok"],
    "serving_bench": ["case", "mode", "dtype", "n_requests", "rows_per_s",
                      "p50_ms", "p99_ms", "mean_batch",
                      "speedup_vs_batch1", "artifact_bytes",
                      "size_ratio_fp32_over_int8", "max_margin_err",
                      "max_err_bound", "max_abs_err_vs_oracle",
                      "n_active", "compiled_shapes"],
}

def _fmt_cell(v):
    if isinstance(v, float):
        return f"{v:.4g}"
    if isinstance(v, list):
        s = ", ".join(_fmt_cell(x) for x in v[:6])
        return s + (", …" if len(v) > 6 else "")
    return str(v)


def bench_table(name: str, rows: list) -> str:
    cols = BENCH_COLUMNS.get(name)
    if cols is None:
        cols = []
        for r in rows:
            cols.extend(k for k in r if k not in cols)
    lines = [f"### {name}", "",
             "| " + " | ".join(cols) + " |",
             "|" + "---|" * len(cols)]
    for r in rows:
        lines.append("| " + " | ".join(_fmt_cell(r.get(c, "—"))
                                       for c in cols) + " |")
    return "\n".join(lines)


def benchmarks_section() -> str:
    """§Benchmarks: one table per committed results/benchmarks/*.json."""
    if not BENCH_RESULTS.exists():
        return ""
    out = ["## Benchmarks", ""]
    for f in sorted(BENCH_RESULTS.glob("*.json")):
        if f.name == "analysis.json":      # rendered by analysis_section
            continue
        rec = json.loads(f.read_text())
        rows = rec.get("rows", [])
        if not rows:
            continue
        out.append(bench_table(rec.get("figure", f.stem), rows))
        out.append("")
    return "\n".join(out) if len(out) > 2 else ""


def analysis_section() -> str:
    """§Static analysis: the lint/audit gate state, from the summary that
    ``python -m repro.analysis --check --audit --json …`` writes."""
    f = BENCH_RESULTS / "analysis.json"
    if not f.exists():
        return ""
    rec = json.loads(f.read_text())
    audit = rec.get("audit", {})
    n_ok = sum(1 for r in audit.values() if r.get("status") == "ok")
    n_fail = len(audit) - n_ok
    lines = [
        "## Static analysis (lint & audit gate)",
        "",
        "| files scanned | rules | findings | new | baselined | "
        "audits ok | audits failed |",
        "|---|---|---|---|---|---|---|",
        f"| {rec.get('files_scanned', '—')} | {len(rec.get('rules', []))} "
        f"| {rec.get('violations_total', '—')} "
        f"| {rec.get('violations_new', '—')} "
        f"| {rec.get('violations_baselined', '—')} "
        f"| {n_ok if audit else '—'} | {n_fail if audit else '—'} |",
    ]
    by_code = rec.get("by_code", {})
    if by_code:
        lines += ["", "Baselined/waived findings by rule: "
                  + ", ".join(f"{c}={n}" for c, n in sorted(by_code.items()))
                  + "  (every entry carries a reason in "
                  "`src/repro/analysis/baseline.json`; see DESIGN.md §11)"]
    return "\n".join(lines)


def main():
    section = benchmarks_section()
    if section:
        print(section)
    section = analysis_section()
    if section:
        print(section)


if __name__ == "__main__":
    main()
