"""Generate the EXPERIMENTS.md §Dry-run / §Roofline tables from the
results/dryrun JSON records, and the §Benchmarks section from the committed
results/benchmarks/*.json records (kernels, fig2_4_l1, path_bench,
cv_bench, ...).

    PYTHONPATH=src:. python -m benchmarks.make_report > /tmp/tables.md
"""
from __future__ import annotations

import json
import pathlib

RESULTS = pathlib.Path(__file__).resolve().parents[1] / "results" / "dryrun"
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

ARCH_ORDER = ["gemma3-12b", "qwen2.5-32b", "phi4-mini-3.8b",
              "mistral-large-123b", "zamba2-1.2b", "deepseek-v2-lite-16b",
              "mixtral-8x7b", "xlstm-1.3b", "llama-3.2-vision-11b",
              "whisper-tiny", "dglmnet"]
SHAPE_ORDER = ["train_4k", "prefill_32k", "decode_32k", "long_500k",
               "glm_web", "glm_tall"]


def load(mesh_tag):
    recs = {}
    d = RESULTS / mesh_tag
    if not d.exists():
        return recs
    for f in d.glob("*.json"):
        r = json.loads(f.read_text())
        recs[(r["arch"], r["shape"])] = r
    return recs


def fmt_s(x):
    if x >= 1.0:
        return f"{x:.2f}s"
    return f"{x * 1e3:.1f}ms"


def roofline_table(recs, mesh_tag):
    lines = [
        f"### Mesh {mesh_tag}",
        "",
        "| arch | shape | compute | memory | collective | dominant | "
        "MODEL_FLOPs | useful ratio | peak GB/chip | status |",
        "|---|---|---|---|---|---|---|---|---|---|",
    ]
    for arch in ARCH_ORDER:
        for shape in SHAPE_ORDER:
            r = recs.get((arch, shape))
            if r is None:
                continue
            if r["status"] == "skipped":
                lines.append(f"| {arch} | {shape} | — | — | — | — | — | — |"
                             f" — | skipped: {r['reason'].split(':')[-1].strip()} |")
                continue
            if r["status"] != "ok":
                lines.append(f"| {arch} | {shape} | — | — | — | — | — | — |"
                             f" — | {r['status']} |")
                continue
            ro = r["roofline"]
            mf = r.get("model_flops")
            ur = r.get("useful_compute_ratio")
            peak = r.get("memory", {}).get("peak_bytes_est", 0) / 1e9
            lines.append(
                f"| {arch} | {shape} | {fmt_s(ro['compute_s'])} | "
                f"{fmt_s(ro['memory_s'])} | {fmt_s(ro['collective_s'])} | "
                f"**{ro['dominant']}** | {mf:.2e} | "
                f"{ur:.2f} | {peak:.1f} | ok |")
    return "\n".join(lines)


def summary(recs):
    n_ok = sum(r["status"] == "ok" for r in recs.values())
    n_skip = sum(r["status"] == "skipped" for r in recs.values())
    n_fail = len(recs) - n_ok - n_skip
    return n_ok, n_skip, n_fail


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
    print("## Dry-run / Roofline")
    print()
    for mesh_tag in ("1x16x16", "2x16x16"):
        recs = load(mesh_tag)
        ok, skip, fail = summary(recs)
        print(f"<!-- {mesh_tag}: ok={ok} skipped={skip} failed={fail} -->")
        print(roofline_table(recs, mesh_tag))
        print()
    section = benchmarks_section()
    if section:
        print(section)
    section = analysis_section()
    if section:
        print(section)


if __name__ == "__main__":
    main()
