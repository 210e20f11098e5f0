"""Trace/metrics/convergence summarizer: ``python -m
repro.launch.trace_report DIR`` (DESIGN.md §12).

A traced run (``REPRO_TRACE=dir`` or ``dist_run --trace dir``) leaves
three artifact families in one directory:

  * ``trace_<pid>.json`` shards (+ ``trace_merged.json``) — Chrome
    trace-event spans, one pid lane per process;
  * ``metrics_<pid>.json`` — counter/gauge/histogram snapshots;
  * ``convergence_<pid>.jsonl`` — the solver's per-superstep event
    stream.

This CLI digests them into the terminal summary an operator wants BEFORE
opening Perfetto: top spans by self time (a span's duration less what
its child spans on the same thread cover, so nested spans never count
twice), per-process phase attribution (which node is slow, and in WHICH
phase — compute vs network is the straggler-diagnosis question), merged
metrics, and the convergence tail.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

from repro.obs import convergence as conv_lib
from repro.obs import metrics as metrics_lib
from repro.timing import percentiles

# span name -> diagnosis phase bucket (everything else: "other"); a span
# is charged its self time.  ``solver/superstep`` and its ``solver/sync``
# are the superstep from dispatch to metrics on the host; ``solver/run``'s
# self time is the outer loop's bookkeeping between supersteps; the λ-path
# loop's spans hold λ set-up, screening and the KKT checks.
_PHASE_OF_SPAN = {
    "solver/superstep": "compute",
    "solver/sync": "compute",
    "solver/run": "outer_loop",
    "solver/path": "lambda_path",
    "solver/lambda": "lambda_path",
    "solver/screen": "lambda_path",
    "solver/kkt": "lambda_path",
    "solver/stream_stats": "compute",
    "solver/stream_sweep": "compute",
    "solver/stream_line_search": "compute",
    "solver/fault_sleep": "injected_wait",
    "io/parse_chunk": "io",
    "io/prefetch_produce": "io",
    "ckpt/save": "checkpoint",
    "ckpt/commit": "checkpoint",
    "ckpt/restore": "checkpoint",
    "serve/flush": "serve",
}


def _iter_spans(trace: dict):
    """Yield (pid, tid, name, dur_us, self_us) for every balanced B/E
    pair.  ``self_us`` is the duration less what the span's direct
    children on the same thread cover (they nest, so their durations
    add)."""
    stacks: dict = {}
    for ev in trace.get("traceEvents", []):
        ph = ev.get("ph")
        if ph == "B":
            stacks.setdefault((ev["pid"], ev["tid"]), []).append([ev, 0.0])
        elif ph == "E":
            st = stacks.get((ev["pid"], ev["tid"]))
            if st:
                b, children = st.pop()
                dur = max(ev["ts"] - b["ts"], 0.0)
                if st:
                    st[-1][1] += dur
                yield (ev["pid"], ev["tid"], b["name"], dur,
                       max(dur - children, 0.0))


def load_spans(dir: pathlib.Path):
    """All spans across every shard (prefers the per-pid shards; falls
    back to ``trace_merged.json`` when only the merge exists)."""
    shards = sorted(p for p in dir.glob("trace_*.json")
                    if p.name != "trace_merged.json")
    if not shards:
        merged = dir / "trace_merged.json"
        shards = [merged] if merged.exists() else []
    spans = []
    for p in shards:
        spans.extend(_iter_spans(json.loads(p.read_text())))
    return spans


def span_table(spans) -> list:
    """Per-name totals sorted by self time: the 'where did the wall go'
    table (the self times add up to the time the outermost spans
    cover, each instant once)."""
    by_name: dict = {}
    for _, _, name, dur, self_us in spans:
        by_name.setdefault(name, []).append((dur, self_us))
    rows = []
    for name, pairs in by_name.items():
        pct = percentiles([d for d, _ in pairs])
        rows.append({"span": name, "count": len(pairs),
                     "self_ms": round(sum(s for _, s in pairs) / 1e3, 3),
                     "total_ms": round(sum(d for d, _ in pairs) / 1e3, 3),
                     "p50_us": round(pct["p50"], 1),
                     "p99_us": round(pct["p99"], 1)})
    rows.sort(key=lambda r: -r["self_ms"])
    return rows


def phase_attribution(dir: pathlib.Path, spans) -> dict:
    """Per-process µs by diagnosis phase, each span charged its self time.

    The convergence streams carry the solver's OWN per-phase attribution
    (``phase_us`` — fault-plan/probe-derived, including "network"/"io"
    wait states the host spans cannot see); span buckets fill in the io/
    checkpoint/serve side.  A node whose excess shows up under compute is
    an ALB problem; under network/io it is not (DESIGN.md §12)."""
    per_pid: dict = {}
    for pid, _, name, _, self_us in spans:
        bucket = _PHASE_OF_SPAN.get(name, "other")
        per_pid.setdefault(pid, {})[bucket] = \
            per_pid.setdefault(pid, {}).get(bucket, 0.0) + self_us
    for conv in sorted(dir.glob("convergence_*.jsonl")):
        pid = conv.stem.split("_", 1)[1]
        pid = int(pid) if pid.isdigit() else pid
        slot = per_pid.setdefault(pid, {})
        for ev in conv_lib.read_events(conv):
            for phase, us in (ev.get("phase_us") or {}).items():
                key = f"solver.{phase}"
                slot[key] = slot.get(key, 0.0) + float(us)
    return {str(pid): {k: round(v, 1) for k, v in sorted(d.items())}
            for pid, d in sorted(per_pid.items())}


def merged_metrics(dir: pathlib.Path):
    snaps = [json.loads(p.read_text())
             for p in sorted(dir.glob("metrics_*.json"))]
    return metrics_lib.merge_all(snaps) if snaps else None


def convergence_summary(dir: pathlib.Path):
    streams = sorted(dir.glob("convergence_*.jsonl"))
    if not streams:
        return None
    events = []
    for p in streams:
        events.extend(conv_lib.read_events(p))
    if not events:
        return None
    events.sort(key=lambda e: (e.get("step") or 0))
    last = events[-1]
    return {
        "n_events": len(events),
        "n_streams": len(streams),
        "final_f": last.get("f"),
        "final_nnz": last.get("nnz"),
        "lam_points": len({e.get("lam_index") for e in events}),
        "supersteps": last.get("supersteps"),
        "sweep_tile_launches": last.get("sweep_tile_launches"),
        "sweep_tiles_skipped": last.get("sweep_tiles_skipped"),
        "mean_step_us": round(
            sum(e["step_us"] for e in events
                if e.get("step_us")) / max(
                sum(1 for e in events if e.get("step_us")), 1), 1),
    }


def summarize(dir) -> dict:
    dir = pathlib.Path(dir)
    spans = load_spans(dir)
    return {
        "dir": str(dir),
        "n_spans": len(spans),
        "spans": span_table(spans),
        "phase_attribution": phase_attribution(dir, spans),
        "metrics": merged_metrics(dir),
        "convergence": convergence_summary(dir),
    }


def _print_summary(s: dict):
    print(f"== trace report: {s['dir']} ({s['n_spans']} spans) ==")
    if s["spans"]:
        print("\n-- top spans (by self time) --")
        print(f"{'span':32} {'count':>7} {'self_ms':>10} {'total_ms':>10} "
              f"{'p50_us':>9} {'p99_us':>9}")
        for r in s["spans"][:12]:
            print(f"{r['span']:32} {r['count']:>7} {r['self_ms']:>10} "
                  f"{r['total_ms']:>10} {r['p50_us']:>9} {r['p99_us']:>9}")
    if s["phase_attribution"]:
        print("\n-- per-process phase attribution (µs) --")
        for pid, phases in s["phase_attribution"].items():
            parts = ", ".join(f"{k}={v:.0f}" for k, v in phases.items())
            print(f"  pid {pid}: {parts}")
    m = s["metrics"]
    if m:
        print("\n-- merged metrics --")
        for name, v in sorted(m.get("counters", {}).items()):
            print(f"  counter {name} = {v}")
        for name, g in sorted(m.get("gauges", {}).items()):
            print(f"  gauge   {name} = {g['value']}")
        for name, h in sorted(m.get("histograms", {}).items()):
            p50 = metrics_lib.snapshot_quantile(h, 50)
            p99 = metrics_lib.snapshot_quantile(h, 99)
            fmt = lambda v: "-" if v is None else f"{v:.3g}"
            print(f"  hist    {name}: n={h['n']} "
                  f"p50~{fmt(p50)} p99~{fmt(p99)}")
    c = s["convergence"]
    if c:
        print("\n-- convergence --")
        print(f"  {c['n_events']} events / {c['n_streams']} stream(s); "
              f"final f={c['final_f']} nnz={c['final_nnz']} "
              f"supersteps={c['supersteps']} "
              f"mean_step_us={c['mean_step_us']}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("dir", help="trace/metrics/convergence directory")
    ap.add_argument("--json", default="",
                    help="also write the full summary as JSON here")
    args = ap.parse_args(argv)
    d = pathlib.Path(args.dir)
    if not d.is_dir():
        print(f"trace_report: {d} is not a directory", file=sys.stderr)
        return 2
    s = summarize(d)
    _print_summary(s)
    if args.json:
        pathlib.Path(args.json).write_text(json.dumps(s, indent=2))
    return 0


if __name__ == "__main__":
    sys.exit(main())
