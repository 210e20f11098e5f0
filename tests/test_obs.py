"""repro.obs contract tests (DESIGN.md §12): trace round-trip and
Perfetto-format invariants, the <5µs disabled-span overhead bound,
metrics merge associativity, the pinned convergence schema, and the
trace_report summarizer."""
import json
import pathlib
import sys
import threading
import time

import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "src"))

from repro.obs import convergence as conv     # noqa: E402
from repro.obs import metrics                 # noqa: E402
from repro.obs import trace                   # noqa: E402
from repro.timing import percentiles          # noqa: E402


# -------------------------------------------------------------------- trace

class TestTrace:
    def test_round_trip_chrome_format(self, tmp_path):
        tr = trace.Tracer(tmp_path, pid=7, jax_annotations=False)
        with tr.span("outer", args={"k": 1}):
            with tr.span("inner"):
                pass
        tr.instant("mark")
        path = tr.save()
        assert path == tmp_path / "trace_7.json"
        data = json.loads(path.read_text())
        evs = data["traceEvents"]
        # every event carries the Chrome trace-event envelope
        for e in evs:
            assert {"ph", "pid", "tid", "name"} <= set(e)
            if e["ph"] != "M":
                assert isinstance(e["ts"], float)
            assert e["pid"] == 7
        assert [e["name"] for e in evs if e["ph"] == "B"] == \
            ["outer", "inner"]
        assert sum(1 for e in evs if e["ph"] == "E") == 2
        assert sum(1 for e in evs if e["ph"] == "i") == 1
        # nesting: inner's E precedes outer's E, timestamps ordered
        body = [e for e in evs if e["ph"] in "BE"]
        assert [e["ph"] for e in body] == ["B", "B", "E", "E"]
        ts = [e["ts"] for e in body]
        assert ts == sorted(ts)
        b = next(e for e in evs if e["ph"] == "B" and e["name"] == "outer")
        assert b["args"] == {"k": 1}

    def test_export_balances_open_and_orphaned_spans(self):
        tr = trace.Tracer(pid=1, capacity=4, jax_annotations=False)
        sp = tr.span("open")
        sp.__enter__()              # never exited
        evs = tr.export()["traceEvents"]
        per_tid = {}
        for e in evs:
            if e["ph"] in "BE":
                per_tid.setdefault(e["tid"], []).append(e["ph"])
        for phs in per_tid.values():
            assert phs.count("B") == phs.count("E")
        # orphan E (its B evicted off the ring) is dropped
        tr2 = trace.Tracer(pid=1, capacity=2, jax_annotations=False)
        for i in range(4):          # 4 B + 4 E through a 2-slot ring
            with tr2.span(f"s{i}"):
                pass
        evs2 = [e for e in tr2.export()["traceEvents"] if e["ph"] in "BE"]
        assert sum(e["ph"] == "B" for e in evs2) == \
            sum(e["ph"] == "E" for e in evs2)

    def test_span_elapsed_us(self):
        tr = trace.Tracer(jax_annotations=False)
        with tr.span("t") as sp:
            time.sleep(0.01)
        assert 8_000 <= sp.elapsed_us <= 500_000

    def test_threads_get_distinct_tid_lanes(self):
        tr = trace.Tracer(pid=0, jax_annotations=False)

        def work():
            with tr.span("worker"):
                pass

        t = threading.Thread(target=work, name="io-thread")
        t.start()
        t.join()
        with tr.span("main"):
            pass
        evs = tr.export()["traceEvents"]
        tids = {e["tid"] for e in evs if e["ph"] == "B"}
        assert len(tids) == 2
        names = {e["args"]["name"] for e in evs
                 if e["ph"] == "M" and e["name"] == "thread_name"}
        assert "io-thread" in names

    def test_disabled_span_overhead_under_5us(self):
        trace.disable()
        samples = []
        for _ in range(1000):
            t0 = time.perf_counter_ns()
            with trace.span("hot/loop"):
                pass
            samples.append((time.perf_counter_ns() - t0) / 1e3)
        p50 = percentiles(samples)["p50"]
        assert p50 < 5.0, f"disabled span p50 {p50:.2f}µs >= 5µs"
        # and no allocation side channel: same cached object every call
        assert trace.span("a") is trace.span("b")

    def test_merge_dir_keeps_all_pid_lanes(self, tmp_path):
        for pid in (0, 1):
            tr = trace.Tracer(tmp_path, pid=pid, jax_annotations=False)
            with tr.span("step"):
                pass
            tr.save()
        merged_path = trace.merge_dir(tmp_path)
        assert merged_path == tmp_path / "trace_merged.json"
        evs = json.loads(merged_path.read_text())["traceEvents"]
        assert {e["pid"] for e in evs if e["ph"] == "M"} == {0, 1}
        # metadata sorts first; re-merging skips the merged file itself
        assert evs[0]["ph"] == "M"
        again = json.loads(trace.merge_dir(tmp_path).read_text())
        assert len(again["traceEvents"]) == len(evs)

    def test_enable_disable_module_tracer(self, tmp_path):
        try:
            tr = trace.enable(tmp_path, jax_annotations=False)
            assert trace.get_tracer() is tr and tr.enabled
            assert trace.trace_dir() == tmp_path
            with trace.span("on"):
                pass
            assert tr.export()["traceEvents"]
        finally:
            trace.disable()
        assert not trace.get_tracer().enabled
        assert trace.trace_dir() is None


# ------------------------------------------------------------------ metrics

def _snap(counter_v, gauge_pairs, hist_obs):
    r = metrics.MetricsRegistry()
    r.counter("c").inc(counter_v)
    for v in gauge_pairs:
        r.gauge("g").set(v)
    h = r.histogram("h", buckets=(1.0, 10.0, 100.0))
    for v in hist_obs:
        h.observe(v)
    return r.snapshot()


class TestMetrics:
    def test_merge_is_associative_and_commutative(self):
        a = _snap(1, [3.0], [0.5, 20.0])
        b = _snap(2, [7.0], [5.0])
        c = _snap(4, [1.0], [200.0, 0.1])
        left = metrics.merge(metrics.merge(a, b), c)
        right = metrics.merge(a, metrics.merge(b, c))
        assert left == right
        assert metrics.merge(a, b) == metrics.merge(b, a)
        assert left["counters"]["c"] == 7.0
        assert left["histograms"]["h"]["n"] == 5
        assert left == metrics.merge_all([a, b, c])

    def test_gauge_merge_keeps_latest_seq(self):
        a = _snap(0, [5.0], [])
        b = _snap(0, [9.0], [])       # later registry -> larger seq
        assert metrics.merge(a, b)["gauges"]["g"]["value"] == 9.0
        assert metrics.merge(b, a)["gauges"]["g"]["value"] == 9.0

    def test_histogram_bucket_mismatch_raises(self):
        r1 = metrics.MetricsRegistry()
        r1.histogram("h", buckets=(1.0, 2.0)).observe(0.5)
        r2 = metrics.MetricsRegistry()
        r2.histogram("h", buckets=(1.0, 3.0)).observe(0.5)
        with pytest.raises(ValueError):
            metrics.merge(r1.snapshot(), r2.snapshot())

    def test_histogram_quantile_and_snapshot_quantile_agree(self):
        h = metrics.Histogram(buckets=(1.0, 10.0, 100.0))
        for v in (0.5, 2.0, 3.0, 50.0):
            h.observe(v)
        snap = {"buckets": list(h.buckets), "counts": list(h.counts),
                "sum": h.sum, "n": h.n}
        for q in (50.0, 99.0):
            assert metrics.snapshot_quantile(snap, q) == h.quantile(q)
        assert h.quantile(50.0) <= 10.0   # median falls in (1, 10] bucket

    def test_default_registry_save(self, tmp_path):
        metrics.counter("obs_test.save").inc()
        path = metrics.save_default(tmp_path)
        assert path.name.startswith("metrics_")
        snap = json.loads(path.read_text())
        assert snap["counters"]["obs_test.save"] >= 1.0


# -------------------------------------------------------------- convergence

class TestConvergence:
    GOLDEN_KEYS = (
        "schema", "step", "outer_it", "lam_index", "lam1", "lam2",
        "f", "loss", "deviance", "alpha", "mu", "nnz", "accepted_unit",
        "active_size", "screened", "kkt_violations",
        "supersteps", "sweep_tile_launches", "sweep_tiles_skipped",
        "step_us", "phase_us",
    )

    def test_schema_keys_are_golden(self):
        """The schema is a public contract: adding/renaming a key must
        bump SCHEMA_VERSION and update this golden copy consciously."""
        assert conv.SCHEMA_KEYS == self.GOLDEN_KEYS
        assert conv.SCHEMA_VERSION == 1

    def test_emit_round_trip_fills_missing_with_none(self, tmp_path):
        p = tmp_path / "conv.jsonl"
        with conv.ConvergenceStream(p) as s:
            s.emit(step=0, f=1.5, nnz=3)
            s.emit(step=1, f=1.2, nnz=4, phase_us={"sweep": 10.0})
        evs = conv.read_events(p)
        assert len(evs) == 2
        assert list(evs[0]) == list(self.GOLDEN_KEYS)
        assert evs[0]["schema"] == 1 and evs[0]["f"] == 1.5
        assert evs[0]["alpha"] is None
        assert evs[1]["phase_us"] == {"sweep": 10.0}

    def test_emit_rejects_unknown_field(self, tmp_path):
        with conv.ConvergenceStream(tmp_path / "c.jsonl") as s:
            with pytest.raises(ValueError, match="unknown convergence"):
                s.emit(step=0, objektive=1.0)

    def test_reader_rejects_schema_mismatch(self, tmp_path):
        p = tmp_path / "c.jsonl"
        p.write_text(json.dumps({"schema": 999, "step": 0}) + "\n")
        with pytest.raises(ValueError, match="schema 999"):
            conv.read_events(p)

    def test_solver_emits_stream(self, tmp_path):
        """A real (tiny) fit wired to a stream yields one event per outer
        iteration with live objective/active-set numbers."""
        from repro.core.dglmnet import DGLMNETConfig
        from repro.core.solver import GLMSolver

        rng = np.random.default_rng(0)
        X = rng.normal(size=(48, 24)).astype(np.float32)
        y = (X @ (rng.normal(size=24) * (rng.random(24) < 0.3))
             + 0.05 * rng.normal(size=48)).astype(np.float32)
        solver = GLMSolver(X, y, config=DGLMNETConfig(
            tile_size=8, max_outer=5, tol=0.0))
        path = tmp_path / "conv.jsonl"
        solver.set_convergence_stream(path)
        solver.fit(lam1=0.05, lam2=1e-3)
        evs = conv.read_events(path)
        assert len(evs) == 5
        assert [e["step"] for e in evs] == list(range(1, 6))
        # single fit: the 1-based outer iteration IS the global step
        assert all(e["outer_it"] == e["step"] for e in evs)
        assert all(isinstance(e["f"], float) for e in evs)
        assert evs[-1]["active_size"] == 24
        assert evs[-1]["nnz"] >= 1


# ------------------------------------------------------------- trace_report

class TestTraceReport:
    def _populate(self, tmp_path):
        for pid, dur in ((0, 1_000), (1, 4_000)):
            tr = trace.Tracer(tmp_path, pid=pid, jax_annotations=False)
            tr.span("solver/superstep").__enter__()
            # fabricate a deterministic duration: append the matching E
            # dur µs after the recorded B (ring stores ns)
            ph, ts, tid, name, _ = tr._events[0]
            tr._events.append(("E", ts + dur * 1000, tid, name, None))
            tr.save()
        with conv.ConvergenceStream(tmp_path / "convergence_0.jsonl") as s:
            s.emit(step=0, f=2.0, nnz=1, supersteps=1, step_us=900.0,
                   phase_us={"sweep": 700.0, "line_search": 200.0})
        r = metrics.MetricsRegistry()
        r.counter("io.chunk_cache.hit").inc(3)
        r.save(tmp_path / "metrics_0.json")

    def test_summarize_and_bench_row(self, tmp_path):
        """The summary charges each span its self time: nested spans do
        not count twice in the span table or the phase attribution."""
        from repro.launch import trace_report

        self._populate(tmp_path)
        tr = trace.Tracer(tmp_path, pid=2, jax_annotations=False)
        # run 0..10 ms holds a superstep 1..7 ms, which holds its sync
        # 3..6 ms; then the KKT check 11..12 ms inside the λ 0..12 ms
        for ph, ms, name in (("B", 0, "solver/lambda"), ("B", 0, "solver/run"),
                             ("B", 1, "solver/superstep"),
                             ("B", 3, "solver/sync"), ("E", 6, "solver/sync"),
                             ("E", 7, "solver/superstep"),
                             ("E", 10, "solver/run"), ("B", 11, "solver/kkt"),
                             ("E", 12, "solver/kkt"),
                             ("E", 12, "solver/lambda")):
            tr._events.append((ph, ms * 1_000_000, 1, name, None))
        tr.save()
        s = trace_report.summarize(tmp_path)
        assert s["n_spans"] == 7
        rows = {r["span"]: r for r in s["spans"]}
        assert rows["solver/superstep"]["count"] == 3
        # pids 0 and 1: 1 + 4 ms, no children; pid 2: 6 ms less its sync
        assert rows["solver/superstep"]["total_ms"] == \
            pytest.approx(11.0, rel=0.01)
        assert rows["solver/superstep"]["self_ms"] == \
            pytest.approx(8.0, rel=0.01)
        assert rows["solver/run"]["total_ms"] == pytest.approx(10.0)
        assert rows["solver/run"]["self_ms"] == pytest.approx(4.0)
        assert rows["solver/lambda"]["self_ms"] == pytest.approx(1.0)
        # self times add up to what the outermost spans cover: 1 + 4 ms
        # (pids 0 and 1) and 12 ms (pid 2)
        assert sum(r["self_ms"] for r in rows.values()) == \
            pytest.approx(5.0 + 12.0)
        assert s["spans"][0]["span"] == "solver/superstep"
        attrib = s["phase_attribution"]
        assert attrib["0"]["compute"] == pytest.approx(1_000.0)
        assert attrib["1"]["compute"] == pytest.approx(4_000.0)
        assert attrib["0"]["solver.sweep"] == pytest.approx(700.0)
        assert attrib["2"] == {"compute": pytest.approx(6_000.0),
                               "outer_loop": pytest.approx(4_000.0),
                               "lambda_path": pytest.approx(2_000.0)}
        assert s["metrics"]["counters"]["io.chunk_cache.hit"] == 3.0
        assert s["convergence"]["n_events"] == 1
        assert s["convergence"]["final_f"] == 2.0

    def test_cli_writes_outputs(self, tmp_path, capsys):
        from repro.launch import trace_report

        self._populate(tmp_path)
        out_json = tmp_path / "summary.json"
        rc = trace_report.main([str(tmp_path), "--json", str(out_json)])
        assert rc == 0
        out = capsys.readouterr().out
        assert "solver/superstep" in out and "self_ms" in out
        summary = json.loads(out_json.read_text())
        assert summary["n_spans"] == 2
        [row] = summary["spans"]
        assert row["self_ms"] == row["total_ms"] == pytest.approx(5.0,
                                                                   rel=0.01)


# ----------------------------------------------------- the solver's spans

def _spans(events):
    """Every balanced span of one thread's export, in the order they
    close: {name, args, parent, t0, t1} (µs)."""
    out, stack = [], []
    for e in events:
        if e["ph"] == "B":
            stack.append(e)
        elif e["ph"] == "E":
            b = stack.pop()
            out.append({"name": b["name"], "args": b.get("args"),
                        "parent": stack[-1]["name"] if stack else None,
                        "t0": b["ts"], "t1": e["ts"]})
    return out


def _solver(**kw):
    from repro.core.dglmnet import DGLMNETConfig
    from repro.core.solver import GLMSolver
    from repro.data import synthetic

    ds = synthetic.make_dense(n=96, p=32, k_true=4, seed=3)
    return GLMSolver(ds.train.X, np.asarray(ds.train.y), config=DGLMNETConfig(
        family="logistic", tile_size=8, coupling="jacobi", max_outer=12,
        tol=1e-7), **kw)


def _traced(fn):
    """Run ``fn`` with the module tracer on in memory; its spans."""
    tr = trace.enable(None, jax_annotations=False)
    try:
        fn()
    finally:
        trace.disable()
    return _spans(tr.export()["traceEvents"])


class TestSolverSpans:
    PARENT = {"solver/lambda": "solver/path", "solver/screen": "solver/lambda",
              "solver/kkt": "solver/lambda", "solver/superstep": "solver/run",
              "solver/sync": "solver/superstep"}

    def test_fit_path_exports_the_span_tree(self):
        s = _solver()
        K = 4
        spans = _traced(lambda: s.fit_path(n_lambdas=K, lam_ratio=0.05))
        names = [sp["name"] for sp in spans]
        for name in ("solver/path", "solver/lambda", "solver/screen",
                     "solver/run", "solver/superstep", "solver/sync",
                     "solver/kkt"):
            assert name in names, name
        assert names.count("solver/path") == 1 and names[-1] == "solver/path"
        for sp in spans:
            if sp["name"] in self.PARENT:
                assert sp["parent"] == self.PARENT[sp["name"]], sp
        # every KKT round of a λ is one solver/run inside it; the λ_max
        # null fit (the intercept) is one run of the path outside any λ
        runs = [sp for sp in spans if sp["name"] == "solver/run"]
        in_lambda = [sp for sp in runs if sp["parent"] == "solver/lambda"]
        assert {sp["parent"] for sp in runs} <= {"solver/lambda",
                                                 "solver/path"}
        assert len(in_lambda) == s.launch_stats["kkt_rounds"]
        assert all(sp["args"]["round"] >= 0 for sp in in_lambda)
        assert [sp["args"]["k"] for sp in spans
                if sp["name"] == "solver/lambda"] == list(range(K))
        # the sync nests in its superstep, which closes after it
        steps = [sp for sp in spans if sp["name"] == "solver/superstep"]
        syncs = [sp for sp in spans if sp["name"] == "solver/sync"]
        assert len(steps) == len(syncs) == s.launch_stats["supersteps"]
        for st, sy in zip(steps, syncs):
            assert st["t0"] <= sy["t0"] <= sy["t1"] <= st["t1"]
        assert s.launch_stats["lambdas"] == K
        assert s.launch_stats["kkt_rounds"] >= K

    @pytest.mark.parametrize("telemetry", [False, True])
    def test_one_superstep_span_per_superstep_under_a_fault_plan(
            self, telemetry):
        from repro.dist import faults
        from repro.dist.telemetry import SuperstepTelemetry

        from repro.sharding import compat

        # telemetry-driven ALB maps node speeds onto a mesh's columns
        s = _solver(fault_plan=faults.FaultPlan(num_processes=1,
                                                tile_cost_s=1e-5),
                    **({"telemetry": SuperstepTelemetry(1, warmup=1),
                        "mesh": compat.make_mesh((1, 1), ("data", "model"))}
                       if telemetry else {}))
        lams = s.lambda_max() * np.array([0.5, 0.2])
        n0 = s.launch_stats["supersteps"]
        spans = _traced(lambda: s.fit_path(lambdas=lams))
        steps = [sp for sp in spans if sp["name"] == "solver/superstep"]
        assert len(steps) == s.launch_stats["supersteps"] - n0 > 0
        assert all(set(sp["args"]) == {"step", "tiles"} for sp in steps)
        syncs = [sp for sp in spans if sp["name"] == "solver/sync"]
        assert len(syncs) == len(steps)
        assert all(sp["parent"] == "solver/superstep" for sp in syncs)
        assert all(sp["parent"] == "solver/run" for sp in steps)
        assert s.launch_stats["lambdas"] == 2

    def test_step_us_is_the_superstep_from_dispatch_to_sync(self, tmp_path):
        s = _solver()
        path = tmp_path / "conv.jsonl"
        s.set_convergence_stream(path)
        spans = _traced(lambda: s.fit(lam1=0.01, max_outer=5, tol=0.0))
        events = conv.read_events(path)
        steps = [sp for sp in spans if sp["name"] == "solver/superstep"]
        syncs = [sp for sp in spans if sp["name"] == "solver/sync"]
        assert len(events) == len(steps) == len(syncs) == 5
        for ev, st, sy in zip(events, steps, syncs):
            assert ev["step_us"] >= sy["t1"] - sy["t0"]
            assert ev["step_us"] == pytest.approx(st["t1"] - st["t0"],
                                                  abs=1.0)
        # a single fit is one solver/run outside any λ
        [run] = [sp for sp in spans if sp["name"] == "solver/run"]
        assert run["parent"] is None and run["args"] is None
