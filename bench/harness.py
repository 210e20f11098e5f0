"""The benchmark harness: one run of one cell.

Everything that belongs to one cell, configuration, traffic mix, per-layer
metric or kernel lives in a file of its own under the benchmark directory,
found by the name ``BENCHMARK.json`` gives it:

    workloads/<cell>.json      configuration, traffic, chips, check limits,
                               the runtime's environment
    configs/<config>.json      the deployment: sizes, solver settings, path
    traffic/<traffic>.json     how the window drives the session
    gen/<generator>.py         makes the data from the seed
    reference/<reference>.py   the plain reference the answers are held to
    metrics/<metric>.py        reads one per-layer metric
    kernels/<module>.py        one Pallas kernel's operations and bytes

A run builds the data and one session, warms it up with a short path,
then runs whole λ-paths for ``--seconds`` and checks a sample of them
against the reference once the window has closed.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import importlib.util
import json
import math
import os
import pathlib
import sys
import tempfile
import time

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
CHECKOUT = BENCH.parent


def cell_environment(argv, root: pathlib.Path = BENCH) -> dict:
    """Set the environment the cell named by ``--workload`` in ``argv``
    asks for (``environment`` in ``workloads/<cell>.json``), for a runtime
    that reads it when JAX starts, so call this before JAX is imported; a
    variable already set is kept.  Returns what the cell asks for."""
    argv = list(argv)
    if "--workload" not in argv[:-1]:
        return {}
    path = root / "workloads" / f"{argv[argv.index('--workload') + 1]}.json"
    if not path.exists():
        return {}
    wanted = json.loads(path.read_text()).get("environment", {})
    for key, value in wanted.items():
        os.environ.setdefault(key, str(value))
    return wanted


class NoDevice(RuntimeError):
    """The accelerator the cell needs is not there."""


def load_module(path: pathlib.Path, tag: str):
    if not path.exists():
        raise FileNotFoundError(f"no {tag} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{tag}_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Registry:
    """The benchmark's files, found by name."""
    root: pathlib.Path = BENCH
    benchmark_path: pathlib.Path = CHECKOUT / "BENCHMARK.json"

    def __post_init__(self):
        self.root = pathlib.Path(self.root)
        self.benchmark = json.loads(pathlib.Path(self.benchmark_path)
                                    .read_text())
        self._modules = {}

    def data(self, kind: str, name: str) -> dict:
        return json.loads((self.root / kind / f"{name}.json").read_text())

    def module(self, kind: str, name: str):
        key = (kind, name)
        if key not in self._modules:
            self._modules[key] = load_module(self.root / kind / f"{name}.py",
                                             kind)
        return self._modules[key]

    def has_module(self, kind: str, name: str) -> bool:
        return (self.root / kind / f"{name}.py").exists()

    def metrics(self, section: str, cell: str) -> list:
        """The metrics of ``section`` that the cell reports."""
        return [m for m in self.benchmark[section]
                if cell in m.get("workloads", [cell])]


@dataclasses.dataclass
class LayerContext:
    """What a per-layer metric reader may read."""
    config: dict
    chips: int
    problem: object
    counters: dict          # program counters over the traced paths
    device_bytes: dict      # bytes the session placed, per device
    trace: object           # trace_reduce.Reduced of the traced paths
    peaks: dict             # the device's row of peaks.json


class CompileCounter:
    """Counts JAX compilations (tracing, lowering, backend compile or a
    persistent-cache load) while armed.  One per process: JAX's monitoring
    listeners cannot be removed once registered."""
    _instance = None

    def __init__(self):
        self.armed = False
        self.events = []

    @classmethod
    def get(cls):
        if cls._instance is None:
            from jax import monitoring
            cls._instance = inst = cls()

            def on_duration(name, _secs, **_kw):
                if inst.armed and (name.startswith("/jax/core/compile/")
                                   or "cache_retrieval" in name):
                    inst.events.append(name)
            monitoring.register_event_duration_secs_listener(on_duration)
        return cls._instance


def _devices(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX found platform {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def _peak_bytes(devs) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


def _profile_options():
    """Device ops and host annotations, without JAX's default tracing of
    every Python call, which would slow the traced path's host side."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def grid_factors(seed: int, traffic: dict) -> np.ndarray:
    """The factors the window's paths scale the cell's grid by, path i by
    entry i modulo their number: ``grid_points`` evenly spaced over
    ``grid_scale``, in an order drawn from the seed.  Every seed runs the
    same set of fits; a window that outlasts the set starts it again, each
    path a whole fit from a cold start."""
    lo, hi = traffic["grid_scale"]
    points = np.linspace(lo, hi, int(traffic["grid_points"]))
    return np.random.default_rng(abs(int(seed))).permutation(points)


def check_answers(reg, config, problem, answers, sample, margins):
    """The numbers compared with the reference, each per sampled path: the
    worst KKT residual and objective gap over its λ, and for the window's
    last path, whose fitted state the session keeps, the worst gap of the
    maintained margins."""
    ref = reg.module("reference", config["reference"])
    per_path = []
    for i in sample:
        lambdas, betas, f, lam2 = answers[i]
        k, g = ref.check_path(problem, config["family"], lambdas, lam2,
                              betas, f)
        numbers = {"kkt": float(np.max(k)), "objective_gap": float(np.max(g))}
        if i == len(answers) - 1:
            numbers["margin_gap"] = ref.margin_gap(problem, betas[-1],
                                                   margins)
        per_path.append(numbers)
    return per_path


def run_cell(args, *, reg=None, require_tpu=True, t_start=None,
             session_hook=None, control=False):
    """One run; returns (result dict, check lines).  ``session_hook``
    wraps the session after it is built (tests plant faults there);
    ``control`` builds it with the configuration's lower-precision control
    settings instead (``bench/readings.py``)."""
    t_start = time.perf_counter() if t_start is None else t_start
    reg = Registry() if reg is None else reg
    cell = reg.data("workloads", args.workload)
    config = reg.data("configs", cell["config"])
    traffic = reg.data("traffic", cell["traffic"])
    chips = int(cell["chips"])
    mesh_shape = tuple(traffic.get("mesh") or (1, 1))
    if math.prod(mesh_shape) != chips:
        raise ValueError(f"mesh {mesh_shape} does not fill {chips} chips")

    import jax
    devs = _devices(chips, require_tpu)
    kind = devs[0].device_kind
    peaks = json.loads((reg.root / "peaks.json").read_text())["devices"]
    if require_tpu and kind not in peaks:
        raise KeyError(f"device kind {kind!r} has no row in peaks.json")
    counter = CompileCounter.get()

    # --- set-up: data, session, warm-up path -------------------------------
    # seconds from process start to the end of each part, and the
    # compilations or cache loads it made, for the log
    marks = {"devices": time.perf_counter() - t_start}
    counter.events.clear()
    counter.armed = True
    gen = reg.module("gen", config["generator"])
    problem = gen.generate(config, traffic, args.seed, mesh_shape)
    marks["data"] = time.perf_counter() - t_start
    from bench import system
    solver = {**config["solver"], **config["control"]["solver"]} \
        if control else None
    session = system.Session(config, traffic, problem, solver=solver)
    if hasattr(problem, "release"):
        problem.release()
    if session_hook is not None:
        session = session_hook(session)
    grid = session.grid()
    marks["session"] = time.perf_counter() - t_start
    with jax.profiler.TraceAnnotation("bench/warmup"):
        session.fit(grid[:int(traffic["warmup_lambdas"])])
    setup_s = time.perf_counter() - t_start
    marks["warm-up"] = setup_s
    counter.armed = False
    setup_compiles = collections.Counter(
        name.rsplit("/", 1)[-1] for name in counter.events)

    # --- the measured window ------------------------------------------------
    factors = grid_factors(args.seed, traffic)
    trace_paths = int(traffic.get("trace_paths", 1)) if args.trace else 0
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace_paths \
        else None
    answers, traced = [], None
    counter.events.clear()
    counter.armed = True
    c0 = session.counters()
    t0 = time.perf_counter()
    while True:
        i = len(answers)
        if i == 0 and trace_paths:
            session.mirror_spans(True)
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=_profile_options())
        lambdas = grid * factors[i % len(factors)]
        with jax.profiler.TraceAnnotation("bench/fit_path"):
            lam, betas, f, _ = session.fit(lambdas)
        answers.append((lam, betas, f, session.lam2))
        if trace_paths and i + 1 == trace_paths:
            jax.profiler.stop_trace()
            session.mirror_spans(False)
            c1 = session.counters()
            traced = {k: c1[k] - c0[k] for k in c0}
            traced["paths"] = trace_paths
        if time.perf_counter() - t0 >= args.seconds and \
                len(answers) >= trace_paths:
            break
    window_s = time.perf_counter() - t0
    counter.armed = False
    compiles = len(counter.events)
    c1 = session.counters()
    counts = {k: c1[k] - c0[k] for k in c0}
    peak = _peak_bytes(devs)
    device_bytes = session.device_bytes()
    margins = session.margins()
    programs = session.programs() if args.trace else ()
    session.close()
    del session
    gc.collect()

    result = {"correct": None, "attempted": len(answers), "failed": 0}
    if args.trace:
        from bench import trace_reduce
        reduced = trace_reduce.reduce_dir(trace_dir, reg, counters=traced,
                                          peaks=peaks.get(kind),
                                          programs=programs)
        ctx = LayerContext(config, chips, problem, traced, device_bytes,
                           reduced, peaks.get(kind, {}))
        metrics = {}
        for m in reg.metrics("per_layer", args.workload):
            value = reg.module("metrics", m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        result["metrics"] = metrics
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        values = {"path_s": window_s / len(answers),
                  "peak_hbm_gib": peak / 2 ** 30, "setup_s": setup_s}
        result["metrics"] = {
            m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
            for m in reg.metrics("end_to_end", args.workload)}
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    if args.trace:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = reduced.breakdown()
    result["device"] = device

    # --- the answers against the reference -----------------------------------
    n = len(answers)
    k = min(int(traffic["check_paths"]), n)
    sample = set(np.random.default_rng([args.seed, 10 ** 6]).choice(
        n, size=k, replace=False).tolist())
    sample = sorted(sample | {n - 1})
    per_path = check_answers(reg, config, problem, answers, sample, margins)
    limits = cell["limits"]
    numbers = {name: max(p[name] for p in per_path if name in p)
               for name in ("kkt", "objective_gap", "margin_gap")}
    numbers["compiles_in_window"] = compiles
    checks = {name: {"value": numbers[name], "limit": limits[name]}
              for name in ("kkt", "objective_gap", "margin_gap",
                           "compiles_in_window")}
    result["correct"] = all(c["value"] <= c["limit"]
                            for c in checks.values())
    result["failed"] = n if compiles else sum(
        1 for p in per_path if any(v > limits[name] for name, v in p.items()))
    result["checks"] = checks
    lines = ["set-up to " + ", ".join(f"{k} {v:.3f} s" for k, v in
                                    marks.items())
             + f"; set-up compilations {dict(setup_compiles)}",
             f"checked paths {sample} of {n}; supersteps in window "
             f"{counts['supersteps']}"]
    lines += [f"check {name}: {c['value']!r} (limit {c['limit']!r})"
              for name, c in checks.items()]
    return result, lines


def main(argv=None, *, reg=None, require_tpu=True, t_start=None):
    import argparse
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result, lines = run_cell(args, reg=reg, require_tpu=require_tpu,
                                 t_start=t_start)
    except NoDevice as e:
        print(f"bench: {e}; nothing was measured", file=sys.stderr)
        return 3
    for line in lines:
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
