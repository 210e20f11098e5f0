"""repro.dist: bootstrap contexts, fault plans, telemetry EMA, hardened ALB
budgets, and the 2-process end-to-end parity/restart runs (DESIGN.md §9).

The multi-process tests spawn coordinated worker processes through
``repro.dist.launcher`` (each with ONE fake CPU device), so they run on this
single-core host exactly like a 2-node job; everything else is plain
host-side unit testing.
"""
import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
SRC = REPO / "src"
PROG = pathlib.Path(__file__).parent / "progs" / "multiproc_glm.py"
sys.path.insert(0, str(SRC))

from repro.core import alb                             # noqa: E402
from repro.dist import bootstrap, faults, launcher     # noqa: E402
from repro.dist.telemetry import SuperstepTelemetry    # noqa: E402


# ---------------------------------------------------------------- bootstrap

class TestBootstrap:
    def test_single_process_context_default(self):
        ctx = bootstrap.context()
        assert ctx.process_id == 0 and ctx.num_processes == 1
        assert ctx.is_coordinator and not ctx.multiprocess

    def test_initialize_is_single_process_noop_without_env(self):
        bootstrap._reset_for_tests()
        try:
            ctx = bootstrap.initialize()
            assert not ctx.multiprocess
        finally:
            bootstrap._reset_for_tests()

    def test_barrier_is_noop_single_process(self):
        bootstrap.barrier("unit")     # must not require a runtime client

    def test_worker_env_round_trip(self):
        env = launcher.worker_env(1, 2, "127.0.0.1:1234")
        assert env["REPRO_DIST_PROCID"] == "1"
        assert env["REPRO_DIST_NPROCS"] == "2"
        assert env["REPRO_DIST_COORD"] == "127.0.0.1:1234"
        assert "--xla_force_host_platform_device_count=1" in env["XLA_FLAGS"]
        assert env["JAX_PLATFORMS"] == "cpu"


# ------------------------------------------------------------------- faults

class TestFaultPlan:
    def test_parse_constant_and_stutter(self):
        plan = faults.FaultPlan.parse("0:2.0,1:4.0@10-20", 2,
                                      tile_cost_s=0.01)
        assert plan.factor(0, 5) == 2.0
        assert plan.factor(1, 5) == 1.0          # outside the window
        assert plan.factor(1, 15) == 4.0
        assert plan.max_factor(15) == 4.0
        assert plan.work_s(1, 15, 3) == pytest.approx(4.0 * 0.01 * 3)

    def test_factors_compose_multiplicatively(self):
        plan = faults.FaultPlan(
            num_processes=1, slowdown=(2.0,),
            stutters=(faults.StutterWindow(0, 0, 5, 3.0),))
        assert plan.factor(0, 2) == 6.0
        assert plan.factor(0, 7) == 2.0

    def test_rejects_speedup_factors(self):
        with pytest.raises(ValueError):
            faults.FaultPlan(num_processes=2, slowdown=(0.5, 1.0))

    def test_rejects_wrong_length(self):
        with pytest.raises(ValueError):
            faults.FaultPlan(num_processes=2, slowdown=(2.0,))

    def test_parse_rejects_unknown_process(self):
        with pytest.raises(ValueError):
            faults.FaultPlan.parse("3:2.0", 2)

    def test_zero_tile_cost_disables_injection(self):
        plan = faults.FaultPlan.parse("1:4.0", 2)
        assert plan.work_s(1, 0, 100) == 0.0


# ---------------------------------------------------------------- telemetry

class TestTelemetry:
    def test_warmup_returns_none_then_speeds(self):
        tel = SuperstepTelemetry(2, warmup=2, ema=0.5)
        tel.record_all(0, np.array([4.0, 4.0]), np.array([1.0, 4.0]))
        assert tel.speeds() is None               # one sample < warmup
        tel.record_all(1, np.array([4.0, 4.0]), np.array([1.0, 4.0]))
        sp = tel.speeds()
        assert sp is not None
        assert sp[0] / sp[1] == pytest.approx(4.0)

    def test_ema_tracks_speed_change(self):
        tel = SuperstepTelemetry(1, warmup=1, ema=0.5)
        tel.record_all(0, np.array([8.0]), np.array([1.0]))   # 8 tiles/s
        tel.record_all(1, np.array([4.0]), np.array([1.0]))   # now 4 tiles/s
        assert tel.speeds()[0] == pytest.approx(6.0)          # midpoint

    def test_invalid_sample_keeps_previous_estimate(self):
        tel = SuperstepTelemetry(2, warmup=1)
        tel.record_all(0, np.array([4.0, 4.0]), np.array([1.0, 2.0]))
        tel.record_all(1, np.array([4.0, 4.0]), np.array([0.0, 2.0]))
        sp = tel.speeds()
        assert sp[0] == pytest.approx(4.0)        # divide-by-zero ignored
        assert sp[1] == pytest.approx(2.0)

    def test_single_process_record_skips_exchange(self):
        tel = SuperstepTelemetry(1, warmup=1)
        tel.record(0, tiles=6, seconds=2.0)
        assert tel.speeds()[0] == pytest.approx(3.0)


# ------------------------------------------------- hardened ALB (satellite)

class TestALBTelemetryHardening:
    def test_sanitize_clamps_nan_zero_negative_to_median(self):
        out = alb.sanitize_speeds(np.array([np.nan, 0.0, -3.0, 2.0, 4.0]))
        assert (out > 0).all()
        med = np.median([2.0, 4.0])
        np.testing.assert_allclose(out[:3], med)
        np.testing.assert_allclose(out[3:], [2.0, 4.0])

    def test_sanitize_all_invalid_falls_back_uniform(self):
        out = alb.sanitize_speeds(np.array([np.nan, -1.0, 0.0]))
        np.testing.assert_allclose(out, 1.0)

    def test_budgets_reject_bad_speeds_without_sanitize(self):
        with pytest.raises(ValueError):
            alb.alb_budgets(np.array([1.0, np.nan]), 8, 0.5)

    def test_budgets_accept_bad_speeds_with_sanitize(self):
        b = alb.alb_budgets(np.array([1.0, np.nan]), 8, 0.5, sanitize=True)
        np.testing.assert_array_equal(b, [8, 8])  # NaN → median → uniform

    @pytest.mark.parametrize("rule", ["lower", "completion"])
    def test_pivot_node_budget_is_exactly_n_tiles(self, rule):
        rng = np.random.default_rng(3)
        for _ in range(20):
            speeds = rng.lognormal(0.0, 0.6, size=rng.integers(2, 12))
            n_tiles = int(rng.integers(2, 30))
            kappa = float(rng.uniform(0.3, 0.9))
            budgets = alb.alb_budgets(speeds, n_tiles, kappa,
                                      pivot_rule=rule)
            pivot = alb._pivot(np.asarray(speeds, np.float64), kappa, rule)
            m = int(np.argmin(np.abs(speeds - pivot)))
            assert budgets[m] == n_tiles

    @pytest.mark.parametrize("rule", ["lower", "completion"])
    def test_budgets_scale_invariant(self, rule):
        """Budgets depend only on speed RATIOS — rescaling the clock (the
        same cluster measured in tiles/ms vs tiles/s) changes nothing."""
        speeds = np.array([4.0, 1.0, 2.5, 1.0])
        a = alb.alb_budgets(speeds, 8, 0.5, pivot_rule=rule)
        b = alb.alb_budgets(speeds * 1000.0, 8, 0.5, pivot_rule=rule)
        np.testing.assert_array_equal(a, b)

    def test_completion_pivot_downbudgets_straggler_at_m2(self):
        """The telemetry-runtime case: M=2, κ=0.5, one 4× straggler.  The
        completion rule parks the slow node at ~n_tiles/4; the historical
        lower rule can only up-budget the fast node."""
        speeds = np.array([4.0, 1.0])
        comp = alb.alb_budgets(speeds, 8, 0.5, pivot_rule="completion")
        np.testing.assert_array_equal(comp, [8, 2])
        low = alb.alb_budgets(speeds, 8, 0.5, pivot_rule="lower")
        np.testing.assert_array_equal(low, [32, 8])


# ---------------------------------------------- 2-process end-to-end runs

def _run_single(tmp_path, design, steps=12):
    out = tmp_path / f"single_{design}.json"
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = str(SRC)
    env.pop("REPRO_DIST_PROCID", None)
    r = subprocess.run(
        [sys.executable, str(PROG), "--mode", "single", "--design", design,
         "--steps", str(steps), "--out", str(out)],
        capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, f"single ref failed:\n{r.stdout}\n{r.stderr}"
    return json.loads(out.read_text())


def _run_dist(tmp_path, mode, design, steps=12, ckpt_dir=""):
    out = tmp_path / f"{mode}_{design}.json"
    args = ["--mode", mode, "--design", design, "--steps", str(steps),
            "--out", str(out)]
    if ckpt_dir:
        args += ["--ckpt-dir", str(ckpt_dir)]
    res = launcher.run_local(2, PROG, args=args, timeout_s=600)
    assert res.ok, res.summary()
    return json.loads(out.read_text())


@pytest.mark.slow
@pytest.mark.parametrize("design", ["dense", "block"])
def test_two_process_beta_parity(tmp_path, design):
    """The same (1, 2) mesh fit run as 2 coordinated processes must match
    the single-process 2-device reference to ≤1e-5 — the distributed
    runtime changes WHERE shards live, never what is computed."""
    ref = _run_single(tmp_path, design)
    dist = _run_dist(tmp_path, "dist", design)
    assert dist["num_processes"] == 2
    ref_b = np.asarray(ref["beta_packed"])
    dist_b = np.asarray(dist["beta_packed"])
    assert np.max(np.abs(ref_b - dist_b)) <= 1e-5
    assert np.max(np.abs(np.asarray(ref["beta_user"])
                         - np.asarray(dist["beta_user"]))) <= 1e-5


@pytest.mark.slow
def test_two_process_checkpoint_restart(tmp_path):
    """Kill-and-restart: run A checkpoints at superstep 4 and exits; a
    FRESH pair of processes resumes from the directory and must land on
    the same iterate as the uninterrupted run."""
    ckpt = tmp_path / "ckpt"
    full = _run_dist(tmp_path, "dist", "dense", steps=12)
    _run_dist(tmp_path, "ckpt-a", "dense", steps=12, ckpt_dir=ckpt)
    assert any(ckpt.glob("ckpt_*")), "run A wrote no checkpoint"
    resumed = _run_dist(tmp_path, "ckpt-b", "dense", steps=12, ckpt_dir=ckpt)
    assert np.max(np.abs(np.asarray(full["beta_packed"])
                         - np.asarray(resumed["beta_packed"]))) <= 1e-5
    assert resumed["n_iter"] == full["n_iter"]


@pytest.mark.slow
def test_two_process_phase_telemetry_and_trace_merge(tmp_path):
    """Phase-attributed telemetry over the real 2-process KV exchange:
    both nodes fold identical state (incl. the SAME unknown-phase
    rejection count), the network-slow node keeps full compute speed in
    ``compute_speeds``/``effective_speeds``, and the per-process trace
    shards merge into one Perfetto-loadable file with two pid lanes."""
    from repro.obs import trace as obs_trace

    prog = pathlib.Path(__file__).parent / "progs" / "dist_phases.py"
    out = tmp_path / "phases"
    trace_dir = tmp_path / "trace"
    res = launcher.run_local(
        2, prog, args=["--out", str(out), "--trace-dir", str(trace_dir)],
        timeout_s=600)
    assert res.ok, res.summary()

    views = [json.loads((tmp_path / f"phases.p{p}.json").read_text())
             for p in range(2)]
    # every process folded the same exchanged samples -> identical state
    for key in ("speeds", "compute_speeds", "effective_speeds",
                "phase_breakdown", "rejected_phase_keys"):
        assert views[0][key] == views[1][key], key
    v = views[0]
    assert v["rejected_phase_keys"] == 1           # node 0's bogus key
    sp = np.asarray(v["speeds"])
    assert sp[0] / sp[1] == pytest.approx(4.0, rel=0.05)   # aggregate: 4x
    csp = np.asarray(v["compute_speeds"])
    assert csp[1] == pytest.approx(csp[0], rel=0.05)  # network != compute
    esp = np.asarray(v["effective_speeds"])
    assert esp[1] == pytest.approx(esp[0], rel=0.05)
    assert "network" in v["phase_breakdown"]
    assert "bogus_phase" not in v["phase_breakdown"]

    # two shards -> one merged Perfetto file with both pid lanes
    merged_path = obs_trace.merge_dir(trace_dir)
    merged = json.loads(merged_path.read_text())
    evs = merged["traceEvents"]
    pids = {e["pid"] for e in evs if e.get("ph") == "M"}
    assert pids == {0, 1}
    for pid in (0, 1):
        b = sum(1 for e in evs if e["pid"] == pid and e.get("ph") == "B"
                and e["name"] == "phases/superstep")
        e = sum(1 for e in evs if e["pid"] == pid and e.get("ph") == "E")
        assert b == 6 and e >= b
