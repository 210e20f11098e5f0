"""Distributed GLM launcher: ``python -m repro.launch.dist_run [...]``.

Two modes sharing one entry point (DESIGN.md §9):

  * **parent** (no ``REPRO_DIST_PROCID`` in the environment): spawn
    ``--nprocs`` coordinated local worker processes through
    ``repro.dist.launcher`` — the one-machine stand-in for a cluster
    scheduler — and relay their output;
  * **worker** (env set, or ``--nprocs 1``): ``bootstrap.initialize()``,
    build the process-spanning mesh, and run the ``--demo`` lasso fit on a
    synthetic design, optionally under an injected fault plan
    (``--faults "1:4.0"``) with telemetry-driven ALB (``--telemetry``).

``--data FILE`` switches the worker to MULTI-PROCESS OUT-OF-CORE
training (DESIGN.md §10): every process opens the same on-disk libsvm /
Parquet source through ``repro.io``, claims its contiguous chunk range
(``StreamingDesign.process_slice``), and drives the streaming superstep
with its local chunks only — per-superstep (Gram, gradient, loss)
partials are all-reduced across the process-spanning mesh, so no process
ever materializes more than ``chunk_rows`` rows while the fit is exactly
the single-host fit (``--nprocs 1`` on the same file is the parity
baseline; ``benchmarks/ingest_bench.py`` asserts it).

On a real cluster each node runs the worker directly with
``REPRO_DIST_COORD/NPROCS/PROCID`` set by the scheduler; the parent mode
exists so the same command line works on a laptop.
"""
import argparse
import json
import os
import sys
import time


def _allreduce_sum(mesh, axis: str, flat_local):
    """Sum one host (m,) float32 partial across every process of the
    job, returning the replicated host result on each.

    ``bootstrap.put_global`` cannot carry process-LOCAL values (its model
    is every process presenting the same full array), so this builds the
    global array the other way around — ``make_array_from_single_device_
    arrays`` with each process contributing its own shard of a stacked
    (nprocs, m) axis — and reduces it with a jitted sum whose output
    sharding is fully replicated (the same collective pattern as
    ``bootstrap.gather_to_host``).  Deterministic: XLA's all-reduce gives
    every process bit-identical sums, which the SPMD driver relies on.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    nshard = mesh.shape[axis]
    if nshard == 1:
        return np.asarray(flat_local, np.float32)
    flat_local = np.asarray(flat_local, np.float32)
    m = flat_local.shape[0]
    sharding = NamedSharding(mesh, P(axis))
    # per-device puts of process-local values feed
    # make_array_from_single_device_arrays; put_global would gather instead
    # lint: allow DIST001 — targets are this process's own devices
    locals_ = [jax.device_put(flat_local, d)
               for d in sharding.addressable_devices]
    garr = jax.make_array_from_single_device_arrays(
        (nshard * m,), sharding, locals_)
    summed = jax.jit(
        lambda a: jnp.sum(a.reshape(nshard, m), axis=0),
        out_shardings=NamedSharding(mesh, P()))(garr)
    return np.asarray(summed.addressable_data(0))


def _worker_stream(args) -> int:
    """Out-of-core multi-process worker: local chunk range + allreduce."""
    import numpy as np
    import jax.numpy as jnp
    from repro.core.dglmnet import (DGLMNETConfig, FitState,
                                    make_streaming_superstep)
    from repro.dist import bootstrap, faults
    from repro import io as io_lib

    ctx = bootstrap.initialize()
    mesh = bootstrap.make_dist_mesh()
    axis = "model"

    reader = io_lib.open_reader(args.data, chunk_rows=args.chunk_rows)
    hasher = None
    if args.hash_dim:
        hasher = io_lib.FeatureHasher(args.hash_dim, tile_size=args.tile)
    design, labels, reader = io_lib.open_design(
        reader, tile_size=args.tile, hasher=hasher,
        prefetch_chunks=2 if args.prefetch else 0)
    local, rows = design.process_slice(ctx.process_id, ctx.num_processes)
    n_loc = rows.stop - rows.start
    n_pad = local.n_chunks * local.chunk_rows
    y = np.pad(np.asarray(labels[rows], np.float32),
               (0, n_pad - n_loc), constant_values=1.0)
    w = np.pad(np.ones((n_loc,), np.float32), (0, n_pad - n_loc))
    o = np.zeros((n_pad,), np.float32)

    p_pad = local.shape[1]
    cfg = DGLMNETConfig(tile_size=args.tile, max_outer=args.steps)
    fns = make_streaming_superstep(cfg)
    lams = jnp.asarray([args.lam1, args.lam2], jnp.float32)
    active = jnp.ones((p_pad,), jnp.float32)
    penf = jnp.ones((p_pad,), jnp.float32)
    budget = jnp.full((1,), p_pad // args.tile, jnp.int32)
    state = FitState(beta=jnp.zeros((p_pad,), jnp.float32),
                     xb=jnp.zeros((0,), jnp.float32),
                     mu=jnp.float32(cfg.mu_init),
                     cursor=jnp.zeros((1,), jnp.int32),
                     step=jnp.int32(0))

    def row_slices(i):
        sl = slice(i * local.chunk_rows, (i + 1) * local.chunk_rows)
        return jnp.asarray(y[sl]), jnp.asarray(w[sl]), jnp.asarray(o[sl])

    t0 = time.perf_counter()
    f_prev, n_iter = None, 0
    for it in range(args.steps):
        acc = (jnp.zeros((p_pad, p_pad), jnp.float32),
               jnp.zeros((p_pad,), jnp.float32), jnp.float32(0.0))
        for i, Xc in local.iter_chunks():
            yc, wc, oc = row_slices(i)
            acc = fns.stats_chunk(Xc, yc, wc, oc, state.beta, acc)
        # per-process partials -> global (Gram, gradient, loss): ONE
        # flattened allreduce per superstep phase
        flat = np.concatenate([np.asarray(acc[0]).ravel(),
                               np.asarray(acc[1]),
                               np.float32(acc[2]).reshape(1)])
        red = _allreduce_sum(mesh, axis, flat)
        acc = (jnp.asarray(red[:p_pad * p_pad].reshape(p_pad, p_pad)),
               jnp.asarray(red[p_pad * p_pad:-1]),
               jnp.float32(red[-1]))
        prep = fns.prepare(acc, state.beta, state.mu, lams, active, penf,
                           state.cursor, budget)
        losses = jnp.zeros((fns.n_candidates,), jnp.float32)
        for i, Xc in local.iter_chunks():
            yc, wc, oc = row_slices(i)
            losses = fns.ls_chunk(Xc, yc, wc, oc, state.beta,
                                  prep["dbeta"], prep["cand"], losses)
        losses = jnp.asarray(_allreduce_sum(mesh, axis,
                                            np.asarray(losses)))
        state, metrics = fns.finish(losses, prep, state, lams, penf)
        n_iter = it + 1
        # the KV-based host allreduce already forces host round-trips each
        # superstep; these readbacks ride syncs the protocol requires anyway
        # lint: allow SYNC001 — host-mediated allreduce is the design here
        f = float(metrics["f"])
        if f_prev is not None and abs(f_prev - f) <= args.tol * max(
                abs(f_prev), 1.0):
            break
        f_prev = f
    wall = time.perf_counter() - t0

    beta = np.asarray(state.beta)
    if ctx.is_coordinator:
        row = {
            "mode": "stream", "data": str(args.data),
            "num_processes": ctx.num_processes,
            "rows": reader.n_rows, "features": reader.n_features,
            "design_cols": p_pad, "chunks_local": local.n_chunks,
            "chunk_rows": args.chunk_rows,
            "hash_dim": args.hash_dim or None,
            "prefetch": bool(args.prefetch),
            "supersteps": n_iter, "f_final": f,
            "nnz": int((np.abs(beta) > 1e-8).sum()),
            "wall_s": round(wall, 3),
            "rows_per_s": round(reader.n_rows * n_iter * 2 / max(
                wall, 1e-9), 1),
            "beta_head": [float(v) for v in beta[:8]],
        }
        blob = json.dumps(row)
        print(blob)
        if args.out:
            with open(args.out, "w") as fh:
                fh.write(blob)
    faults.guarded_barrier("dist-run-stream-exit")
    return 0


def _worker(args) -> int:
    from repro.core.solver import GLMSolver
    from repro.core.dglmnet import DGLMNETConfig
    from repro.dist import bootstrap, faults, telemetry

    ctx = bootstrap.initialize()
    mesh = bootstrap.make_dist_mesh()
    import numpy as np
    rng = np.random.default_rng(0)
    n, p = args.rows, args.cols
    X = rng.normal(size=(n, p)).astype(np.float32)
    beta_true = np.zeros((p,), np.float32)
    beta_true[: p // 8] = rng.normal(size=p // 8)
    y = (X @ beta_true + 0.1 * rng.normal(size=n)).astype(np.float32)

    plan = None
    if args.faults:
        plan = faults.FaultPlan.parse(args.faults, ctx.num_processes,
                                      tile_cost_s=args.tile_cost_s)
    tel = telemetry.SuperstepTelemetry(phase_aware=args.phase_aware) \
        if args.telemetry else None

    solver = GLMSolver(
        X, y, config=DGLMNETConfig(tile_size=args.tile, max_outer=args.steps),
        mesh=mesh, telemetry=tel, fault_plan=plan)
    res = solver.fit(lam1=args.lam1, lam2=1e-4)
    nnz = int((np.abs(res.beta) > 1e-8).sum())
    if ctx.is_coordinator:
        print(json.dumps({
            "process_id": ctx.process_id,
            "num_processes": ctx.num_processes,
            "mesh": [int(s) for s in mesh.devices.shape],
            "f": res.history["f"][-1], "nnz": nnz,
            "n_iter": res.n_iter, "converged": bool(res.converged),
            "budgets": None if solver._budgets_host is None
            else solver._budgets_host.tolist(),
        }))
    faults.guarded_barrier("dist-run-exit")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--nprocs", type=int, default=2,
                    help="local processes to spawn (parent mode)")
    ap.add_argument("--demo", action="store_true",
                    help="run the synthetic lasso demo fit (worker mode "
                    "runs it always; parent mode spawns workers that do)")
    ap.add_argument("--rows", type=int, default=512)
    ap.add_argument("--cols", type=int, default=256)
    ap.add_argument("--tile", type=int, default=64)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--lam1", type=float, default=0.05)
    ap.add_argument("--faults", default="",
                    help='fault spec, e.g. "1:4.0" or "0:2.0,1:4.0@10-20"')
    ap.add_argument("--tile-cost-s", type=float, default=0.0, dest="tile_cost_s",
                    help="simulated seconds of local work per tile (>0 "
                    "activates fault injection sleeps)")
    ap.add_argument("--telemetry", action="store_true",
                    help="drive ALB budgets from measured node speeds")
    ap.add_argument("--phase-aware", action="store_true", dest="phase_aware",
                    help="budgets react to COMPUTE-phase speed only (a "
                    "network-slow node keeps its tile budget)")
    ap.add_argument("--trace", default="",
                    help="directory for repro.obs traces: every process "
                    "writes a trace_<pid>.json shard (+ metrics/"
                    "convergence streams); the parent merges the shards "
                    "into one Perfetto-loadable trace_merged.json")
    ap.add_argument("--timeout", type=float, default=900.0)
    ap.add_argument("--data", default="",
                    help="libsvm(.gz)/Parquet file: multi-process "
                    "out-of-core training over per-process chunk ranges")
    ap.add_argument("--chunk-rows", type=int, default=4096,
                    dest="chunk_rows")
    ap.add_argument("--hash-dim", type=int, default=0, dest="hash_dim")
    ap.add_argument("--lam2", type=float, default=0.0)
    ap.add_argument("--tol", type=float, default=1e-8)
    ap.add_argument("--prefetch", action=argparse.BooleanOptionalAction,
                    default=True)
    ap.add_argument("--out", default="",
                    help="coordinator writes the result row here (JSON)")
    args = ap.parse_args()

    if os.environ.get("REPRO_DIST_PROCID") is not None or args.nprocs <= 1:
        if args.trace:
            # enable before any solver work; the atexit hook saves this
            # process's shard (workers spawned by the parent inherit
            # REPRO_TRACE instead and are already enabled at import)
            from repro.obs import trace as obs_trace
            if not obs_trace.get_tracer().enabled:
                obs_trace.enable(args.trace)
        return _worker_stream(args) if args.data else _worker(args)

    from repro.dist import launcher
    if args.trace:
        # workers inherit the env → every process traces into the same
        # directory with zero per-call wiring (repro.obs.trace)
        had_trace_env = "REPRO_TRACE" in os.environ
        os.environ["REPRO_TRACE"] = args.trace
    forwarded, skip = [], False
    for a in sys.argv[1:]:
        if skip:
            skip = False
            continue
        if a == "--nprocs":
            skip = True
        elif not a.startswith("--nprocs="):
            forwarded.append(a)
    result = launcher.run_local(args.nprocs, os.path.abspath(__file__),
                                args=forwarded, timeout_s=args.timeout)
    print(f"[dist_run] {args.nprocs} local workers ran on JAX_PLATFORMS=cpu")
    print(result.summary())
    if args.trace:
        from repro.obs import trace as obs_trace
        if not had_trace_env:
            # the env var was for the WORKERS: if importing repro.obs
            # under it enabled tracing in this launcher process too, drop
            # that — a near-empty parent shard would add a junk lane to
            # the merge (and to every later re-merge of the directory)
            os.environ.pop("REPRO_TRACE", None)
            obs_trace.disable()
        merged = obs_trace.merge_dir(args.trace)
        if merged is not None:
            print(f"[dist_run] merged trace: {merged} "
                  "(load at https://ui.perfetto.dev)")
    return 0 if result.ok else 1


if __name__ == "__main__":
    sys.exit(main())
