import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture × input shape) on
the production meshes, and extract the roofline terms.

MUST be run as its own process (`python -m repro.launch.dryrun`): the
XLA_FLAGS line above executes before any jax import so the CPU platform
exposes 512 placeholder devices.  Smoke tests / benchmarks never import this
module and keep seeing 1 device.

Per cell this writes results/dryrun/<mesh>/<arch>__<shape>.json with:
  memory_analysis (per-chip bytes), cost_analysis flops (XLA's, loop-naive),
  the trip-count-aware static profile (flops / bytes / collective bytes),
  the three roofline terms, MODEL_FLOPS and the useful-compute ratio.
"""
import argparse      # noqa: E402
import json          # noqa: E402
import pathlib       # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np   # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import SHAPES  # noqa: E402
from repro.configs.base import cell_is_runnable, tp_pad_config  # noqa: E402
from repro.configs.glm_webscale import GLM_SHAPES  # noqa: E402
from repro.configs.registry import ARCHS, get_arch  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.sharding import compat  # noqa: E402
from repro.models import lm  # noqa: E402
from repro.optim import adamw  # noqa: E402
from repro.roofline.hlo import analyze_hlo  # noqa: E402
from repro.roofline.model import model_flops, roofline_terms  # noqa: E402

RESULTS = pathlib.Path(__file__).resolve().parents[3] / "results" / "dryrun"


def _mem_dict(compiled):
    try:
        m = compiled.memory_analysis()
        return {
            "argument_bytes": int(m.argument_size_in_bytes),
            "output_bytes": int(m.output_size_in_bytes),
            "temp_bytes": int(m.temp_size_in_bytes),
            "alias_bytes": int(m.alias_size_in_bytes),
            "peak_bytes_est": int(m.argument_size_in_bytes
                                  + m.temp_size_in_bytes
                                  + m.output_size_in_bytes
                                  - m.alias_size_in_bytes),
        }
    except Exception as e:  # pragma: no cover
        return {"error": repr(e)}


def lower_cell(arch_name: str, shape_name: str, mesh, *, do_compile=True,
               overrides: dict | None = None):
    """Lower (and compile) one cell; returns the result record.
    ``overrides``: ArchConfig.replace kwargs (perf-iteration knobs:
    parallelism/seq_shard/remat/attn_chunk/...)."""
    cfg = get_arch(arch_name)
    shape = SHAPES[shape_name]
    runnable, why = cell_is_runnable(cfg, shape)
    rec = {"arch": arch_name, "shape": shape_name,
           "mesh": "x".join(map(str, mesh.devices.shape)),
           "kind": shape.kind}
    if not runnable:
        rec.update(status="skipped", reason=why)
        return rec
    if overrides:
        cfg = cfg.replace(**overrides)
        rec["overrides"] = dict(overrides)
    if getattr(cfg, "parallelism", "tp") == "tp":
        cfg, pads = tp_pad_config(cfg, mesh.shape["model"])
        if pads:
            rec["tp_padding"] = {k: list(v) for k, v in pads.items()}

    t0 = time.perf_counter()
    with mesh:
        batch, caches, cache_len, token = lm.input_specs(cfg, shape, mesh)
        if shape.kind == "train":
            params, opt_state = lm.abstract_state(cfg, mesh)
            opt_cfg = adamw.AdamWConfig()
            step, _ = lm.make_train_step(cfg, opt_cfg)
            lowered = jax.jit(step, donate_argnums=(0, 1)).lower(
                params, opt_state, batch)
        elif shape.kind == "prefill":
            params, _ = lm.abstract_state(cfg, mesh, with_opt=False)
            step, _ = lm.make_prefill_step(cfg)
            lowered = jax.jit(step, donate_argnums=(1,)).lower(
                params, caches, batch)
        else:  # decode
            params, _ = lm.abstract_state(cfg, mesh, with_opt=False)
            step, _ = lm.make_decode_step(cfg)
            lowered = jax.jit(step, donate_argnums=(1,)).lower(
                params, caches, token, cache_len, batch)
    rec["lower_s"] = round(time.perf_counter() - t0, 2)

    if not do_compile:
        rec["status"] = "lowered"
        return rec

    t0 = time.perf_counter()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.perf_counter() - t0, 2)
    rec["status"] = "ok"
    rec["memory"] = _mem_dict(compiled)
    try:
        ca = compiled.cost_analysis()
        rec["xla_cost_flops"] = float(ca.get("flops", -1.0))
    except Exception:
        rec["xla_cost_flops"] = None

    n_chips = int(np.prod(mesh.devices.shape))
    stats = analyze_hlo(compiled.as_text())
    rec["profile"] = stats.as_dict()
    rec["roofline"] = roofline_terms(stats, n_chips)
    mf = model_flops(cfg, shape)
    rec["model_flops"] = mf
    hlo_total = stats.flops * n_chips
    rec["hlo_flops_total"] = hlo_total
    rec["useful_compute_ratio"] = (mf / hlo_total) if hlo_total else None
    return rec


def lower_glm_cell(shape_name: str, mesh, *, do_compile=True,
                   coupling="jacobi", compress=None):
    """The paper's own workload on the production mesh.

    Shapes with ``occupancy < 1`` lower the blocked-sparse path: the design
    is an abstract ``BlockSparseDesign`` pytree whose brick leaves are sized
    for the shape's brick occupancy, so the per-chip memory/roofline numbers
    reflect the CSR-of-bricks layout instead of a dense (n, p) block.
    """
    from repro.core.dglmnet import DGLMNETConfig, FitState, make_superstep
    from repro.data.design import BlockSparseDesign

    gs = GLM_SHAPES[shape_name]
    D = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
    M = mesh.shape["model"]
    occ = getattr(gs, "occupancy", 1.0)
    rec = {"arch": "dglmnet", "shape": shape_name,
           "mesh": "x".join(map(str, mesh.devices.shape)), "kind": "glm",
           "coupling": coupling,
           "design": "bricks" if occ < 1.0 else "dense",
           "occupancy": occ}

    n, p, T = gs.n_examples, gs.n_features, gs.tile_size
    p_loc = p // M
    n_tiles = p_loc // T
    cfg = DGLMNETConfig(family="logistic", lam1=1.0, lam2=1.0, tile_size=T,
                        coupling=coupling, kernel_backend="ref",
                        compress_margin=compress)
    axis_data = "data"
    superstep = make_superstep(cfg, axis_data=axis_data, axis_model="model",
                               n_tiles_local=n_tiles)

    row_axes = ("pod", "data") if "pod" in mesh.shape else "data"
    row_spec = P(row_axes)
    feat_spec = P("model")

    def sds(shape_, dtype, spec):
        return jax.ShapeDtypeStruct(shape_, dtype,
                                    sharding=NamedSharding(mesh, spec))

    if occ < 1.0:
        rb = 256
        n_loc = -(-n // (D * rb)) * rb
        n = D * n_loc                       # row-padded total
        n_rb = n_loc // rb
        B = max(1, int(round(occ * n_rb * n_tiles)))
        K = max(1, int(round(occ * n_rb)))
        proto = BlockSparseDesign(None, None, None, None, T, rb, n_loc,
                                  n_tiles, K, leading=2)
        x_specs = proto.partition_specs(row_axes, "model")
        X = BlockSparseDesign(
            sds((D, M, B, rb, T), jnp.float32, x_specs.bricks),
            sds((D, M, B), jnp.int32, x_specs.brick_row),
            sds((D, M, B), jnp.int32, x_specs.brick_tile),
            sds((D, M, n_tiles + 1), jnp.int32, x_specs.tile_ptr),
            T, rb, n_loc, n_tiles, K, leading=2)
        rec["brick_bytes_per_chip"] = B * rb * T * 4
    else:
        x_specs = P(row_axes, "model")
        X = sds((n, p), jnp.float32, x_specs)
    y = sds((n,), jnp.float32, row_spec)
    weights = sds((n,), jnp.float32, row_spec)   # obs weights × fold × pad
    offset = sds((n,), jnp.float32, row_spec)    # margin offsets
    budget = sds((M,), jnp.int32, feat_spec)
    lams = sds((2,), jnp.float32, P())        # runtime [λ1, λ2] (replicated)
    active = sds((p,), jnp.float32, feat_spec)  # screening mask
    penf = sds((p,), jnp.float32, feat_spec)    # per-feature penalty factors
    state = FitState(
        beta=sds((p,), jnp.float32, feat_spec),
        xb=sds((n,), jnp.float32, row_spec),
        mu=jax.ShapeDtypeStruct((), jnp.float32),
        cursor=sds((M,), jnp.int32, feat_spec),
        step=jax.ShapeDtypeStruct((), jnp.int32),
    )
    state_specs = FitState(beta=feat_spec, xb=row_spec, mu=P(),
                           cursor=feat_spec, step=P())
    metric_spec = {k: P() for k in ("f", "f_before", "loss", "alpha", "mu",
                                    "nnz", "accepted_unit", "D")}
    # NOTE: inside shard_map the "pod"+"data" axes act jointly as the row
    # axis; we pass axis_data="data" for single-pod and handle multi-pod by
    # treating ("pod","data") as one flattened axis via shard_map axes.
    if "pod" in mesh.shape:
        axis_data_names = ("pod", "data")

        def superstep_mp(X, y, weights, offset, budget, lams, active, penf,
                         state):
            return make_superstep(cfg, axis_data=axis_data_names,
                                  axis_model="model",
                                  n_tiles_local=n_tiles)(
                X, y, weights, offset, budget, lams, active, penf, state)
        fn = superstep_mp
    else:
        fn = superstep

    t0 = time.perf_counter()
    with mesh:
        mapped = jax.jit(compat.shard_map(
            fn, mesh=mesh,
            in_specs=(x_specs, row_spec, row_spec, row_spec, feat_spec, P(),
                      feat_spec, feat_spec, state_specs),
            out_specs=(state_specs, metric_spec), check_vma=False))
        lowered = mapped.lower(X, y, weights, offset, budget, lams, active,
                               penf, state)
    rec["lower_s"] = round(time.perf_counter() - t0, 2)
    if not do_compile:
        rec["status"] = "lowered"
        return rec
    t0 = time.perf_counter()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.perf_counter() - t0, 2)
    rec["status"] = "ok"
    rec["memory"] = _mem_dict(compiled)
    n_chips = int(np.prod(mesh.devices.shape))
    stats = analyze_hlo(compiled.as_text())
    rec["profile"] = stats.as_dict()
    rec["roofline"] = roofline_terms(stats, n_chips)
    # useful FLOPs per outer iteration: tile Gram blocks (2·n·p·T — the
    # dominant term; exact per-tile Newton needs X_tᵀWX_t) + gradient and
    # margin matvecs (≈ 4·n·p); for bricks both scale with occupancy
    rec["model_flops"] = occ * (2.0 * n * p * T + 4.0 * n * p)
    rec["hlo_flops_total"] = stats.flops * n_chips
    rec["useful_compute_ratio"] = (rec["model_flops"]
                                   / rec["hlo_flops_total"]
                                   if stats.flops else None)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all",
                    help="arch id, 'all', or 'dglmnet'")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi",
                                                       "both"])
    ap.add_argument("--no-compile", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--override", default="",
                    help="comma-separated ArchConfig overrides, e.g. "
                         "'parallelism=fsdp,seq_shard=False'")
    args = ap.parse_args()

    overrides = {}
    for kv in filter(None, args.override.split(",")):
        k, v = kv.split("=")
        overrides[k] = {"True": True, "False": False}.get(
            v, int(v) if v.isdigit() else v)

    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("1x16x16", make_production_mesh(multi_pod=False)))
    if args.mesh in ("multi", "both"):
        meshes.append(("2x16x16", make_production_mesh(multi_pod=True)))

    archs = list(ARCHS) if args.arch == "all" else [args.arch]
    n_ok = n_skip = n_fail = 0
    for mesh_tag, mesh in meshes:
        outdir = RESULTS / (mesh_tag + args.tag)
        outdir.mkdir(parents=True, exist_ok=True)
        for arch in archs:
            if arch == "dglmnet":
                shapes = (list(GLM_SHAPES) if args.shape == "all"
                          else [args.shape])
            else:
                shapes = list(SHAPES) if args.shape == "all" else [args.shape]
            for shape in shapes:
                out = outdir / f"{arch}__{shape}.json"
                try:
                    if arch == "dglmnet":
                        rec = lower_glm_cell(
                            shape, mesh, do_compile=not args.no_compile,
                            coupling=overrides.get("coupling", "jacobi"),
                            compress=overrides.get("compress"))
                    else:
                        rec = lower_cell(arch, shape, mesh,
                                         do_compile=not args.no_compile,
                                         overrides=overrides or None)
                except Exception:
                    rec = {"arch": arch, "shape": shape, "mesh": mesh_tag,
                           "status": "failed",
                           "error": traceback.format_exc(limit=20)}
                out.write_text(json.dumps(rec, indent=2))
                st = rec["status"]
                n_ok += st in ("ok", "lowered")
                n_skip += st == "skipped"
                n_fail += st == "failed"
                extra = ""
                if st == "ok":
                    r = rec["roofline"]
                    extra = (f" dominant={r['dominant']}"
                             f" bound={r['bound_s']:.4f}s"
                             f" compile={rec['compile_s']}s")
                print(f"[{mesh_tag}] {arch} × {shape}: {st}{extra}",
                      flush=True)
    print(f"dry-run summary: ok={n_ok} skipped={n_skip} failed={n_fail}",
          flush=True)
    return 0 if n_fail == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
