"""Every Pallas kernel must pass the TPU compiler (Mosaic) at the widths
chip_smoke.py runs: compiled ahead of time for a described, not attached,
TPU v5e.  Interpret mode forgives block shapes, dynamic indexing and VMEM
sizes that the chip's compiler refuses; these compiles do not.

The topology is described inside a module-scoped fixture, never at import
time: only one process may load the TPU compiler library at once, and the
test workers all import this file.  The tests skip only where the TPU
compiler (``libtpu``) is not installed; any other failure to describe the
topology fails them.  The persistent compilation cache is off around the
compiles (an executable for a described device cannot be read back
without one)."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.alpha_search import alpha_search_pallas
from repro.kernels.cd_tile_solve import cd_tile_solve_pallas
from repro.kernels.glm_stats import glm_stats_pallas
from repro.kernels.predict_tile import predict_tile_pallas
from repro.kernels.superstep_tile import (margin_ls_pallas,
                                          stats_gram_solve_pallas)
from repro.kernels.tile_gram import tile_gram_pallas

F32, I32 = jnp.float32, jnp.int32
# chip_smoke.py widths: (a) sparse 32,768 rows, T = 512, row_block = 256;
# (b) dense 400,000 rows padded to 1024-row blocks, T = 256
SPARSE_R = 32768 // 128
DENSE_R = 3328                   # 400,000 rows in 256-row (R, 128) blocks
DENSE_NPAD = 400_384             # 400,000 rows in 1024-row blocks
N_CAND = 294                     # (1 + 13) · (1 + 20) line-search candidates
N_CAND_WIDE = 574                # (1 + 13) · (1 + 40): max_backtracks = 40


@pytest.fixture(scope="module")
def topo():
    pytest.importorskip("libtpu", reason="no TPU compiler installed")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("R", [SPARSE_R, DENSE_R])
def test_glm_stats(one_chip, R):
    fn = lambda *a: glm_stats_pallas(*a, family="logistic", block_rows=256,
                                     interpret=False)
    _compile(one_chip, fn, *[((R, 128), F32)] * 3)


@pytest.mark.parametrize("T", [256, 512])
def test_tile_gram(one_chip, T):
    K, rb, n_rb = 128, 256, 128
    fn = lambda *a: tile_gram_pallas(*a, interpret=False)
    _compile(one_chip, fn, ((K, rb, T), F32), ((K,), I32), ((), I32),
             ((n_rb, rb), F32), ((n_rb, rb), F32))


@pytest.mark.parametrize("T", [256, 512])
def test_cd_tile_solve(one_chip, T):
    fn = lambda *a: cd_tile_solve_pallas(*a, interpret=False)
    _compile(one_chip, fn, ((T, T), F32), *[((T,), F32)] * 4, ((4,), F32),
             ((T,), F32))


@pytest.mark.parametrize("R,K", [(SPARSE_R, 14), (DENSE_R, 20)])
def test_alpha_search(one_chip, R, K):
    fn = lambda *a: alpha_search_pallas(*a, family="logistic",
                                        block_rows=256, interpret=False)
    _compile(one_chip, fn, *[((R, 128), F32)] * 4, ((K,), F32))


@pytest.mark.parametrize("B,J,A1,table_rows",
                         [(64, 32, 2048, 2048), (16, 128, 16384, 8192)])
def test_predict_tile(one_chip, B, J, A1, table_rows):
    fn = lambda *a: predict_tile_pallas(*a, family="logistic",
                                        kind="response", block_b=8,
                                        table_rows=table_rows,
                                        interpret=False)
    _compile(one_chip, fn, ((B, J), I32), ((B, J), F32), ((A1, 128), F32),
             ((1, 128), F32))


def test_predict_tile_multi_output(one_chip, monkeypatch):
    """The table tiling ``ops.predict_tile`` chooses for a whole λ-path of
    300 outputs (384 lanes) over 5,461 active features: 2,728-row blocks."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    fn = lambda *a: ops.predict_tile(*a, "logistic", kind="response",
                                     backend="pallas")
    _compile(one_chip, fn, ((64, 32), I32), ((64, 32), F32),
             ((5462, 300), F32), ((300,), F32))


def _fused_shapes(T, n_pad, p):
    nt = p // T
    return nt, n_pad // 128


@pytest.mark.parametrize("T,n_pad,p,precision",
                         [(256, DENSE_NPAD, 2048, "fp32"),
                          (256, DENSE_NPAD, 2048, "bf16"),
                          (512, 32768, 65536, "fp32"),
                          # one 128-block a tile: the unblocked Gram
                          (128, DENSE_NPAD, 2048, "fp32")])
def test_stats_gram_solve(one_chip, T, n_pad, p, precision):
    nt, R = _fused_shapes(T, n_pad, p)
    fn = lambda *a: stats_gram_solve_pallas(
        *a, family="logistic", precision=precision, interpret=False)
    _compile(one_chip, fn, ((nt + 1,), I32), ((nt, n_pad, T), F32),
             *[((R, 128), F32)] * 3, ((nt, T), F32), ((nt, T), F32),
             ((4,), F32))


@pytest.mark.parametrize(
    "T,n_pad,p,precision,family,K",
    [pytest.param(256, DENSE_NPAD, 2048, "fp32", "logistic", N_CAND,
                  id="256-400384-2048-fp32"),
     pytest.param(256, DENSE_NPAD, 2048, "bf16", "logistic", N_CAND,
                  id="256-400384-2048-bf16"),
     pytest.param(512, 32768, 65536, "fp32", "logistic", N_CAND,
                  id="512-32768-65536-fp32"),
     # the heaviest loss body, and the accumulator's VMEM sized from a
     # larger candidate set at the wider tile
     pytest.param(256, DENSE_NPAD, 2048, "fp32", "probit", N_CAND,
                  id="256-400384-2048-fp32-probit"),
     pytest.param(512, DENSE_NPAD, 4096, "fp32", "logistic", N_CAND_WIDE,
                  id="512-400384-4096-fp32-K574")])
def test_margin_ls(one_chip, T, n_pad, p, precision, family, K):
    nt, R = _fused_shapes(T, n_pad, p)
    fn = lambda *a: margin_ls_pallas(*a, family=family,
                                     precision=precision, interpret=False)
    _compile(one_chip, fn, ((nt, n_pad, T), F32), ((nt, T), F32),
             *[((R, 128), F32)] * 3, ((K,), F32))
