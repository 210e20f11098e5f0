"""Multi-device behaviour, run in subprocesses with fake CPU devices so the
main pytest process keeps seeing exactly 1 device (see conftest)."""
import pytest

from conftest import run_prog


@pytest.mark.slow
def test_distributed_glm_equivalence():
    out = run_prog("dist_glm", devices=8)
    assert "DIST_GLM_OK" in out


def test_vocab_parallel_ce():
    out = run_prog("dist_ce", devices=8)
    assert "DIST_CE_OK" in out


@pytest.mark.slow
def test_elastic_checkpoint_resume():
    out = run_prog("dist_ckpt", devices=8)
    assert "DIST_CKPT_OK" in out


@pytest.mark.slow
def test_warm_started_path_sharded():
    """GLMSolver.fit_path on a 2-D mesh (dense + blocked-sparse designs)
    matches cold per-λ fits and compiles the superstep once per session."""
    out = run_prog("dist_path", devices=8)
    assert "DIST_PATH_OK" in out


def test_blocked_sparse_sharded_matches_dense():
    """Acceptance: fit_sharded trains from a SparseCOO on 1×2 / 2×2 meshes
    without materializing the dense matrix on host, matching the dense-path
    objective within 1e-5."""
    out = run_prog("dist_design", devices=4)
    assert "DIST_DESIGN_OK" in out
