"""End-to-end behaviour test for the whole system: the paper's algorithm
fitting sparse (synthetic) data and generalizing to held-out rows."""
from repro.core import dglmnet
from repro.core.dglmnet import DGLMNETConfig
from repro.data import synthetic
from repro.data.sparse import to_dense_blocks


def test_glm_end_to_end_sparse():
    """The paper's workload end to end: sparse data → densified bricks →
    d-GLMNET → generalization (auPRC) beats chance by a wide margin."""
    ds = synthetic.make_sparse(n=2000, p=4000, avg_nnz=60, seed=42)
    X, perm, occ = to_dense_blocks(ds.train.X, 128)
    cfg = DGLMNETConfig(lam1=0.3, lam2=0.1, tile_size=128,
                        coupling="jacobi", max_outer=50)
    res = dglmnet.fit(X, ds.train.y, cfg)
    Xte = ds.test.X.to_dense()[:, perm]
    scores = Xte @ res.beta[:Xte.shape[1]]
    au = synthetic.au_prc(ds.test.y, scores)
    pos_rate = (ds.test.y > 0).mean()
    assert au > pos_rate + 0.15, (au, pos_rate)
