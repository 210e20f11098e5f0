"""Checkpoint manager: atomic commit, keep-k GC, async save, crash-partial
write tolerance, exit-durability of the last async save."""
import gc
import json
import os
import pathlib
import shutil
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager


def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "params": {"w": rng.normal(size=(8, 4)).astype(np.float32),
                   "layers": [rng.normal(size=(3,)).astype(np.float32),
                              rng.normal(size=(5,)).astype(np.float32)]},
        "mu": np.float32(2.5),
        "step": np.int32(7),
    }


def test_roundtrip(tmp_path):
    mgr = CheckpointManager(tmp_path)
    t = _tree()
    mgr.save(10, t, metadata={"note": "hi"})
    like = jax.tree.map(jnp.asarray, t)
    restored, md = mgr.restore(like)
    assert md == {"note": "hi"}
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_keep_last_gc(tmp_path):
    mgr = CheckpointManager(tmp_path, keep_last=2)
    for s in (1, 2, 3, 4, 5):
        mgr.save(s, _tree(s))
    assert mgr.all_steps() == [4, 5]


def test_latest_and_explicit_step(tmp_path):
    mgr = CheckpointManager(tmp_path, keep_last=10)
    mgr.save(3, _tree(3))
    mgr.save(9, _tree(9))
    like = jax.tree.map(jnp.asarray, _tree())
    r9, _ = mgr.restore(like)
    r3, _ = mgr.restore(like, step=3)
    assert not np.allclose(np.asarray(r9["params"]["w"]),
                           np.asarray(r3["params"]["w"]))
    assert mgr.latest_step() == 9


def test_partial_write_is_ignored(tmp_path):
    """A crash mid-write leaves a .tmp dir or a dir without manifest —
    restore must fall back to the last complete checkpoint."""
    mgr = CheckpointManager(tmp_path)
    mgr.save(5, _tree(5))
    # simulate torn writes
    (tmp_path / "ckpt_6.tmp").mkdir()
    broken = tmp_path / "ckpt_7"
    broken.mkdir()
    (broken / "shard_0.npz").write_bytes(b"garbage")
    assert mgr.latest_step() == 5
    like = jax.tree.map(jnp.asarray, _tree())
    _, _ = mgr.restore(like)


def test_async_save(tmp_path):
    mgr = CheckpointManager(tmp_path, async_save=True)
    mgr.save(1, _tree(1))
    mgr.wait()
    assert mgr.all_steps() == [1]


def test_async_last_save_survives_interpreter_exit(tmp_path):
    """The module docstring promises the last checkpoint is durable at
    process exit.  Writer threads are daemonic, so WITHOUT the atexit join
    an exit right after save() kills the writer mid-write — this subprocess
    slows the serializer down to force exactly that race and exits without
    calling wait()."""
    script = textwrap.dedent("""
        import sys, time
        import numpy as np
        import repro.checkpoint.manager as M

        _orig = M.np.savez
        def slow_savez(*a, **kw):
            time.sleep(1.0)          # exit reaches atexit before the write
            _orig(*a, **kw)
        M.np.savez = slow_savez

        mgr = M.CheckpointManager(sys.argv[1], async_save=True)
        mgr.save(7, {"w": np.arange(5.0, dtype=np.float32)})
        # no wait(), no explicit join — straight to interpreter exit
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = str(pathlib.Path(__file__).resolve().parents[1] /
                            "src")
    out = subprocess.run([sys.executable, "-c", script, str(tmp_path)],
                         capture_output=True, text=True, timeout=120,
                         env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    mgr = CheckpointManager(tmp_path)
    assert mgr.latest_step() == 7
    restored, _ = mgr.restore({"w": np.zeros(5, np.float32)})
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.arange(5.0, dtype=np.float32))


def test_del_joins_inflight_writer(tmp_path, monkeypatch):
    """Dropping the manager (its __del__) also commits an in-flight save."""
    import repro.checkpoint.manager as M
    orig = np.savez

    def slow_savez(*a, **kw):
        time.sleep(0.3)
        orig(*a, **kw)

    monkeypatch.setattr(M.np, "savez", slow_savez)
    mgr = CheckpointManager(tmp_path, async_save=True)
    mgr.save(3, {"w": np.arange(4.0, dtype=np.float32)})
    del mgr
    gc.collect()
    assert CheckpointManager(tmp_path).latest_step() == 3


def test_tree_mismatch_raises(tmp_path):
    mgr = CheckpointManager(tmp_path)
    mgr.save(1, _tree())
    bad = {"params": {"w": jnp.zeros((8, 4))}, "mu": jnp.float32(0)}
    with pytest.raises(ValueError):
        mgr.restore(bad)
