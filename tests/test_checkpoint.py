"""Checkpointing: roundtrip, atomic commit, GC, resume, async."""
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import TrainConfig, get_config
from repro.train import checkpoint, trainer


@pytest.fixture
def state():
    cfg = get_config("tiny")
    tc = TrainConfig(steps=5)
    return trainer.init_train_state(jax.random.PRNGKey(0), cfg, tc)


def test_roundtrip(state, tmp_path):
    checkpoint.save(state, str(tmp_path), 3)
    restored = checkpoint.restore(state, str(tmp_path))
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_latest_and_gc(state, tmp_path):
    for s in (1, 2, 3, 4, 5):
        checkpoint.save(state, str(tmp_path), s, keep=2)
    assert checkpoint.latest_step(str(tmp_path)) == 5
    assert checkpoint.all_steps(str(tmp_path)) == [4, 5]


def test_atomic_no_tmp_left(state, tmp_path):
    checkpoint.save(state, str(tmp_path), 7)
    names = os.listdir(tmp_path)
    assert not any(n.startswith("tmp.") for n in names)
    assert "step_00000007" in names


def test_async_save(state, tmp_path):
    t = checkpoint.save(state, str(tmp_path), 9, async_save=True)
    t.join(timeout=30)
    assert checkpoint.latest_step(str(tmp_path)) == 9
    restored = checkpoint.restore(state, str(tmp_path), 9)
    np.testing.assert_array_equal(np.asarray(restored.step),
                                  np.asarray(state.step))


def test_restore_specific_step_and_meta(state, tmp_path):
    checkpoint.save(state, str(tmp_path), 1, extra_meta={"arch": "tiny"})
    checkpoint.save(state, str(tmp_path), 2)
    r1 = checkpoint.restore(state, str(tmp_path), step=1)
    assert checkpoint.load_meta(str(tmp_path), 1)["arch"] == "tiny"


def test_shape_mismatch_rejected(state, tmp_path):
    checkpoint.save(state, str(tmp_path), 1)
    bad = jax.tree.map(lambda x: jnp.zeros(x.shape + (1,), x.dtype)
                       if x.ndim > 0 else x, state)
    with pytest.raises(ValueError):
        checkpoint.restore(bad, str(tmp_path), 1)


def test_missing_dir_raises(state, tmp_path):
    with pytest.raises(FileNotFoundError):
        checkpoint.restore(state, str(tmp_path / "nope"))


def test_bfloat16_roundtrip(tmp_path):
    """npz keeps bfloat16 (the frozen base's dtype) as raw bytes; restore
    views them as bfloat16 again instead of failing to cast."""
    tree = {"w": (jnp.arange(6, dtype=jnp.float32).reshape(2, 3) / 3
                  ).astype(jnp.bfloat16),
            "q": jnp.linspace(0.0, 1.0, 4, dtype=jnp.float32)}
    checkpoint.save(tree, str(tmp_path), 1)
    restored = checkpoint.restore(tree, str(tmp_path))
    assert restored["w"].dtype == jnp.bfloat16
    for k in tree:
        np.testing.assert_array_equal(np.asarray(restored[k], np.float32),
                                      np.asarray(tree[k], np.float32))
