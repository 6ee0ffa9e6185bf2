"""Checkpointing tests: atomic writes, keep-k, async save, restore."""
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.train import checkpoint as ckpt


def _tree(step):
    return {"params": {"w": jnp.full((4, 3), float(step)),
                       "b": jnp.arange(3.0)},
            "step": jnp.asarray(step)}


def test_save_restore_roundtrip(tmp_path):
    d = str(tmp_path)
    ckpt.save(d, 7, _tree(7), extra={"train_step": 7})
    tree, extra = ckpt.restore(d, 7, _tree(0))
    assert extra["train_step"] == 7
    np.testing.assert_array_equal(np.asarray(tree["params"]["w"]),
                                  np.full((4, 3), 7.0))


def test_keep_last_k_gc(tmp_path):
    d = str(tmp_path)
    for s in range(6):
        ckpt.save(d, s, _tree(s), keep_last=2)
    assert ckpt.all_steps(d) == [4, 5]
    assert ckpt.latest_step(d) == 5


def test_atomic_no_tmp_leftover(tmp_path):
    d = str(tmp_path)
    ckpt.save(d, 1, _tree(1))
    assert not any(p.endswith(".tmp") for p in os.listdir(d))


def test_async_save(tmp_path):
    d = str(tmp_path)
    t = ckpt.save_async(d, 3, _tree(3))
    t.join(timeout=30)
    assert ckpt.latest_step(d) == 3


def test_restore_rejects_structure_mismatch(tmp_path):
    d = str(tmp_path)
    ckpt.save(d, 1, _tree(1))
    with pytest.raises(ckpt.CheckpointCorrupt):
        ckpt.restore(d, 1, {"different": jnp.zeros(3)})
