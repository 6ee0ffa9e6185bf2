"""Mobility models: teleport moves and bounded random walks."""
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.sim import mobility, radio
from repro.sim.mobility import random_moves, random_walk


def test_random_moves_is_teleport_no_step_param():
    """Regression: random_moves used to accept (and ignore) a step_m arg."""
    assert "step_m" not in inspect.signature(random_moves).parameters
    idx, xyz = random_moves(jax.random.PRNGKey(0), 100, 10, 3000.0)
    idx, xyz = np.asarray(idx), np.asarray(xyz)
    assert idx.shape == (10,) and xyz.shape == (10, 3)
    assert len(set(idx.tolist())) == 10          # distinct UEs
    assert (xyz[:, :2] >= 0.0).all() and (xyz[:, :2] <= 3000.0).all()


def test_random_walk_respects_step_bounds_and_clipping():
    key = jax.random.PRNGKey(1)
    pos = jnp.asarray(np.column_stack([
        np.random.default_rng(0).uniform(0, 1000, (50, 2)),
        np.full(50, 1.5)]).astype(np.float32))
    idx = jnp.arange(50)
    step = 30.0
    new = np.asarray(random_walk(key, pos, idx, step, 1000.0))
    d = new[:, :2] - np.asarray(pos)[:, :2]
    assert (np.abs(d) <= step + 1e-4).all()
    np.testing.assert_allclose(new[:, 2], 1.5)

    # clipping at the border: start in a corner, huge step
    corner = jnp.asarray([[0.5, 0.5, 1.5]], dtype=jnp.float32)
    out = np.asarray(random_walk(key, corner, jnp.arange(1), 5000.0, 1000.0))
    assert (out[:, :2] >= 0.0).all() and (out[:, :2] <= 1000.0).all()


# ------------------------------------------- the mover window, by position
# (n, shards, n_move): one block holding the window's start and wrapped end
# (unsharded), the window covering the block, and blocks that the window
# misses, enters, leaves, covers or holds both ends of
WINDOWS = {"unsharded": (40, 1, 8), "unsharded_all": (40, 1, 40),
           "shards": (40, 4, 8), "shards_cover": (40, 4, 15),
           "shards_both_ends": (40, 4, 35)}


def _starts(n, n_move):
    return sorted({0, n // 2, n - n_move, (n - n_move + 1) % n, n - 1})


def _field(n, seed=0):
    rng = np.random.default_rng(seed)
    xy = rng.uniform(0.0, 500.0, (n, 2))
    xy[:3] = [[0.0, 0.0], [500.0, 500.0], [0.0, 500.0]]   # on the border
    return jnp.asarray(np.column_stack([xy, np.full(n, 1.5)]), jnp.float32)


@pytest.mark.parametrize("case", sorted(WINDOWS))
def test_walk_window_matches_per_row_walk(case):
    """``walk_window`` moves each block's window rows by slices exactly as
    the per-row reference (``window_displacements`` + ``apply_walk``) does,
    for starts at 0, the middle, ``n - n_move``, one past it and ``n - 1``
    and every shard offset; the other rows keep their bits."""
    n, shards, n_move = WINDOWS[case]
    n_loc = n // shards
    U = _field(n)
    d = jax.random.uniform(jax.random.PRNGKey(3), (n_move, 2),
                           minval=-40.0, maxval=40.0)
    for start in _starts(n, n_move):
        for s in range(shards):
            off = s * n_loc
            blk = U[off:off + n_loc]
            win = radio.window_rows(jnp.int32(start), n_move, n, offset=off,
                                    n_loc=n_loc)
            rows = off + jnp.arange(n_loc)
            disp, moved = mobility.window_displacements(
                jnp.int32(start), d, rows, n)
            want = np.asarray(mobility.apply_walk(blk, disp, 500.0))
            got = np.asarray(mobility.walk_window(blk, d, win, 500.0))
            np.testing.assert_array_equal(got, want,
                                          err_msg=f"start {start} shard {s}")
            still = ~np.asarray(moved)
            assert (got[still].view(np.uint32)
                    == np.asarray(blk)[still].view(np.uint32)).all()
            assert int(win.count) == int(radio.window_indices(
                jnp.int32(start), n_move, n, offset=off, n_loc=n_loc)[1])
