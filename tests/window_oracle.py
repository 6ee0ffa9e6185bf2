"""The index form of the mover window, behind the interface of its slices.

The tests' oracle for ``radio.window_rows`` and ``mobility.walk_window``:
``radio.window_indices`` with a gather and ``.at[idx].set``, and the
per-row walk of ``mobility.window_displacements`` and ``apply_walk``.
Inside ``index_path()`` an engine traces with these in their place, so
its rollout can be compared with the one that takes the window by slices.
"""
import contextlib

import jax.numpy as jnp

from repro.sim import mobility, radio


class IndexRows(radio.WindowRows):
    """A window's rows as the padded index vector ``idx``."""

    def take(self, x):
        return x[self.idx]

    def put(self, x, rows):
        return x.at[self.idx].set(rows)


def index_rows(start, n_win, n, *, offset=0, n_loc=None, size=None):
    n_loc = n if n_loc is None else n_loc
    idx, count = radio.window_indices(start, n_win, n, offset=offset,
                                      n_loc=n_loc)
    if size is not None and size > idx.shape[0]:
        idx = jnp.concatenate(
            [idx, jnp.zeros((size - idx.shape[0],), jnp.int32)])
    win = IndexRows(size=int(idx.shape[0]), full=False, runs=(), pad=None,
                    count=count)
    win.idx, win.start, win.offset, win.n = idx, start, offset, n
    return win


def walk_rows(positions, d, win, extent_m):
    rows = win.offset + jnp.arange(positions.shape[0])
    disp, _ = mobility.window_displacements(win.start, d, rows, win.n)
    return mobility.apply_walk(positions, disp, extent_m)


@contextlib.contextmanager
def index_path():
    saved = radio.window_rows, mobility.walk_window
    radio.window_rows, mobility.walk_window = index_rows, walk_rows
    try:
        yield
    finally:
        radio.window_rows, mobility.walk_window = saved
