"""Cross-shard collectives shared by the engine's mesh layouts.

The scan engine's UE-sharded and UE x cell episode meshes
(``episode_fns(mesh=..., cell_axis=...)``, DESIGN.md §Million-UE-scaling),
the PF scheduler's cross-shard reductions and the radio chain's
cell-sharded attachment all use :func:`_axis_index` and
:func:`_global_best`, so the tie-break contract (lowest global index,
bitwise-equal to single-host ``jnp.argmax``) is defined once, here.

Both are mesh-agnostic: pass the mesh axis names, which may be a tuple
of several axes (linearised row-major).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _axis_index(axes) -> jnp.ndarray:
    """Linearised shard index over one or more mesh axes (row-major)."""
    idx = jnp.int32(0)
    for ax in axes:
        idx = idx * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
    return idx


def _global_best(loc_max, loc_arg, m_loc, cell_axis):
    """Combine per-cell-shard (max, argmax) into the global best server.

    Tie-break matches single-host jnp.argmax: lowest global cell index wins.
    Uses pmax/pmin/psum (replication-inferable) rather than all_gather.
    Returns (global_max, global_arg, mine) where ``mine`` marks rows whose
    winning cell lives on this shard.
    """
    gmax = jax.lax.pmax(loc_max, cell_axis)
    my = _axis_index(cell_axis)
    cand = jnp.where(loc_max >= gmax, my, jnp.int32(2 ** 30))
    win_shard = jax.lax.pmin(cand, cell_axis)
    mine = win_shard == my
    a = jax.lax.psum(
        jnp.where(mine, loc_arg + my * m_loc, 0).astype(jnp.int32), cell_axis)
    return gmax, a, mine
