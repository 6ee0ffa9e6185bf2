"""Distributed CRRM: the paper's engine sharded over a TPU mesh.

Two implementations (both shard UEs over the ``data`` mesh axes and cells over
``model``):

* :func:`make_materialized_step` -- paper-faithful: every Figure-1 block is
  materialised as a sharded matrix; interference and attachment reduce over
  the ``model`` axis with ``psum`` / ``all_gather``.  Memory O(N_loc x M_loc).

* :func:`make_streaming_step` -- TPU-native beyond-paper form: cell tiles are
  streamed through a ``lax.scan`` and per-UE interference / best-server state
  is accumulated online (flash-attention style), so no N x M intermediate ever
  exists.  Memory O(N_loc + M_loc).  This is the jnp twin of the
  ``kernels/fused_sinr`` Pallas kernel.

* :func:`make_incremental_rows_step` -- the smart update at scale: recompute
  only the moved UE rows (streaming over all cells) and patch the persistent
  O(N) state (w, u, a).  Cost O(m x M) instead of O(N x M).

All functions are mesh-agnostic: pass the relevant UE/cell axis names, which
may be tuples (e.g. UE axis ("pod", "data") on the multi-pod mesh).

The scan engine's UE x cell episode mesh (``episode_fns(cell_axis=...)``,
DESIGN.md §Million-UE-scaling) reuses :func:`_global_best` and
:func:`_axis_index` for its cross-cell-shard attachment and owning-shard
serving-row gathers, so the tie-break contract (lowest global cell index,
bitwise-equal to single-host ``jnp.argmax``) is defined once, here.
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.sim import phy


def _axis_index(axes) -> jnp.ndarray:
    """Linearised shard index over one or more mesh axes (row-major)."""
    idx = jnp.int32(0)
    for ax in axes:
        idx = idx * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
    return idx


def _pad_cells(C_loc, P_loc, tile: int):
    """Pad the local cell block to a tile multiple with zero-power cells."""
    m_loc = C_loc.shape[0]
    pad = (-m_loc) % tile
    if pad:
        C_loc = jnp.concatenate(
            [C_loc, jnp.full((pad, 3), 1e9, C_loc.dtype)], axis=0)
        P_loc = jnp.concatenate(
            [P_loc, jnp.zeros((pad, P_loc.shape[1]), P_loc.dtype)], axis=0)
    return C_loc, P_loc



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


def _geometry(U, C):
    dx = U[:, None, 0] - C[None, :, 0]
    dy = U[:, None, 1] - C[None, :, 1]
    dz = U[:, None, 2] - C[None, :, 2]
    d2d = jnp.sqrt(dx * dx + dy * dy)
    d3d = jnp.sqrt(d2d * d2d + dz * dz)
    return d2d, d3d


def _throughput(se, a, n_cells, subband_bw, p, ue_axis):
    """Fairness allocation with cell loads reduced across UE shards."""
    active = se > 0.0
    wgt = jnp.where(active, jnp.power(jnp.maximum(se, 1e-12), -p), 0.0)
    denom = jnp.zeros((n_cells, se.shape[1]), se.dtype).at[a].add(wgt)
    denom = jax.lax.psum(denom, ue_axis)          # cell loads: global over UEs
    denom_i = denom[a]
    share = jnp.where(denom_i > 0.0, wgt / jnp.maximum(denom_i, 1e-30), 0.0)
    return share * subband_bw * se


def make_materialized_step(mesh, pathgain_fn: Callable, noise_w: float,
                           n_cells: int, subband_bw: float, fairness_p: float,
                           ue_axis=("data",), cell_axis=("model",)):
    """Paper-faithful distributed pipeline; returns jit-able f(U, C, Pw)."""
    ue_axis = tuple(ue_axis)
    cell_axis = tuple(cell_axis)

    def step(U_loc, C_loc, P_loc):
        # U_loc: (n_ue_loc, 3)  C_loc: (m_loc, 3)  P_loc: (m_loc, K)
        m_loc = C_loc.shape[0]
        d2d, d3d = _geometry(U_loc, C_loc)
        g = pathgain_fn(d2d, d3d, C_loc[None, :, 2], U_loc[:, None, 2])
        r = g[:, :, None] * P_loc[None, :, :]          # local RSRP block
        total = jax.lax.psum(r.sum(axis=1), cell_axis)  # (n_ue_loc, K)

        # global best server: per-shard (max, argmax) combined collectively
        wide = r.sum(axis=2)                            # (n_ue_loc, m_loc)
        loc_max = wide.max(axis=1)
        loc_arg = wide.argmax(axis=1).astype(jnp.int32)
        _, a, mine = _global_best(loc_max, loc_arg, m_loc, cell_axis)

        # wanted signal: owning shard contributes, others psum zeros
        my = _axis_index(cell_axis)
        local_col = jnp.clip(a - my * m_loc, 0, m_loc - 1)
        w_loc = jnp.take_along_axis(r, local_col[:, None, None], axis=1)[:, 0, :]
        w = jax.lax.psum(jnp.where(mine[:, None], w_loc, 0.0), cell_axis)

        u = total - w
        gamma = w / (noise_w + u)
        se = phy.spectral_efficiency(gamma)
        tput = _throughput(se, a, n_cells, subband_bw, fairness_p, ue_axis)
        return gamma, a, tput

    in_specs = (P(ue_axis, None), P(cell_axis, None), P(cell_axis, None))
    out_specs = (P(ue_axis, None), P(ue_axis), P(ue_axis, None))
    return jax.shard_map(step, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs)


def _stream_over_cells(U_loc, C_loc, P_loc, pathgain_fn, tile: int,
                       vary_axes=()):
    """Online accumulation over cell tiles: (total, best_val, best_idx, w_best).

    The running state is O(n_ue_loc); each tile's (n_ue_loc x tile) block
    lives only inside one scan iteration (VMEM-resident on TPU).
    """
    n_loc, k = P_loc.shape[0], P_loc.shape[1]
    n_tiles = max(1, n_loc // tile)
    C_t = C_loc[:n_tiles * tile].reshape(n_tiles, tile, 3)
    P_t = P_loc[:n_tiles * tile].reshape(n_tiles, tile, k)

    def body(carry, xs):
        total, best_val, best_idx, w_best = carry
        (c_tile, p_tile, t) = xs
        d2d, d3d = _geometry(U_loc, c_tile)
        g = pathgain_fn(d2d, d3d, c_tile[None, :, 2], U_loc[:, None, 2])
        r = g[:, :, None] * p_tile[None, :, :]       # (n_ue_loc, tile, K)
        total = total + r.sum(axis=1)
        wide = r.sum(axis=2)                          # (n_ue_loc, tile)
        t_max = wide.max(axis=1)
        t_arg = wide.argmax(axis=1).astype(jnp.int32) + t * tile
        t_w = jnp.take_along_axis(
            r, (t_arg - t * tile)[:, None, None], axis=1)[:, 0, :]
        better = t_max > best_val
        best_val = jnp.where(better, t_max, best_val)
        best_idx = jnp.where(better, t_arg, best_idx)
        w_best = jnp.where(better[:, None], t_w, w_best)
        return (total, best_val, best_idx, w_best), None

    n_ue_loc = U_loc.shape[0]
    init = (jnp.zeros((n_ue_loc, k)),
            jnp.full((n_ue_loc,), -jnp.inf),
            jnp.zeros((n_ue_loc,), jnp.int32),
            jnp.zeros((n_ue_loc, k)))
    if vary_axes:
        # inside shard_map the scan carry must be typed device-varying
        init = jax.tree_util.tree_map(
            lambda x: jax.lax.pcast(x, tuple(vary_axes), to="varying"), init)
    (total, best_val, best_idx, w_best), _ = jax.lax.scan(
        body, init, (C_t, P_t, jnp.arange(n_tiles)))
    return total, best_val, best_idx, w_best


def make_streaming_step(mesh, pathgain_fn: Callable, noise_w: float,
                        n_cells: int, subband_bw: float, fairness_p: float,
                        ue_axis=("data",), cell_axis=("model",),
                        cell_tile: int = 512):
    """O(N+M)-memory distributed pipeline (beyond-paper, TPU-native)."""
    ue_axis = tuple(ue_axis)
    cell_axis = tuple(cell_axis)

    def step(U_loc, C_loc, P_loc):
        m_loc = C_loc.shape[0]
        tile = min(cell_tile, m_loc)
        C_pad, P_pad = _pad_cells(C_loc, P_loc, tile)
        total, best_val, best_arg, w_best = _stream_over_cells(
            U_loc, C_pad, P_pad, pathgain_fn, tile, ue_axis + cell_axis)
        total = jax.lax.psum(total, cell_axis)

        _, a, mine = _global_best(best_val, best_arg, m_loc, cell_axis)
        w = jax.lax.psum(jnp.where(mine[:, None], w_best, 0.0), cell_axis)

        u = total - w
        gamma = w / (noise_w + u)
        se = phy.spectral_efficiency(gamma)
        tput = _throughput(se, a, n_cells, subband_bw, fairness_p, ue_axis)
        return gamma, a, tput

    in_specs = (P(ue_axis, None), P(cell_axis, None), P(cell_axis, None))
    out_specs = (P(ue_axis, None), P(ue_axis), P(ue_axis, None))
    return jax.shard_map(step, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs)


def make_incremental_rows_step(mesh, pathgain_fn: Callable, noise_w: float,
                               n_cells: int, subband_bw: float,
                               fairness_p: float, ue_axis=("data",),
                               cell_axis=("model",), cell_tile: int = 512):
    """Smart update at scale: recompute only moved rows against all cells.

    State (w, u, a, best_val) is O(N); the moved-row block (m x M_loc) streams
    through the same online accumulator.  Moved indices are replicated
    (every shard sees all moves; each patches the rows it owns).

    f(U, C, Pw, w, u, a, best_val, idx, new_pos) -> (U', w', u', a', best_val', tput)
    """
    ue_axis = tuple(ue_axis)
    cell_axis = tuple(cell_axis)

    def step(U_loc, C_loc, P_loc, w, u, a, best_val, idx, new_pos):
        n_ue_loc = U_loc.shape[0]
        m_loc = C_loc.shape[0]
        # which moved UEs live on this UE shard?
        ue_shard = _axis_index(ue_axis)
        lo = ue_shard * n_ue_loc
        local = (idx >= lo) & (idx < lo + n_ue_loc)
        # clamp foreign indices to row 0; mask their writes later
        li = jnp.where(local, idx - lo, 0)
        U_loc = U_loc.at[li].set(
            jnp.where(local[:, None], new_pos, U_loc[li]))

        moved = U_loc[li]                              # (m, 3)
        tile = min(cell_tile, m_loc)
        C_pad, P_pad = _pad_cells(C_loc, P_loc, tile)
        total, bval, barg, w_best = _stream_over_cells(
            moved, C_pad, P_pad, pathgain_fn, tile, ue_axis + cell_axis)
        total = jax.lax.psum(total, cell_axis)
        bv_rows, a_rows, mine = _global_best(bval, barg, m_loc, cell_axis)
        w_rows = jax.lax.psum(
            jnp.where(mine[:, None], w_best, 0.0), cell_axis)
        u_rows = total - w_rows

        # patch only locally owned rows
        def patch(buf, rows_new):
            old = buf[li]
            mask = local.reshape((-1,) + (1,) * (rows_new.ndim - 1))
            return buf.at[li].set(jnp.where(mask, rows_new, old))

        w = patch(w, w_rows)
        u = patch(u, u_rows)
        a = patch(a, a_rows)
        best_val = patch(best_val, bv_rows)

        gamma = w / (noise_w + u)
        se = phy.spectral_efficiency(gamma)
        tput = _throughput(se, a, n_cells, subband_bw, fairness_p, ue_axis)
        return U_loc, w, u, a, best_val, tput

    in_specs = (P(ue_axis, None), P(cell_axis, None), P(cell_axis, None),
                P(ue_axis, None), P(ue_axis, None), P(ue_axis),
                P(ue_axis), P(None), P(None, None))
    out_specs = (P(ue_axis, None), P(ue_axis, None), P(ue_axis, None),
                 P(ue_axis), P(ue_axis), P(ue_axis, None))
    return jax.shard_map(step, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs)
