"""UE mobility: random-waypoint-style displacements for a subset of UEs.

The paper's example 13 moves a fraction (10%) of UEs randomly each step; the
smart-update mechanism then only recomputes the dirtied rows.

Also home to the birth-death UE process of the digital-twin serving layer
(DESIGN.md §Digital-twin-serving): :class:`ChurnConfig` is the hashable
trace-time switch and :func:`birth_death_step` the pure per-TTI transition
over a capacity-padded active mask -- UEs arrive (Poisson) into free
capacity slots and depart (exponential lifetimes) inside the compiled scan,
no retracing.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class ChurnConfig(NamedTuple):
    """The birth-death process parameters (hashable -- a trace-time switch).

    The UE axis is *capacity-padded*: ``n_ues`` is the slot capacity, the
    live population is the ``active`` mask's popcount.  Stationary mean
    occupancy is ``arrival_rate_hz * mean_lifetime_s`` (M/M/inf), so size
    the capacity comfortably above it -- arrivals beyond free capacity are
    blocked (dropped), which is the standard finite-capacity truncation.
    """

    arrival_rate_hz: float        # Poisson arrival intensity, UEs/second
    mean_lifetime_s: float        # exponential lifetime -> per-TTI departure
    max_arrivals_per_tti: int     # static cap = the birth dirty-row budget
    newborn_backlog_bits: float = 0.0   # seed backlog (inf = full buffer)


def birth_death_step(k_birth, k_death, active, tti_s: float,
                     churn: ChurnConfig):
    """One TTI of the birth-death process over the capacity-padded mask.

    Departures first (each active UE leaves with probability
    ``tti_s / mean_lifetime_s`` -- the exponential lifetime discretised at
    TTI resolution), then arrivals: ``min(Poisson(rate * tti_s),
    max_arrivals_per_tti, free slots)`` newborns occupy the lowest-index
    free slots (slot ids carry no physical meaning -- position and fading
    are freshly drawn per newborn, so any free slot is exchangeable).

    Returns ``(active, born, n_born)``: the updated mask, the newborn
    boolean mask and its popcount.  Pure and shape-static: drops into
    ``lax.scan`` bodies and ``vmap`` batches unchanged.
    """
    n = active.shape[0]
    p_dep = min(1.0, tti_s / churn.mean_lifetime_s)
    depart = jax.random.bernoulli(k_death, p_dep, (n,)) & active
    active = active & ~depart
    lam = churn.arrival_rate_hz * tti_s
    n_arrive = jnp.minimum(
        jax.random.poisson(k_birth, lam, ()),
        churn.max_arrivals_per_tti).astype(jnp.int32)
    free = ~active
    free_rank = jnp.cumsum(free.astype(jnp.int32)) - 1   # rank among free
    born = free & (free_rank < n_arrive)
    return active | born, born, born.sum().astype(jnp.int32)


def random_moves(key, n_ues: int, n_move: int, extent_m: float):
    """Pick ``n_move`` distinct UEs and new positions for them.

    Returns (idx (n_move,), new_xyz (n_move, 3)).  Positions are fresh uniform
    draws -- teleport mobility by design (the paper's stress test), so there
    is no step-size parameter; use ``random_walk`` for incremental,
    ``step_m``-bounded displacement.
    """
    k1, k2 = jax.random.split(key)
    idx = jax.random.choice(k1, n_ues, (n_move,), replace=False)
    xy = jax.random.uniform(k2, (n_move, 2), minval=0.0, maxval=extent_m)
    z = jnp.full((n_move, 1), 1.5)
    return idx, jnp.concatenate([xy, z], axis=1)


def walk_steps(key, n: int, step_m: float):
    """Draw ``n`` uniform random-walk displacements in [-step_m, step_m)^2.

    Split from :func:`apply_walk` so the episode engine can draw at
    *global* UE count and slice the local shard's rows (its sharded-PRNG
    convention) while sharing this one walk implementation.
    """
    return jax.random.uniform(key, (n, 2), minval=-step_m, maxval=step_m)


def apply_walk(positions, d, extent_m: float):
    """Displace every position by ``d``, clamped at the region borders."""
    new_xy = jnp.clip(positions[:, :2] + d, 0.0, extent_m)
    return jnp.concatenate([new_xy, positions[:, 2:3]], axis=1)


def window_movers(key, n: int, n_move: int, step_m: float):
    """Exact-count mover selection: a random-offset circular index window.

    The digital-twin mobility regime (``mobility_move_frac``): exactly
    ``n_move`` of the ``n`` UEs take a walk step this TTI.  Movers are the
    circular window ``[start, start + n_move) mod n`` at a uniformly random
    ``start`` -- UE indices carry no spatial meaning (positions are i.i.d.
    draws), so a random index window IS a uniform random subset spatially,
    selected in O(n_move) with *no* permutation sort, and each UE's
    marginal move probability per TTI is ``n_move / n``.  The exact static
    count is what gives the incremental radio path its dirty-row budget.
    Returns ``(start, d)`` with ``d`` the (n_move, 2) displacement draws
    (global shapes -- the engine's global-draw-then-slice convention).
    """
    k_off, k_step = jax.random.split(key)
    start = jax.random.randint(k_off, (), 0, n)
    return start, walk_steps(k_step, n_move, step_m)


def window_displacements(start, d, rows, n: int):
    """Per-row displacement + mover mask for the window-mover convention.

    The per-row form of :func:`walk_window`, kept as the reference its
    slices are tested against: ``rows`` are global UE indices (a shard
    passes its own block); row r is a mover iff ``(r - start) mod n <
    n_move`` and then takes draw ``d[(r - start) mod n]`` -- so every
    shard reconstructs exactly the rows it owns from the same global
    draw, and non-movers get a zero displacement.
    """
    n_move = d.shape[0]
    j = (rows - start) % n
    moved = j < n_move
    dj = d[jnp.clip(j, 0, n_move - 1)]
    return jnp.where(moved[:, None], dj, 0.0), moved


def walk_window(positions, d, win, extent_m: float):
    """Walk the window movers of a block of positions, in place.

    ``win`` is the block's :class:`repro.sim.radio.WindowRows` of the
    window ``(start, d)`` of :func:`window_movers`: each run of window rows
    is one ``dynamic_slice`` of the positions, and its draws one slice of
    ``d`` (taken from ``d`` twice over, since a block's run may begin at
    any slot), so the movers get exactly :func:`apply_walk` with the
    draws of :func:`window_displacements`.  Every other row is left as it
    is, which is bitwise what a zero displacement gives: a position lies
    in ``[0, extent_m]`` and is never -0.0, so its clip is itself.
    """
    n_move = d.shape[0]
    dd = jnp.concatenate([d, d])

    def step(U, c, mask, slot0):
        rows = jax.lax.dynamic_slice_in_dim(U, c, win.size)
        dj = jax.lax.dynamic_slice_in_dim(dd, jnp.remainder(slot0, n_move),
                                          win.size)
        xy = jnp.where(mask, jnp.clip(rows[:, :2] + dj, 0.0, extent_m),
                       rows[:, :2])
        return jax.lax.dynamic_update_slice_in_dim(
            U, jnp.concatenate([xy, rows[:, 2:3]], axis=1), c, 0)

    return win.update(positions, step)


def random_walk(key, positions, idx, step_m: float, extent_m: float):
    """Displace the selected UEs by a uniform step, clamped at borders."""
    d = walk_steps(key, idx.shape[0], step_m)
    return apply_walk(positions[idx], d, extent_m)
