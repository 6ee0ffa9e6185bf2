"""Per-cell resource-block allocation policies (pure ``jnp``).

A cell owns ``n_rb`` resource blocks per frequency chunk per TTI.  A policy
maps the radio state produced by the CRRM graph (spectral efficiency ``se``,
``cqi``, attachment ``a``) plus MAC state (backlog-derived ``active`` mask,
PF average-rate EWMA, round-robin cursor) to an allocation matrix

    ``alloc[i, k]`` = resource blocks granted to UE ``i`` on chunk ``k``.

The frequency axis ``k`` is whatever the caller resolves the grid at: the
legacy power subbands (wideband CQI, ``n_rb`` RBs per chunk) or the
frequency-selective CQI subbands of ``n_rb_subbands > 1`` (``rb_per_chunk``
RBs per chunk, so max-CQI and PF pick *which* RBs a UE gets, not just how
many).  All policies are shape-polymorphic in ``k``.

Invariant (property-tested in tests/test_mac_properties.py):
``sum_i alloc[i, k] [a_i == j] == n_rb`` for every cell ``j`` with at least
one active attached UE on chunk ``k``, and 0 for every other cell.

Policies:

* ``rr``       -- round-robin: active attached UEs split the grid evenly,
  the integer remainder rotates with a per-TTI cursor;
* ``max_cqi``  -- opportunistic: the active UE with the best CQI takes the
  cell's whole subband grid (winner-take-all);
* ``pf``       -- proportional fair: RBs split in proportion to the
  alpha-fair weight ``rate / avg**alpha`` with ``alpha = (1+p)/(1-p)``
  derived from ``fairness_p``.  The stationary solution of that control
  law is the paper's fairness-weighted share ``se**-p`` (the legacy
  ``ThroughputNode``), which is what the single-shot graph node uses; the
  episode engine feeds the true EWMA state instead.

All functions are shape-polymorphic pure ``jnp`` and traceable, so they run
both as smart-update graph nodes and inside ``jax.lax.scan``.

Mesh-sharded operation (DESIGN.md §Radio-fns): every policy accepts an
optional ``ue_axis`` -- mesh axis name(s) the UE dimension is sharded over
inside ``shard_map``.  A cell's RB grid mixes *all* of its attached UEs, so
the per-cell reductions (active counts, PF weight sums, the max-CQI winner)
become collectives: ``psum``/``pmax`` over the UE axis plus the cross-shard
argmax of ``core.distributed._global_best`` (tie-break = lowest global UE
index, matching single-device ``jnp.argmax``).  ``ue_axis=None`` (the
default) compiles the exact legacy single-device program.

On the UE x cell episode mesh (DESIGN.md §Million-UE-scaling) the scheduler
is *deliberately not* cell-sharded: its per-cell bins are O(n_cells x K)
scalars -- tiny next to the radio leaves -- so every shard keeps the full
``n_cells`` bin range, attachment indices stay global, and the policies
need only the UE-axis collectives above.  The engine replicates ``se`` /
``cqi`` / ``a`` along the cell axes before calling ``allocate``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.mac import segments

SCHEDULER_POLICIES = ("rr", "max_cqi", "pf")

#: fairness_p -> alpha-fair exponent is singular at p=1 (max-min fairness);
#: cap keeps the exponent finite while remaining far steeper than any
#: realistic rate spread needs.
_ALPHA_MAX = 63.0

#: finite stand-in for -inf on the differentiable scheduler paths: deep
#: enough that exp(_NEG - anything) underflows to exactly 0.0 (bitwise the
#: -inf forward), finite so reverse-mode never forms inf - inf = nan.
_NEG = -1e30


def _cell_mask(active, a, n_cells):
    """M[i, j, k] = UE i is active on subband k and attached to cell j."""
    onehot = (a[:, None] == jnp.arange(n_cells)[None, :])
    return active[:, None, :] & onehot[:, :, None]


def allocate_rr(active, a, n_cells, n_rb, cursor, ue_axis=None,
                differentiable=False):
    """Round-robin: even integer split, remainder rotated by ``cursor``.

    A UE's within-cell rank (its position in the cell's active roster) is
    computed by segment rank -- one stable sort by cell plus O(n_ue x K)
    prefix sums -- instead of the O(n_ue x n_cell x K) within-cell rank
    cumsum (the measured 52 ms/TTI MAC bottleneck at 100k UE x 57 cells;
    ROADMAP).  Stable sort keeps each cell's UEs in original-index order,
    so the rank (and therefore the allocation) is bitwise identical to
    the cumsum formulation -- asserted against a mask-cumsum oracle in
    tests/test_twin.py.

    Sharded (``ue_axis``): a UE's within-cell rank is its local rank plus
    the active counts of all lower shards (the global UE order is
    shard-major, i.e. contiguous blocks), and the per-cell active totals
    are psummed.
    """
    act_i = active.astype(jnp.int32)                   # (n_ue, K)
    counts = segments.segment_sum(act_i, a, n_cells,   # (n_cells, K) local
                                  differentiable=differentiable)
    order = jnp.argsort(a)                 # stable: in-cell order preserved
    csum = jnp.cumsum(act_i[order], axis=0)            # actives at pos <= s
    offs = jnp.cumsum(counts, axis=0) - counts         # actives in cells < j
    rank_sorted = csum - 1 - offs[a[order]]            # (n_ue, K)
    rank = jnp.empty_like(rank_sorted).at[order].set(rank_sorted)
    if ue_axis is None:
        n_active = counts[a]
    else:
        from repro.core.distributed import _axis_index
        gathered = jax.lax.all_gather(counts, ue_axis)  # (n_shards, ...)
        my = _axis_index(ue_axis)
        shard = jnp.arange(gathered.shape[0])[:, None, None]
        before = jnp.where(shard < my, gathered, 0).sum(axis=0)
        rank = rank + before[a]                        # global within-cell
        n_active = gathered.sum(axis=0)[a]
    n_act = jnp.maximum(n_active, 1)
    base = n_rb // n_act
    extra = ((rank - cursor) % n_act) < (n_rb % n_act)
    return jnp.where(active, (base + extra).astype(jnp.float32), 0.0)


def allocate_max_cqi(active, cqi, a, n_cells, n_rb, ue_axis=None):
    """Winner-take-all: the best-CQI active UE gets the cell's whole grid.

    Sharded (``ue_axis``): the per-cell winner is the cross-shard argmax
    of ``core.distributed._global_best`` (ties to the lowest global UE
    index, exactly like single-device ``jnp.argmax``).
    """
    M = _cell_mask(active, a, n_cells)
    score = jnp.where(M, cqi[:, None, :], -1)          # (n_ue, n_cells, K)
    if ue_axis is None:
        winner = jnp.argmax(score, axis=0)             # (n_cells, K)
        i = jnp.arange(active.shape[0])[:, None]
    else:
        from repro.core.distributed import _axis_index, _global_best
        n_loc = active.shape[0]
        _, winner, _ = _global_best(
            score.max(axis=0), score.argmax(axis=0).astype(jnp.int32),
            n_loc, ue_axis)
        i = (_axis_index(ue_axis) * n_loc + jnp.arange(n_loc))[:, None]
    mine = winner[a]                                   # (n_ue, K)
    return jnp.where(active & (mine == i), float(n_rb), 0.0)


def allocate_max_cqi_soft(active, se, a, n_cells, n_rb, tau):
    """Soft max_cqi: a temperature-``tau`` softmax share of the grid.

    The differentiable relaxation of :func:`allocate_max_cqi`
    (``RelaxConfig.soft_sched``): each cell's active UEs split its
    ``n_rb`` RBs in proportion to ``softmax(se / tau)`` instead of
    winner-take-all.  Scoring on the (smoothly relaxed) spectral
    efficiency rather than the i32 CQI is what lets the gradient flow
    from the allocation back into powers; as ``tau -> 0`` the share
    collapses onto the best-SE UE and this reduces to the hard policy
    (up to argmax tie-breaking).  Structurally the same log-space
    segment-reduction program as :func:`allocate_pf`.  Single-device
    only -- the relaxed engine path rejects meshes.
    """
    logits = jnp.where(active, se / tau, _NEG)
    cell_max = segments.segment_max(logits, a, n_cells, fill=_NEG,
                                    differentiable=True)
    w = jnp.exp(logits - cell_max[a])
    w = jnp.where(active, w, 0.0)
    denom = segments.segment_sum(w, a, n_cells, differentiable=True)
    # 1e-15 floor: the VJP squares the denominator (see served_bits)
    share = jnp.where(denom[a] > 0.0, w / jnp.maximum(denom[a], 1e-15), 0.0)
    return n_rb * share


def allocate_pf(active, log_w, a, n_cells, n_rb, ue_axis=None,
                differentiable=False):
    """Weight-proportional split of the grid (log-space for stability).

    Two per-cell reductions over every UE: the weight maximum (the
    log-space stabiliser) and the weight sum.  The maximum is exact in
    any order, so on the TPU with few cells it lowers as a dense masked
    reduction (``segments.segment_max``: bitwise the scatter, which the
    TPU serialises); the float sum depends on its order and stays the
    scatter.

    Sharded (``ue_axis``): both reduce locally, then over the UE axis
    with ``pmax``/``psum``.  ``differentiable`` selects the plain-scatter
    segment reductions (autodiff-traceable; the relaxed engine path).
    """
    # the idle sentinel: -inf is exact but poisons reverse-mode autodiff
    # (-inf - -inf = nan in the exp's argument; the nan survives the
    # where-mask's zero cotangent), so the differentiable path uses a
    # finite sentinel -- exp(-1e30 - m) underflows to the same 0.0
    # forward, with a clean zero gradient
    neg = _NEG if differentiable else -jnp.inf
    log_w = jnp.where(active, log_w, neg)
    # segment reductions, bitwise the .at[a].max/.at[a].add scatters:
    # the max dense over few cells on the TPU, the sum a scatter whose
    # custom vmap rule avoids the slow rank-2 batched scatter
    # (repro.mac.segments)
    cell_max = segments.segment_max(log_w, a, n_cells, fill=neg,
                                    differentiable=differentiable)
    if ue_axis is not None:
        cell_max = jax.lax.pmax(cell_max, ue_axis)
    w = jnp.exp(log_w - cell_max[a])                   # in (0, 1], 0 if idle
    w = jnp.where(active, w, 0.0)
    denom = segments.segment_sum(w, a, n_cells,
                                 differentiable=differentiable)
    if differentiable:
        # the VJP squares the denominator; keep the square normal-range
        return n_rb * jnp.where(denom[a] > 0.0,
                                w / jnp.maximum(denom[a], 1e-15), 0.0)
    if ue_axis is not None:
        denom = jax.lax.psum(denom, ue_axis)
    share = jnp.where(denom[a] > 0.0, w / jnp.maximum(denom[a], 1e-30), 0.0)
    return n_rb * share


def allocate(policy, active, cqi, a, n_cells, n_rb, cursor, log_w,
             ue_axis=None, differentiable=False):
    """Dispatch to a policy; single entry point for graph node and engine.

    ``log_w`` carries the PF weights (stationary from the single-shot
    graph, EWMA-temporal from the episode engine); the other policies
    ignore it.  ``ue_axis`` names the mesh axes the UE dimension is
    sharded over inside ``shard_map`` (None = single device).
    ``differentiable`` routes the segment reductions around their
    ``custom_vmap`` wrapper (no autodiff rule) -- set by the engine's
    relaxed path, a trace-time switch with a bitwise-identical primal.
    """
    if policy == "rr":
        return allocate_rr(active, a, n_cells, n_rb, cursor, ue_axis,
                           differentiable)
    if policy == "max_cqi":
        return allocate_max_cqi(active, cqi, a, n_cells, n_rb, ue_axis)
    if policy == "pf":
        return allocate_pf(active, log_w, a, n_cells, n_rb, ue_axis,
                           differentiable)
    raise ValueError(
        f"unknown scheduler policy {policy!r}; choose from "
        f"{SCHEDULER_POLICIES}")


def pf_log_weights_stationary(se, fairness_p):
    """log(se**-p): the alpha-fair stationary weights (legacy allocation)."""
    return -fairness_p * jnp.log(jnp.maximum(se, 1e-12))


def pf_log_weights_ewma(rate, avg, fairness_p):
    """log(rate / avg**alpha): the temporal PF metric over EWMA throughput."""
    alpha = jnp.minimum((1.0 + fairness_p) / jnp.maximum(1.0 - fairness_p,
                                                         1e-6), _ALPHA_MAX)
    return (jnp.log(jnp.maximum(rate, 1e-12))
            - alpha * jnp.log(jnp.maximum(avg, 1e-3)))


def served_bits(alloc, se, backlog, rb_bw_hz, tti_s, floor=1e-30):
    """Bits actually drained per (UE, subband) in one TTI.

    Capacity of the grant, capped by the UE's total backlog (a UE cannot
    transmit bits it does not have); the cap scales every subband of the
    grant uniformly.

    ``floor`` guards the backlog/grant ratio.  The 1e-30 default is
    forward-exact; the relaxed engine path raises it to 1e-6 bits because
    reverse-mode forms ``tot**2`` in the division's VJP and a soft-SE
    grant total of ~1e-25 bits underflows that square to 0.0 -> nan.  At
    1e-6 the square stays normal; grants below a millionth of a bit are
    physically nothing, so the relaxed forward is unchanged to f32.
    """
    cap = alloc * rb_bw_hz * se * tti_s                # (n_ue, K) bits
    tot = cap.sum(axis=-1)
    scale = jnp.where(tot > 0.0,
                      jnp.minimum(backlog / jnp.maximum(tot, floor), 1.0),
                      0.0)
    return cap * scale[:, None]
