"""The pure-functional radio chain: D -> G -> RSRP -> a -> SINR -> CQI -> SE.

This module is the single source of truth for the paper's Figure-1 physics.
Every consumer is a thin view over it:

* the smart-update graph (``core/blocks.py``) keeps its dirty-row caching
  machinery but delegates the *math* of each node to the functions here;
* the scan-compiled TTI engine (``mac/engine.py``) calls the same functions
  inside ``lax.scan`` (and inside ``shard_map`` on a device mesh);
* the batched env (``env/crrm_env.py``) calls :func:`radio_forward` inside
  ``reset`` to recompute the chain for a freshly drawn topology, which is
  what makes batching over *topologies* (not just seeds) possible.

Everything here is pure and jit/vmap/shard_map-compatible along the UE axis:
no hidden state, no Python mutation, arrays in -> arrays out.  The split
follows Sionna's differentiable-by-construction layers (PAPERS.md): physics
as stateless functions, caching as a wrapper.

Two data types:

* :class:`RadioConfig` -- the hashable trace-time configuration (pathloss /
  antenna closures, noise, frequency grid, fading + reporting knobs).  It is
  a NamedTuple of hashables, so it can ride ``jax.jit`` static arguments and
  key trace caches.
* :class:`RadioStatic` -- the per-deployment pytree: cell positions, the
  power matrix and sector boresights as *leaves* (traced, vmap-able) with a
  ``RadioConfig`` as static aux data.  ``CRRM.radio_static()`` builds one
  from the live graph roots.

PRNG key conventions (THE single documented convention -- ``CRRM``,
the episode engine and the env all draw through these helpers):

* :func:`episode_key` -- the per-simulation episode key is
  ``fold_in(PRNGKey(seed), 0x6d6163)`` ("mac");
* :func:`tti_keys` -- TTI ``t`` of an episode consumes four streams
  ``fold_in(key, 4 * t + i)`` for ``i`` = mobility, fading, traffic, HARQ
  (in that order);
* :func:`reset_keys` -- a topology-resampling env reset splits its seed into
  ``(topology, fading, episode)`` with one ``jax.random.split(key, 3)``;
* :func:`draw_fading` -- the one fading draw (wideband or per-RB subband
  block fading), shared by ``CRRM.resample_fading`` and the engine's
  per-TTI redraw so both consume identical streams from equal keys.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.sim import fading as fading_mod
from repro.sim import phy
from repro.sim.antenna import Antenna_gain


class RadioConfig(NamedTuple):
    """Hashable trace-time configuration of the radio chain.

    ``pathgain_fn`` and ``antenna`` are bound methods / frozen dataclasses
    (hashable, comparable), so a ``RadioConfig`` can sit in jit caches and
    in the static aux data of a :class:`RadioStatic` pytree.
    """

    pathgain_fn: Callable    # (d2d, d3d, h_bs, h_ut) -> linear gain
    antenna: Antenna_gain    # sector pattern (ignored when n_sectors == 1)
    n_sectors: int
    noise_w: float           # noise power per frequency chunk (watts)
    n_subbands: int          # power subbands
    n_rb: int                # physical RBs per subband
    n_rb_subbands: int       # CQI subbands per power subband (1 = wideband)
    coherence_rb: int        # block-fading coherence bandwidth, in RBs
    rayleigh_fading: bool
    attach_ignores_fading: bool   # associate on the long-term mean RSRP
    cqi_wideband: bool       # EESM-pool CQI reports per power subband
    eesm_beta: float

    @property
    def n_freq(self) -> int:
        """Scheduling-frequency chunks (trailing axis of SE/CQI/RSRP)."""
        return self.n_subbands * self.n_rb_subbands


def config_from_params(params, pathgain_fn, antenna) -> RadioConfig:
    """Bind a ``CRRM_parameters`` to concrete pathloss/antenna closures."""
    p = params
    return RadioConfig(
        pathgain_fn=pathgain_fn, antenna=antenna, n_sectors=p.n_sectors,
        noise_w=p.chunk_noise_W, n_subbands=p.n_subbands, n_rb=p.n_rb,
        n_rb_subbands=p.n_rb_subbands, coherence_rb=p.coherence_rb,
        rayleigh_fading=p.rayleigh_fading,
        attach_ignores_fading=p.attach_ignores_fading,
        cqi_wideband=(p.cqi_report == "wideband"),
        eesm_beta=p.cqi_eesm_beta)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class RadioStatic:
    """Per-deployment radio inputs: array leaves + a static config.

    A pytree whose leaves (cell positions ``C``, power matrix ``P``, sector
    boresights ``bore``) trace through jit/vmap/shard_map while the
    :class:`RadioConfig` rides as static aux data -- so a jitted consumer
    re-specialises per *configuration* but not per *deployment*.
    """

    C: Any                   # (n_cells, 3)
    P: Any                   # (n_cells, n_freq) watts
    bore: Any                # (n_cells,) sector boresights, radians
    cfg: RadioConfig

    def tree_flatten(self):
        return (self.C, self.P, self.bore), self.cfg

    @classmethod
    def tree_unflatten(cls, cfg, children):
        C, P, bore = children
        return cls(C, P, bore, cfg)


class RadioOutputs(NamedTuple):
    """Everything :func:`radio_forward` derives for one set of positions."""

    G: Any                   # faded gain (n_ue, n_cell[, n_freq])
    rsrp: Any                # (n_ue, n_cell, n_freq)
    a: Any                   # (n_ue,) i32 serving-cell attachment
    gamma: Any               # (n_ue, n_freq) linear SINR
    cqi: Any                 # (n_ue, n_freq) at reporting resolution
    mcs: Any                 # (n_ue, n_freq)
    se: Any                  # (n_ue, n_freq) bits/s/Hz


# ---------------------------------------------------------------------------
# composable pure functions (the Figure-1 boxes)
# ---------------------------------------------------------------------------
def compute_distances(U, C):
    """(d2d, d3d, az): 2-D/3-D distances and the cell->UE bearing."""
    dx = U[:, None, 0] - C[None, :, 0]
    dy = U[:, None, 1] - C[None, :, 1]
    dz = U[:, None, 2] - C[None, :, 2]
    d2d = jnp.sqrt(dx * dx + dy * dy)
    d3d = jnp.sqrt(d2d * d2d + dz * dz)
    az = jnp.arctan2(dy, dx)
    return d2d, d3d, az


def make_gain_fn(pathgain_fn, antenna: Antenna_gain, n_sectors: int):
    """The link-gain closure: pathloss x sector pattern x fading.

    Shared verbatim by the graph's ``GainNode`` and :func:`pathgains`, so
    both paths are bit-exact by construction.  The fading factor may carry
    one extra trailing frequency axis (per-RB block fading); the gain then
    inherits that rank.
    """
    def gain(d2d, d3d, az, h_ut, h_bs, bore, fad):
        g = pathgain_fn(d2d, d3d, h_bs[None, :], h_ut[:, None])
        if n_sectors > 1:
            g = g * antenna.gain_linear(az, bore)
        if fad.ndim == g.ndim + 1:        # frequency-selective fading
            g = g[..., None]
        return g * fad

    return gain


def pathgains(cfg: RadioConfig, U, C, bore, geom=None):
    """Unfaded linear gain (n_ue, n_cell): pathloss x sector pattern.

    ``geom`` lets a caller reuse a :func:`compute_distances` result.
    """
    d2d, d3d, az = compute_distances(U, C) if geom is None else geom
    gain = make_gain_fn(cfg.pathgain_fn, cfg.antenna, cfg.n_sectors)
    ones = jnp.ones((U.shape[0], C.shape[0]), d2d.dtype)
    return gain(d2d, d3d, az, U[:, 2], C[:, 2], bore, ones)


def apply_fading(G0, fad):
    """Broadcast a fading factor onto an unfaded gain (rank-polymorphic);
    ``fad=None`` is the unfaded channel (``G0 * 1 == G0`` bitwise)."""
    if fad is None:
        return G0
    if fad.ndim == G0.ndim + 1:
        return G0[..., None] * fad
    return G0 * fad


def rsrp(G, P):
    """R[i, j, k] = p_jk * G_ijk (stacked per-frequency blocks of Fig. 1).

    ``G`` is (n_ue, n_cell) for the flat wideband channel or (n_ue, n_cell,
    n_freq) when fading is frequency selective; resolved at trace time.
    """
    if G.ndim == 3:
        return G * P[None, :, :]
    return G[:, :, None] * P[None, :, :]


def attachment(R):
    """Serve each UE from the cell with the largest wideband RSRP."""
    return jnp.argmax(R.sum(axis=2), axis=1).astype(jnp.int32)


def wanted(R, a):
    """w[i, k]: the serving cell's RSRP per frequency chunk."""
    return jnp.take_along_axis(R, a[:, None, None], axis=1)[:, 0, :]


def interference(R, w):
    """u[i, k] = sum_j R[i, j, k] - w[i, k]."""
    return R.sum(axis=1) - w


def sinr_from_wu(w, u, noise_w: float):
    """gamma = w / (noise + u), linear."""
    return w / (noise_w + u)


def sinr(R, a, noise_w: float):
    """(gamma, w, u) for serving assignment ``a``."""
    w = wanted(R, a)
    u = interference(R, w)
    return sinr_from_wu(w, u, noise_w), w, u


def quantize_cqi(gamma):
    """Per-chunk CQI quantisation of a linear SINR tensor."""
    return phy.sinr_db_to_cqi(phy.sinr_to_db(gamma))


def pool_report(gamma, n_rb_subbands: int, eesm_beta: float = 1.0):
    """Effective SINR at per-power-subband *reporting* resolution (EESM).

    Pools each power subband's ``n_rb_subbands`` CQI chunks with the
    exponential effective-SINR map (EESM, the standard link-abstraction
    for wideband CQI feedback on a selective channel):

        gamma_eff = -beta * log( mean_k exp(-gamma_k / beta) )

    which is dominated by the *faded* chunks -- a single wideband MCS must
    survive the whole allocation, so the report is conservative (a linear
    mean would Jensen-inflate it and wideband reporting would spuriously
    *beat* subband reporting).  Computed via logsumexp for stability at
    the large linear SINRs the chain produces; broadcast back onto the
    full frequency grid so downstream shapes are unchanged.
    Rank-polymorphic over leading axes (works on the (n_ue, n_freq) chain
    and the engine's tabulated (n_ue, n_cell, n_freq) tensors alike).
    """
    s = n_rb_subbands
    shp = gamma.shape
    g = gamma.reshape(shp[:-1] + (shp[-1] // s, s))
    eff = -eesm_beta * (jax.scipy.special.logsumexp(-g / eesm_beta, axis=-1)
                        - jnp.log(float(s)))
    return jnp.broadcast_to(eff[..., None], eff.shape + (s,)).reshape(shp)


def cqi_report(gamma, n_rb_subbands: int, wideband: bool,
               eesm_beta: float = 1.0):
    """CQI at the configured reporting resolution (``cqi_report`` knob).

    ``wideband`` decouples reporting from fading resolution: the SINR is
    EESM-pooled per power subband before quantisation, so every chunk of
    a subband reports the same CQI.  At ``n_rb_subbands=1`` (or subband
    reporting) this is exactly the legacy per-chunk :func:`quantize_cqi`.
    """
    if wideband and n_rb_subbands > 1:
        return quantize_cqi(pool_report(gamma, n_rb_subbands, eesm_beta))
    return quantize_cqi(gamma)


def cqi_of(cfg: RadioConfig, gamma):
    """:func:`cqi_report` with the knobs read off a :class:`RadioConfig`."""
    return cqi_report(gamma, cfg.n_rb_subbands, cfg.cqi_wideband,
                      cfg.eesm_beta)


def mcs_of(cqi):
    return phy.cqi_to_mcs(cqi)


def se_of(mcs, cqi):
    """Spectral efficiency of the selected MCS, zeroed at CQI 0."""
    return jnp.where(cqi > 0, phy.mcs_to_efficiency(mcs), 0.0)


def se_chain(cfg: RadioConfig, gamma):
    """(se, cqi) from a linear SINR tensor, at reporting resolution."""
    cqi = cqi_of(cfg, gamma)
    return se_of(mcs_of(cqi), cqi), cqi


# ---------------------------------------------------------------------------
# differentiable relaxations (DESIGN.md §RL-and-differentiability)
# ---------------------------------------------------------------------------
class RelaxConfig(NamedTuple):
    """Trace-time flags selecting soft relaxations of the MAC chain.

    The forward chain has three non-differentiable points: argmax
    attachment, the CQI quantisation staircase, and the max_cqi
    scheduler's winner-take-all.  Each gets an independently flag-gated
    relaxation; ``relax=None`` everywhere compiles the *exact* legacy
    program (trace-time switch, bitwise pin in tests/test_rl.py).  A
    NamedTuple of hashable scalars, so it rides jit static arguments and
    the ``episode_fns_for`` cache key like :class:`RadioConfig`.

    * ``soft_attach`` -- replace argmax attachment in the SINR chain by a
      temperature-``attach_tau`` softmax over per-cell wideband RSRP (in
      log domain, so the temperature is scale-free).  The *scheduling*
      attachment stays the hard argmax (an i32 index must index arrays);
      only the wanted/interference split softens, which is where the
      gradient w.r.t. per-cell powers flows.
    * ``cqi_mode`` -- ``"soft"``: SE from
      :func:`phy.soft_spectral_efficiency` (a C-inf sigmoid-staircase;
      the mode finite-difference checks validate); ``"ste"``:
      straight-through -- hard SE forward, soft-surrogate gradient
      (``soft + stop_gradient(hard - soft)``); ``"hard"``: quantised
      staircase (zero gradient almost everywhere).
    * ``soft_sched`` -- max_cqi's winner-take-all becomes a
      temperature-``sched_tau`` softmax share over each cell's active
      UEs (pf/rr are unaffected: pf is already smooth, rr is
      CQI-independent).
    """

    soft_attach: bool = True
    attach_tau: float = 0.1       # log-RSRP softmax temperature
    cqi_mode: str = "soft"        # "soft" | "ste" | "hard"
    se_sharpness: float = 2.0     # sigmoid slope of the soft staircase, /dB
    soft_sched: bool = True
    sched_tau: float = 1.0        # SE-softmax temperature (bits/s/Hz scale)


def soft_attach_sinr(R, meas, tau: float, noise_w: float):
    """Soft wanted/interference split: gamma under softmax attachment.

    ``meas`` is the (n_ue, n_cell) wideband association measurement (the
    same tensor the hard argmax reads).  Attachment weights are
    ``softmax(log meas / tau)`` per UE; the wanted power is the weighted
    combination of per-cell RSRP rows and everything else interferes:

        w[i, k] = sum_j p_ij R[i, j, k],   u[i, k] = sum_j R[i, j, k] - w

    As ``tau -> 0`` the weights collapse onto the argmax cell and this
    reduces to :func:`sinr`.  Differentiable w.r.t. ``R`` *and* ``meas``
    (so power changes can re-rank cells with a smooth effect).
    """
    logits = jnp.log(jnp.maximum(meas, 1e-30)) / tau
    p = jax.nn.softmax(logits, axis=1)                     # (n_ue, n_cell)
    w = jnp.einsum("uc,ucf->uf", p, R)
    u = R.sum(axis=1) - w
    return sinr_from_wu(w, u, noise_w)


def se_chain_relaxed(cfg: RadioConfig, gamma, relax: "RelaxConfig | None"):
    """(se, cqi): :func:`se_chain` with the CQI staircase optionally relaxed.

    ``relax=None`` / ``cqi_mode="hard"`` is byte-for-byte :func:`se_chain`.
    The reported ``cqi`` stays hard-quantised i32 in every mode (consumers
    index tables with it); only the SE value softens.
    """
    if relax is None or relax.cqi_mode == "hard":
        return se_chain(cfg, gamma)
    if cfg.cqi_wideband and cfg.n_rb_subbands > 1:
        gamma = pool_report(gamma, cfg.n_rb_subbands, cfg.eesm_beta)
    cqi = quantize_cqi(gamma)
    soft = phy.soft_spectral_efficiency(gamma, relax.se_sharpness)
    if relax.cqi_mode == "ste":
        hard = se_of(mcs_of(cqi), cqi)
        return soft + jax.lax.stop_gradient(hard - soft), cqi
    return soft, cqi


# ---------------------------------------------------------------------------
# THE dirtiness convention (DESIGN.md §Smart-update-in-scan)
# ---------------------------------------------------------------------------
# Both smart-update surfaces -- the host-driven graph (core/graph.py row
# buckets) and the scan-compiled incremental path below -- speak one
# convention: a dirty-row set becomes a *fixed-size index vector padded with
# a repeated valid row index*.  Row recomputation is idempotent (same inputs
# -> bit-identical outputs), so padded rows recompute and scatter their own
# unchanged values; no masking, no `where`, no out-of-bounds clamping.  The
# host side pads to power-of-two buckets (logarithmic jit specialisations);
# the traced side compacts a boolean mask to a static budget (one
# specialisation per budget), which is what survives `lax.scan`, `vmap`
# batching and `shard_map` sharding unchanged.
def pad_indices(rows) -> "np.ndarray":
    """Pad a host-side dirty-row index set to the next power-of-two bucket.

    Padding repeats the first index, which keeps the padded recompute
    idempotent while bounding the number of distinct jit specialisations
    logarithmically in the row count.  (Re-exported by ``core.graph`` --
    the graph's row buckets and the scan's :func:`dirty_indices` are two
    faces of this one convention.)
    """
    import numpy as np
    idx = np.asarray(sorted(rows), dtype=np.int32)
    n = len(idx)
    bucket = 1 << max(0, (n - 1).bit_length())
    if bucket > n:
        idx = np.concatenate([idx, np.full(bucket - n, idx[0], np.int32)])
    return idx


def dirty_indices(mask, budget: int):
    """Compact a traced boolean dirty mask to a ``budget``-sized index vector.

    The traced twin of :func:`pad_indices`: the indices of the True entries
    in ascending order, padded with row 0 -- a *valid* row, so the padded
    recompute is idempotent exactly like the graph's repeated-first-index
    buckets.  ``budget`` must be a static upper bound on the dirty count
    (dirt beyond the budget would be silently dropped -- callers derive the
    bound from the mover count).  Pure gather/scatter shapes: composes with
    ``vmap`` and ``shard_map`` (each shard compacts its local mask against
    the same budget).

    Implemented as an O(n log budget) ``top_k`` over a rank score instead
    of the full ``jnp.nonzero`` compaction (a sort-based cumsum+scatter
    that measured 14 ms/TTI at 100k UEs): True rows score ``n - i`` (so
    the top-k of the score IS the ascending True index set), False rows
    score 0 and their slots are rewritten to the row-0 pad.  Callers with
    a *known* window skip even this -- the window-mover regimes address
    their rows by position via :func:`window_rows`.
    """
    n = mask.shape[0]
    k = min(budget, n)
    score = jnp.where(mask, n - jnp.arange(n, dtype=jnp.int32), 0)
    vals, idx = jax.lax.top_k(score, k)
    idx = jnp.where(vals > 0, idx, 0).astype(jnp.int32)
    if budget > n:                       # degenerate: pad beyond the axis
        idx = jnp.concatenate(
            [idx, jnp.zeros((budget - n,), jnp.int32)])
    return idx


def window_indices(start, n_move: int, n: int, *, offset=0, n_loc=None):
    """A circular mover window's dirty rows as an index vector.

    The index form of :func:`window_rows`, kept as the reference its
    slices are tested against: the window movers
    (``sim.mobility.window_movers``) are the global rows ``[start, start +
    n_move) mod n``, and window slot ``k`` maps to row ``(start + k) mod
    n``.  ``offset``/``n_loc`` restrict to a shard's contiguous local
    block (global row ``g`` -> local row ``g - offset``); slots outside
    the block pad with row 0, THE valid-index padding of the dirtiness
    convention.  When the window covers the block (``n_move >= n_loc``)
    every local row recomputes.

    Returns ``(idx, count)``: the padded local index vector plus the
    number of genuinely dirty local rows (the telemetry ``dirty_rows``
    counter; psums to the global ``n_move`` under a mesh).
    """
    n_loc = n if n_loc is None else n_loc
    if n_move >= n_loc:
        return jnp.arange(n_loc, dtype=jnp.int32), jnp.int32(n_loc)
    g = (start + jnp.arange(n_move, dtype=jnp.int32)) % n
    local = g - offset
    valid = (local >= 0) & (local < n_loc)
    return (jnp.where(valid, local, 0).astype(jnp.int32),
            valid.sum().astype(jnp.int32))


#: trace-time tally of how the dirty-row patches addressed their rows
_WINDOW_LOWERINGS = {"slices": 0, "indexed": 0}


def window_lowerings() -> dict:
    """``{"slices": n, "indexed": m}``: how many dirty-row patches traced
    so far in this process took a known window's rows by position
    (:class:`WindowRows`) and how many gathered and scattered through an
    index vector (read deltas)."""
    return dict(_WINDOW_LOWERINGS)


class WindowRows(NamedTuple):
    """A circular window's rows inside one contiguous block, by position.

    Built by :func:`window_rows`; the slice form of :func:`window_indices`.
    The window wraps only at global row ``n``, a block boundary, so the
    block holds its window rows as at most two runs of consecutive rows,
    and a slab of ``size`` rows holds them in row order: run ``(c, first,
    stop, slot0, live)`` fills slab positions ``[first, stop)``, which are
    block rows ``c + p`` and window slots ``slot0 + p``.  ``live`` is None
    for a run that is always taken and, for a second run (a block that
    holds both ends of the window), the traced condition that it is not
    empty.  The slab's other positions are padding: they recompute row
    0's inputs, and row 0 takes their value, as the index form's padded
    writes give it.  With ``full`` the window covers the block and every
    block row recomputes from its own inputs, as the index form's
    ``arange``.  ``count`` is the window rows in the block (the index
    form's count).
    """

    size: int          # static slab rows
    full: bool         # static: the slab is the whole block
    runs: tuple        # ((c, first, stop, slot0, live), ...)
    pad: Any           # (padded, position, at_edge) | None: no padding
    count: Any         # i32 window rows in the block

    def _mask(self, first, stop, ndim):
        p = jnp.arange(self.size, dtype=jnp.int32)
        m = (p >= first) & (p < stop)
        return m.reshape((self.size,) + (1,) * (ndim - 1))

    def update(self, x, fn):
        """``x`` after ``fn(x, c, mask, slot0)`` for each run (a live run
        under ``lax.cond``): ``fn`` rewrites the slab at block row ``c``,
        ``mask`` its window positions."""
        for c, first, stop, slot0, live in self.runs:
            def one(y, c=c, first=first, stop=stop, slot0=slot0):
                return fn(y, c, self._mask(first, stop, y.ndim), slot0)
            x = one(x) if live is None else jax.lax.cond(
                live, one, lambda y: y, x)
        return x

    def take(self, x):
        """The slab of ``x`` the row chain recomputes: the window's rows by
        position, row 0 at the padding.

        A 2-D ``x`` is taken column by column: the TPU stores a narrow
        field such as the positions column-major and the kernel reads a
        row-major slab, and the one-dimensional columns between them let
        each keep its layout, with one copy into the slab."""
        if self.full:
            return x
        if x.ndim == 2:
            return jnp.stack([self.take(x[:, j])
                              for j in range(x.shape[1])], axis=1)
        slab = jnp.broadcast_to(x[0], (self.size,) + x.shape[1:])
        for c, first, stop, _, _ in self.runs:
            rows = jax.lax.dynamic_slice_in_dim(x, c, self.size)
            slab = jnp.where(self._mask(first, stop, x.ndim), rows, slab)
        return slab

    def put(self, x, rows):
        """``x`` with the recomputed slab ``rows`` written back in place:
        the window's rows, then row 0 from the padding."""
        if self.full:
            return rows

        def write(y, c, mask, _):
            old = jax.lax.dynamic_slice_in_dim(y, c, self.size)
            return jax.lax.dynamic_update_slice_in_dim(
                y, jnp.where(mask, rows, old), c, 0)

        x = self.update(x, write)
        if self.pad is None:
            return x
        padded, pos, at_edge = self.pad
        if at_edge:                      # a static slice of the kernel's out
            row = jnp.where(pos == 0, rows[:1], rows[-1:])
        else:
            row = jax.lax.dynamic_slice_in_dim(rows, pos, 1)
        return jax.lax.dynamic_update_slice_in_dim(
            x, jnp.where(padded, row, x[:1]), 0, 0)


def window_rows(start, n_win: int, n: int, *, offset=0, n_loc=None,
                size=None) -> WindowRows:
    """The circular window ``[start, start + n_win) mod n`` inside the
    block of ``n_loc`` rows at global row ``offset``, by position.

    ``size`` is the slab's static row count, ``min(n_win, n_loc)`` by
    default (the index form's budget) and at least that otherwise.  The
    window's rows of the block form one run, or two where the block holds
    both ends of the window: the second is possible only when ``n_win > n
    - n_loc``, which a block of a sharded field meets only when the window
    covers it, so a shard takes one slice per pass.  A single run leaves
    its padding at one edge of the slab.  Built from scalars in O(1);
    :class:`WindowRows` takes and writes the rows with ``dynamic_slice``
    and ``dynamic_update_slice``.
    """
    n_loc = n if n_loc is None else n_loc
    size = min(n_win, n_loc) if size is None else size
    if not min(n_win, n_loc) <= size <= n_loc:
        raise ValueError(f"slab size {size} must lie in "
                         f"[{min(n_win, n_loc)}, {n_loc}]")
    i32 = jnp.int32
    zero = jnp.zeros((), i32)
    delta = jnp.remainder(jnp.asarray(start, i32) - offset, n)
    hi = delta < n_loc                   # the window starts in the block
    lo_len = jnp.clip(delta + n_win - n, 0, n_loc)   # its wrapped end
    # the first run: from the window's start, else its wrapped end
    c = jnp.where(hi, jnp.clip(delta, 0, n_loc - size), 0)
    first = jnp.where(hi, delta - c, 0)
    stop = jnp.where(hi, jnp.minimum(delta + n_win, n_loc) - c, lo_len)
    runs = [(c, first, stop, jnp.where(hi, c - delta, n - delta), None)]
    count = jnp.maximum(stop - first, 0)
    two = n_win > n - n_loc              # both ends can lie in the block
    if two:
        live = hi & (lo_len > 0)
        lo_end = jnp.where(live, lo_len, 0)
        runs.append((zero, zero, lo_end, n - delta, live))
        count = count + lo_end
    if n_win >= n_loc:
        full, pad, count = True, None, jnp.asarray(n_loc, i32)
    elif two:                            # one block: the window fills it
        full = False                     # but for the slab's spare rows
        pad = None if size == n_win else (
            jnp.asarray(True), jnp.where(lo_end < first, lo_end, stop),
            False)
    else:
        full = False
        pad = (count < size, jnp.where(first > 0, 0, size - 1), True)
    return WindowRows(size=size, full=full, runs=tuple(runs), pad=pad,
                      count=count.astype(i32))


# ---------------------------------------------------------------------------
# the incremental (smart-update-in-scan) path
# ---------------------------------------------------------------------------
class RadioState(NamedTuple):
    """The carried radio tensors of the incremental path.

    Everything the MAC needs per TTI plus what a dirty-row patch must
    scatter into.  A plain pytree, so it rides a ``lax.scan`` carry, a
    ``vmap`` batch axis, or a ``shard_map`` UE shard like any other
    per-UE state.  Optional leaves are ``None`` when the regime doesn't
    need them (trace-time constant treedef):

    * ``se``/``cqi``/``a`` -- the serving-chain outputs at the
      instantaneous attachment (non-handover regimes; the O(n_ue) carry,
      attachment being row-local);
    * ``meas`` + ``se_all``/``cqi_all`` -- the (n_ue, n_cell) wideband
      measurement and (n_ue, n_cell, n_freq) per-candidate-cell tables
      (handover regimes, where the serving cell is *carried* MAC state
      and any UE may switch cells without its radio row dirtying -- A3
      reads the full measurement matrix every TTI);
    * ``G``/``G0`` -- the faded / long-term gain matrices, kept only when
      per-cell power deltas must be applied without re-running
      geometry+pathloss (:func:`radio_update_cells`).

    Leaves that a regime doesn't read are ``None`` rather than dead
    weight: an (n_ue, n_cell) leaf in a scan carry costs a scatter *and*
    a carry copy per TTI, which at 100k UEs x 57 cells is most of the
    incremental path's budget.
    """

    meas: Any        # (n_ue, n_cell) wideband measurement RSRP | None
    a: Any           # (n_ue,) i32 attachment (argmax of meas rows) | None
    se: Any          # (n_ue, n_freq) | None
    cqi: Any         # (n_ue, n_freq) | None
    se_all: Any      # (n_ue, n_cell, n_freq) | None
    cqi_all: Any     # (n_ue, n_cell, n_freq) | None
    G: Any           # faded gain (n_ue, n_cell[, n_freq]) | None
    G0: Any          # unfaded gain (n_ue, n_cell) | None


def _chain_rows(cfg: RadioConfig, U_rows, C, bore, fad_rows, P, *,
                with_tables: bool, with_gain: bool,
                cell_axis=None) -> RadioState:
    """The D→G→RSRP→a→SINR→CQI→SE chain for a slab of UE rows.

    Row-local by construction: every output row depends only on its own
    position/fading row (plus the replicated cell state), which is what
    makes the scatter-patch exact.  Called at full width by
    :func:`radio_init` and on gathered dirty rows by
    :func:`radio_update_rows` -- ONE implementation, so the incremental
    path is bit-exact with its own init (and matches the dense engine
    recompute, which composes the same pure functions).

    ``cell_axis`` names the mesh axes the *cell* dimension is sharded
    over (UE×cell meshes): ``C``/``bore``/``P`` and the fading columns
    are then local shards, the interference total psums across shards,
    and attachment runs through the cross-shard argmax
    (``core.distributed._global_best`` -- lowest global cell index wins
    ties, exactly like ``jnp.argmax``).  ``None`` compiles the verbatim
    single-shard chain.
    """
    geom = compute_distances(U_rows, C)
    G0 = pathgains(cfg, U_rows, C, bore, geom=geom)
    # fad_rows=None: the unfaded channel (skip the gather and the *1.0 --
    # G0 * ones is bitwise G0, so this is a pure elision)
    G = G0 if fad_rows is None else apply_fading(G0, fad_rows)
    R = rsrp(G, P)
    if cfg.rayleigh_fading and cfg.attach_ignores_fading:
        meas = rsrp(G0, P).sum(axis=2)      # long-term association (L3)
    else:
        meas = R.sum(axis=2)
    if cell_axis is None:
        a = jnp.argmax(meas, axis=1).astype(jnp.int32)
        mine = my = m_loc = None
    else:
        from repro.core.distributed import _axis_index, _global_best
        m_loc = C.shape[0]
        _, a, mine = _global_best(meas.max(axis=1),
                                  meas.argmax(axis=1).astype(jnp.int32),
                                  m_loc, cell_axis)
        my = _axis_index(cell_axis)
    se = cqi = se_all = cqi_all = None
    if with_tables:
        # the serving cell is carried MAC state (A3): tabulate the SINR
        # chain for every candidate cell so a later handover is a gather
        total = R.sum(axis=1)
        if cell_axis is not None:
            total = jax.lax.psum(total, cell_axis)
        gamma_all = R / (cfg.noise_w + (total[:, None, :] - R))
        se_all, cqi_all = se_chain(cfg, gamma_all)
    else:
        if cell_axis is None:
            gamma, _, _ = sinr(R, a, cfg.noise_w)
        else:
            # owning-shard gather of the serving row, then the psummed
            # interference split (total reorders the per-cell sum across
            # shards: 1e-5-class, the documented mesh contract)
            local_col = jnp.clip(a - my * m_loc, 0, m_loc - 1)
            w_loc = jnp.take_along_axis(
                R, local_col[:, None, None], axis=1)[:, 0, :]
            w = jax.lax.psum(
                jnp.where(mine[:, None], w_loc, 0.0), cell_axis)
            total = jax.lax.psum(R.sum(axis=1), cell_axis)
            gamma = sinr_from_wu(w, total - w, cfg.noise_w)
        se, cqi = se_chain(cfg, gamma)
    return RadioState(meas=meas if with_tables else None,
                      a=None if with_tables else a, se=se,
                      cqi=cqi, se_all=se_all, cqi_all=cqi_all,
                      G=G if with_gain else None,
                      G0=G0 if (with_gain and cfg.rayleigh_fading
                                and cfg.attach_ignores_fading) else None)


def radio_init(cfg: RadioConfig, U, C, bore, fad, P, *,
               with_tables: bool = False,
               with_gain: bool = False, cell_axis=None) -> RadioState:
    """Full-width :class:`RadioState`: the everything-dirty base case.

    Exactly :func:`_chain_rows` over all rows, so a subsequent
    :func:`radio_update_rows` patch scatters values that are bitwise
    consistent with what a full recompute would produce.
    """
    return _chain_rows(cfg, U, C, bore, fad, P, with_tables=with_tables,
                       with_gain=with_gain, cell_axis=cell_axis)


def _gather(x, idx):
    """Rows ``idx`` of ``x``: a window's slab by position, else a gather."""
    return idx.take(x) if isinstance(idx, WindowRows) else x[idx]


def _scatter(old, idx, new_rows):
    if old is None:
        return None
    if isinstance(idx, WindowRows):
        return idx.put(old, new_rows)
    return old.at[idx].set(new_rows)


def _tally(idx):
    _WINDOW_LOWERINGS["slices" if isinstance(idx, WindowRows)
                      else "indexed"] += 1


def radio_update_rows(cfg: RadioConfig, state: RadioState, U, C, bore,
                      fad, P, idx, *, cell_axis=None) -> RadioState:
    """Recompute the chain for UE rows ``idx`` and scatter them in place.

    ``idx`` follows THE dirtiness convention (:func:`dirty_indices` /
    :func:`pad_indices`): a fixed-size vector of dirty rows padded with
    repeated valid indices, so duplicate writes are idempotent and no
    validity mask is needed.  Cost is O(|idx| * n_cell) instead of the
    dense O(n_ue * n_cell) -- the smart-update win, inside jit.
    ``fad=None`` selects the unfaded chain (no gather, no multiply).
    ``idx`` may instead be a :class:`WindowRows`: the rows of a known
    circular window, taken and written back by position (the same rows,
    values and padding, with no per-row addressing).
    ``cell_axis`` shards the cell dimension (see :func:`_chain_rows`);
    the scatter stays local (per-UE leaves are identical on every cell
    shard after the psums, so patched rows agree across shards).
    """
    _tally(idx)
    with jax.named_scope("gather"):
        fad_rows = None if fad is None else _gather(fad, idx)
        U_rows = _gather(U, idx)
    with jax.named_scope("kernel"):
        rows = _chain_rows(cfg, U_rows, C, bore, fad_rows, P,
                           with_tables=state.se_all is not None,
                           with_gain=state.G is not None,
                           cell_axis=cell_axis)
    with jax.named_scope("scatter"):
        return RadioState(*(_scatter(o, idx, n)
                            for o, n in zip(state, rows)))


def radio_update_rows_fused(cfg: RadioConfig, state: RadioState, U, C, bore,
                            fad, P, idx, *, interpret=None) -> RadioState:
    """:func:`radio_update_rows` through the fused Pallas pipeline.

    The dirty-row kernel variant: take the dirty UE slab (positions +
    fading rows; a window's slices or an index gather, as in
    :func:`radio_update_rows`) with XLA, stream it through
    ``kernels.ops.fused_sinr`` (gain recomputed inside VMEM tiles against
    *all* cells -- the (|idx|, n_cell) matrices never touch HBM), write
    the patched a/se/cqi rows back.  Covers the O(n_ue)-carry regimes
    only: handover tables (``se_all``) and carried gains (``G``) need
    O(n_cell)-per-row outputs the streaming accumulator never
    materialises, so those regimes raise and stay on the XLA row
    recompute.  Same dirtiness
    convention, same idempotent padded scatter; parity vs the XLA rows
    is asserted across every registry scenario in
    tests/test_smart_update_scan.py.
    """
    if state.se_all is not None or state.G is not None:
        raise ValueError(
            "the fused dirty-row backend carries only the O(n_ue) "
            "RadioState (a/se/cqi); handover tables (se_all) and carried "
            "gains (G) need the XLA row recompute (radio_update_rows)")
    from repro.kernels import ops
    _tally(idx)
    with jax.named_scope("gather"):
        fad_rows = None if fad is None else _gather(fad, idx)
        U_rows = _gather(U, idx)
    with jax.named_scope("kernel"):
        gamma, a_rows, _, _ = ops.fused_sinr(
            U_rows, C, P, pathgain_fn=cfg.pathgain_fn, noise_w=cfg.noise_w,
            boresight=bore, fad=fad_rows,
            attach_on_mean=(fad_rows is not None and cfg.rayleigh_fading
                            and cfg.attach_ignores_fading),
            n_sectors=cfg.n_sectors, interpret=interpret)
        se_rows, cqi_rows = se_chain(cfg, gamma)
    rows = RadioState(meas=None, a=a_rows, se=se_rows, cqi=cqi_rows,
                      se_all=None, cqi_all=None, G=None, G0=None)
    with jax.named_scope("scatter"):
        return RadioState(*(_scatter(o, idx, n)
                            for o, n in zip(state, rows)))


def radio_update_cells(cfg: RadioConfig, state: RadioState, P,
                       dirty_cell_mask, *, cell_axis=None) -> RadioState:
    """Apply a per-cell power delta from the carried gain matrices.

    A dirty cell column changes *every* UE's interference sum, so all
    per-UE outputs recompute -- but from the carried ``G``/``G0`` (kept
    with ``with_gain=True``), skipping geometry and pathloss, the
    expensive transcendental half of the chain.  Branch-free: the new
    tensors are computed unconditionally and ``jnp.where``-selected
    against the carried ones on ``dirty_cell_mask.any()``, so the call
    composes with ``vmap``/``shard_map`` (no data-dependent control
    flow).  In the episode engine the power plan is scan-constant, so
    cell dirt collapses into the prepare-time :func:`radio_init`; this
    entry point serves callers that mutate ``P`` mid-stream -- the
    in-scan cell fault process (``sim.faults``) above all, whose
    outage mask changes ``P`` at fault transitions.

    ``cell_axis`` shards the cell dimension exactly as in
    :func:`_chain_rows`: the carried gains and ``P`` are local cell
    blocks, attachment runs through the cross-shard argmax and the
    interference totals psum.  ``dirty_cell_mask`` may be global or
    local -- only its ``any()`` is read, and the fault process computes
    it replicated on every shard.
    """
    R = rsrp(state.G, P)
    if cfg.rayleigh_fading and cfg.attach_ignores_fading:
        meas = rsrp(state.G0, P).sum(axis=2)
    else:
        meas = R.sum(axis=2)
    if cell_axis is None:
        a = jnp.argmax(meas, axis=1).astype(jnp.int32)
        mine = my = m_loc = None
    else:
        from repro.core.distributed import _axis_index, _global_best
        m_loc = meas.shape[1]
        _, a, mine = _global_best(meas.max(axis=1),
                                  meas.argmax(axis=1).astype(jnp.int32),
                                  m_loc, cell_axis)
        my = _axis_index(cell_axis)
    se = cqi = se_all = cqi_all = None
    if state.se_all is not None:
        total = R.sum(axis=1)
        if cell_axis is not None:
            total = jax.lax.psum(total, cell_axis)
        gamma_all = R / (cfg.noise_w + (total[:, None, :] - R))
        se_all, cqi_all = se_chain(cfg, gamma_all)
        a = None
    else:
        if cell_axis is None:
            gamma, _, _ = sinr(R, a, cfg.noise_w)
        else:
            local_col = jnp.clip(a - my * m_loc, 0, m_loc - 1)
            w_loc = jnp.take_along_axis(
                R, local_col[:, None, None], axis=1)[:, 0, :]
            w = jax.lax.psum(
                jnp.where(mine[:, None], w_loc, 0.0), cell_axis)
            total = jax.lax.psum(R.sum(axis=1), cell_axis)
            gamma = sinr_from_wu(w, total - w, cfg.noise_w)
        se, cqi = se_chain(cfg, gamma)
    new = RadioState(meas=meas, a=a, se=se, cqi=cqi, se_all=se_all,
                     cqi_all=cqi_all, G=state.G, G0=state.G0)
    any_dirty = jnp.any(dirty_cell_mask)
    pick = lambda n, o: (None if o is None
                         else jnp.where(any_dirty, n, o))
    return RadioState(*(pick(n, o) for n, o in zip(new, state)))


def radio_update(static: RadioStatic, state: RadioState, U,
                 dirty_ue_mask, dirty_cell_mask=None, *, budget: int,
                 fad=None, P=None, window=None) -> RadioState:
    """One smart update: dirty UE rows + (optionally) dirty cell columns.

    The mask-level façade over :func:`radio_update_rows` /
    :func:`radio_update_cells`: ``dirty_ue_mask`` is compacted to a
    ``budget``-sized index vector (:func:`dirty_indices`) and patched
    row-locally; a non-None ``dirty_cell_mask`` then re-derives the
    per-UE outputs from the carried gains under the (possibly new) power
    matrix ``P``.  Everything is branch-free and shape-static, so the
    call drops into ``lax.scan`` bodies, ``vmap`` batches and
    ``shard_map`` shards unchanged (each shard passes its local mask and
    rows).

    ``window=(start, n)`` declares the dirty rows to be the circular
    index window ``[start, start + n) mod n_ue`` (the window-mover
    mobility regime): its rows are then taken and written back by
    position (:func:`window_rows`, a slab of ``min(budget, n_ue)`` rows)
    instead of compacted from the mask, and ``dirty_ue_mask`` may be
    ``None``.  ``budget`` still bounds the window (``n <= budget`` is
    required).
    """
    cfg = static.cfg
    P = static.P if P is None else P
    if window is not None:
        start, n_win = window
        if n_win > budget:
            raise ValueError(f"window size {n_win} exceeds budget {budget}")
        n = U.shape[0]
        idx = window_rows(start, n_win, n, size=min(budget, n))
    else:
        idx = dirty_indices(dirty_ue_mask, budget)
    state = radio_update_rows(cfg, state, U, static.C, static.bore,
                              fad, P, idx)
    if dirty_cell_mask is not None:
        state = radio_update_cells(cfg, state, P, dirty_cell_mask)
    return state


# ---------------------------------------------------------------------------
# fading + PRNG key conventions (DESIGN.md §Radio-fns)
# ---------------------------------------------------------------------------
#: fold_in tag deriving the per-simulation episode key from params.seed
EPISODE_KEY_TAG = 0x6d6163   # "mac"


def episode_key(seed: int):
    """The legacy per-sim episode key: fold ``EPISODE_KEY_TAG`` into the
    simulation seed (what ``CRRM.init_episode_state(key=None)`` uses)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed), EPISODE_KEY_TAG)


def tti_keys(key, t):
    """The four per-TTI streams: (mobility, fading, traffic, HARQ).

    Stream ``i`` of TTI ``t`` is ``fold_in(key, 4 * t + i)`` -- one flat
    fold per (TTI, purpose) pair, so episodes of any length never collide
    streams and a single TTI is reproducible in isolation.
    """
    return tuple(jax.random.fold_in(key, 4 * t + i) for i in range(4))


def reset_keys(key):
    """A topology-resampling reset's streams: (topology, fading, episode)."""
    return jax.random.split(key, 3)


#: fold_in tag deriving the birth-death churn key lineage from the episode
#: key -- a SEPARATE lineage from the flat 4t+i folds of :func:`tti_keys`,
#: so enabling churn cannot perturb the four legacy per-TTI streams (every
#: pre-churn trajectory stays bitwise intact).
CHURN_KEY_TAG = 0x636872   # "chr"


def churn_keys(key, t):
    """The four per-TTI birth-death streams: (birth, death, position, fading).

    Stream ``i`` of TTI ``t`` is ``fold_in(fold_in(key, CHURN_KEY_TAG),
    4 * t + i)`` -- the same flat per-(TTI, purpose) layout as
    :func:`tti_keys`, hung off its own tag so the two lineages never
    collide.  Depends only on the episode key and the *absolute* TTI
    index, which is what makes chunked digital-twin serving (and
    checkpoint/restore at any chunk boundary) bitwise reproduce an
    uninterrupted run.
    """
    k = jax.random.fold_in(key, CHURN_KEY_TAG)
    return tuple(jax.random.fold_in(k, 4 * t + i) for i in range(4))


#: fold_in tag deriving the cell-fault key lineage from the episode key --
#: its own lineage like :data:`CHURN_KEY_TAG`, so enabling the fault
#: process cannot perturb the four legacy per-TTI streams or the churn
#: streams (every fault-free trajectory stays bitwise intact).
FAULT_KEY_TAG = 0x666c74   # "flt"


def fault_keys(key, t):
    """The per-TTI cell-fault transition key.

    ``fold_in(fold_in(key, FAULT_KEY_TAG), t)`` -- one stream per TTI,
    hung off its own tag (see :func:`churn_keys` for the lineage
    discipline).  Depends only on the episode key and the *absolute*
    TTI index, so chunked digital-twin serving and checkpoint/restore
    at any chunk boundary bitwise reproduce an uninterrupted run.
    """
    return jax.random.fold_in(jax.random.fold_in(key, FAULT_KEY_TAG), t)


def draw_fading(cfg: RadioConfig, key, n_ues: int, n_cells: int,
                dtype=jnp.float32):
    """THE fading draw: wideband Rayleigh or per-RB subband block fading.

    Single source for ``CRRM.resample_fading`` (graph root refresh), the
    engine's per-TTI redraw and the env's topology-resampling reset: equal
    keys yield bit-identical tensors everywhere.  Returns (n_ues, n_cells)
    wideband or (n_ues, n_cells, n_freq) when ``n_rb_subbands > 1``.
    """
    if cfg.n_rb_subbands > 1:
        return fading_mod.subband_rayleigh_power(
            key, n_ues, n_cells, cfg.n_subbands * cfg.n_rb,
            cfg.coherence_rb, cfg.n_freq, dtype)
    return fading_mod.rayleigh_power(key, (n_ues, n_cells), dtype)


def unit_fading(cfg: RadioConfig, n_ues: int, n_cells: int,
                dtype=jnp.float32):
    """The no-fading factor (all ones) at the configured resolution."""
    return jnp.ones((n_ues, n_cells), dtype)


# ---------------------------------------------------------------------------
# shared jitted wrappers
# ---------------------------------------------------------------------------
# The graph nodes (core/blocks.py) and :func:`radio_forward` both dispatch
# THESE jitted callables, so an eager ``radio_forward`` reuses the exact
# executables the graph compiled (or vice versa) and the two are bit-exact
# -- not merely close: separate fusions of the same math can differ by an
# ulp, shared executables cannot.  Static arguments (the pathloss/antenna
# closures, noise, reporting knobs) are hashables, so compilations are also
# shared across simulator instances with equal configurations.
geometry_jit = jax.jit(compute_distances)


@partial(jax.jit, static_argnums=(0, 1, 2))
def gain_jit(pathgain_fn, antenna, n_sectors, U, C, d2d, d3d, az, bore, fad):
    """Jitted :func:`make_gain_fn` application (the ``GainNode`` program)."""
    return make_gain_fn(pathgain_fn, antenna, n_sectors)(
        d2d, d3d, az, U[:, 2], C[:, 2], bore, fad)


rsrp_jit = jax.jit(rsrp)
attach_jit = jax.jit(attachment)
wanted_jit = jax.jit(wanted)
interference_jit = jax.jit(interference)
sinr_jit = jax.jit(sinr_from_wu, static_argnums=(2,))
cqi_jit = jax.jit(quantize_cqi)
cqi_report_jit = jax.jit(cqi_report, static_argnums=(1, 2, 3))
mcs_jit = jax.jit(mcs_of)
se_jit = jax.jit(se_of)


# ---------------------------------------------------------------------------
# the one-call forward pass (dense backends: fused Pallas pipeline | XLA)
# ---------------------------------------------------------------------------
def pallas_available() -> bool:
    """Does the default backend run the compiled fused Pallas kernel?

    True exactly on a TPU.  There the kernel always compiles for real, so
    a configuration the compiler refuses raises to the caller instead of
    quietly falling back to XLA.  Elsewhere ``backend="auto"`` stays on
    XLA; an *explicit* ``backend="pallas"`` still runs through the
    kernel's interpret mode (bit-faithful, Python-speed -- the
    correctness path the CPU tests exercise).
    """
    return jax.default_backend() == "tpu"


def pallas_supported(cfg: RadioConfig, fad) -> bool:
    """Can the fused kernel express this configuration?

    Per-link fading (wideband or per-RB, including the
    ``attach_ignores_fading`` long-term-association regime) streams
    through the kernel's tile pipeline since the incremental backend
    landed, so ``fad`` no longer disqualifies.  The one remaining gap is
    a *non-stock* sector pattern: the kernel inlines the 3GPP 65-deg /
    30-dB horizontal pattern for fusion, so antennas with other
    ``phi_3dB_deg`` / ``A_max_dB`` / ``max_gain_dBi`` values fall back
    to XLA under ``backend="auto"`` (and raise under an explicit
    ``backend="pallas"`` with a diagnostic naming the offending knob).
    """
    return pallas_unsupported_reason(cfg, fad) is None


def pallas_unsupported_reason(cfg: RadioConfig, fad) -> "str | None":
    """``None`` when the fused kernel covers the configuration, else a
    precise human-readable diagnostic (the ``backend="pallas"`` error)."""
    del fad                     # every fading layout is kernel-expressible
    if cfg.n_sectors > 1:
        a = cfg.antenna
        stock = {"phi_3dB_deg": 65.0, "A_max_dB": 30.0, "max_gain_dBi": 0.0}
        for knob, want in stock.items():
            have = getattr(a, knob, want)
            if abs(have - want) > 1e-6:
                return (f"non-stock sector pattern: antenna.{knob}={have!r} "
                        f"(the kernel inlines the stock 3GPP pattern, "
                        f"{knob}={want}); use the XLA backend")
    return None


def _forward_pallas(static: RadioStatic, positions, P, fad=None,
                    interpret=None) -> RadioOutputs:
    """Dense chain through the fused Pallas pipeline (kernels/fused_sinr).

    The (n_ue, n_cell) distance/gain/RSRP matrices never materialise:
    the kernel accumulates the O(N) state (total power, best server, its
    RSRP row) and the CQI/SE tail runs on that.  A ``fad`` tensor streams
    through the tile pipeline (it *is* materialised -- the caller drew
    it -- but the gain/RSRP products stay in VMEM).  ``G``/``rsrp`` are
    ``None`` in the returned :class:`RadioOutputs` -- callers that need
    the full matrices want the XLA backend.
    """
    from repro.kernels import ops
    cfg = static.cfg
    gamma, a, w, u = ops.fused_sinr(
        positions, static.C, P, pathgain_fn=cfg.pathgain_fn,
        noise_w=cfg.noise_w, boresight=static.bore, fad=fad,
        attach_on_mean=(fad is not None and cfg.rayleigh_fading
                        and cfg.attach_ignores_fading),
        n_sectors=cfg.n_sectors, interpret=interpret)
    cqi = cqi_report_jit(gamma, cfg.n_rb_subbands, cfg.cqi_wideband,
                         cfg.eesm_beta)
    mcs = mcs_jit(cqi)
    se = se_jit(mcs, cqi)
    return RadioOutputs(G=None, rsrp=None, a=a, gamma=gamma, cqi=cqi,
                        mcs=mcs, se=se)


def radio_forward(static: RadioStatic, positions, fad=None,
                  fading_key=None, P=None, backend=None) -> RadioOutputs:
    """The whole radio chain as one pure call.

    ``positions`` is (n_ue, 3); the fading factor comes from ``fad`` (an
    explicit tensor), from ``fading_key`` (a fresh :func:`draw_fading`,
    honouring ``cfg.rayleigh_fading``) or defaults to no fading.  ``P``
    overrides the static power matrix (the RL power-control hook).

    ``backend`` selects the dense execution path: ``None``/``"xla"``
    (the materialised chain below -- the default, and the branch every
    bit-exactness claim below refers to), ``"pallas"`` (the fused
    ``kernels/fused_sinr`` pipeline -- O(N) HBM traffic, interpret-mode
    on CPU, ``G``/``rsrp`` returned as ``None`` since they are never
    materialised, outputs within 1e-4 of XLA) or ``"auto"`` (Pallas iff
    :func:`pallas_available` (a TPU) and :func:`pallas_supported` both
    hold, else XLA).  The flip is opt-in -- ``None`` never dispatches the
    kernel, so existing callers keep materialised, bit-exact outputs on
    every platform.  Both branches are parity-tested across every registry
    scenario (tests/test_kernel_vs_crrm.py).

    Bit-exact with the smart-update graph's node queries for the same
    inputs (asserted in tests/test_radio_fns.py): the chain below mirrors
    the graph node-for-node through the shared jitted wrappers above, so
    both paths execute the same compiled programs.  jit-, vmap- (batch
    topologies by vmapping over ``positions``/``fad``) and
    shard_map-compatible along the UE axis; under an outer trace the
    nested jits inline.
    """
    cfg = static.cfg
    P = static.P if P is None else P
    if backend not in (None, "auto", "xla", "pallas"):
        raise ValueError(f"backend must be 'auto', 'xla' or 'pallas'; "
                         f"got {backend!r}")
    n_ue, n_cell = positions.shape[0], static.C.shape[0]
    use_pallas = False
    if backend == "pallas":
        reason = pallas_unsupported_reason(cfg, fad)
        if reason is not None:
            raise ValueError(
                f"backend='pallas' cannot express this configuration: "
                f"{reason}")
        use_pallas = True
    elif backend == "auto":
        use_pallas = pallas_supported(cfg, fad) and pallas_available()
    if use_pallas:
        if fad is None and fading_key is not None and cfg.rayleigh_fading:
            fad = draw_fading(cfg, fading_key, n_ue, n_cell)
        return _forward_pallas(static, positions, P, fad=fad)
    if fad is None:
        if fading_key is not None and cfg.rayleigh_fading:
            fad = draw_fading(cfg, fading_key, n_ue, n_cell)
        else:
            fad = unit_fading(cfg, n_ue, n_cell)
    d2d, d3d, az = geometry_jit(positions, static.C)
    G = gain_jit(cfg.pathgain_fn, cfg.antenna, cfg.n_sectors, positions,
                 static.C, d2d, d3d, az, static.bore, fad)
    R = rsrp_jit(G, P)
    if cfg.rayleigh_fading and cfg.attach_ignores_fading:
        # association on the long-term mean (the graph's parallel branch)
        G0 = gain_jit(cfg.pathgain_fn, cfg.antenna, cfg.n_sectors,
                      positions, static.C, d2d, d3d, az, static.bore,
                      unit_fading(cfg, n_ue, n_cell))
        a = attach_jit(rsrp_jit(G0, P))
    else:
        a = attach_jit(R)
    w = wanted_jit(R, a)
    u = interference_jit(R, w)
    gamma = sinr_jit(w, u, cfg.noise_w)
    cqi = cqi_report_jit(gamma, cfg.n_rb_subbands, cfg.cqi_wideband,
                         cfg.eesm_beta)
    mcs = mcs_jit(cqi)
    se = se_jit(mcs, cqi)
    return RadioOutputs(G=G, rsrp=R, a=a, gamma=gamma, cqi=cqi,
                        mcs=mcs, se=se)
