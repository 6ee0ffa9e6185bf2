"""The scan-compiled TTI engine: a whole episode as ONE compiled program.

The smart-update graph is built for sparse, event-driven mutation (move a
few UEs, re-query).  Time-stepped MAC simulation is the opposite regime:
*every* TTI touches *every* UE's buffer, so per-TTI Python dispatch over the
node graph would dominate.  This module re-expresses one TTI as a pure
function of an explicit :class:`EpisodeState` pytree

    (positions, backlog_bits, pf_avg_rate, rr_cursor, key,
     harq_bits, harq_retx, serving_cell, ttt, t)

and rolls N TTIs with ``jax.lax.scan``: one trace, one XLA program, zero
per-TTI Python (DESIGN.md §TTI-engine, §Env-API).  A 1000-UE x 1000-TTI
episode is a single device launch.

The radio *math* inside the scan is not the engine's: every D/G/RSRP/SINR/
CQI/SE evaluation delegates to the pure chain of ``repro.sim.radio``
(DESIGN.md §Radio-fns), the same functions the smart-update graph nodes
wrap -- one implementation, bit-exact across graph, engine and env.

The episode API is pure-functional (DESIGN.md §Env-API):

* :class:`EpisodeState` -- everything the scan carry needs, as a pytree.
  ``CRRM.init_episode_state(key)`` gathers it from the graph;
* :class:`EpisodeStatic` -- the per-episode radio inputs (cached SE/CQI/
  attachment plus the C/P/boresight/fading roots).  ``CRRM.episode_static()``
  reads them off the graph;
* :func:`make_episode_fns` -- builds ``step(static, state, action)`` and
  ``rollout(static, state, n_tti, action)``, both jit- and vmap-compatible:
  batching N episodes over seeds is ``jax.vmap`` over ``state`` (and
  ``action``), and compiles to one program (``src/repro/env``).

``run_episode`` is a thin wrapper: init state -> rollout -> (optionally)
write the final state back into the graph.  The write-back (``sync_state``)
is retained for the paper's mutate/query workflow but is a legacy
convenience: functional callers thread :class:`EpisodeState` explicitly and
never touch simulator attributes.

Three orthogonal feature axes, each a trace-time (Python) switch so the
disabled configuration compiles to exactly the legacy program:

* frequency-selective link adaptation (``n_rb_subbands > 1``): the fading
  factor is a per-RB block-fading tensor pooled to CQI-subband resolution,
  so SE/CQI/alloc carry a (n_ues, n_freq) frequency axis and the schedulers
  pick *which* RBs each UE gets.  ``n_rb_subbands=1`` is the wideband path.
  ``cqi_report="wideband"`` decouples *reporting* from fading resolution:
  the channel stays selective but CQI/MCS collapse to one report per power
  subband (radio.pool_report).
* stop-and-wait HARQ (``harq_bler > 0``): per-UE process state (pending TB
  bits, retx count) rides in the carry; failed TBs retransmit with a
  soft-combining SINR gain per attempt until ``harq_max_retx`` is exhausted.
  ``harq_bler=0`` compiles the HARQ-free fast path (bit-exact legacy).
* A3 handover (``ho_enabled``): the serving-cell vector ``a`` is carried
  state, updated when a neighbour beats the serving cell by
  ``ho_hysteresis_db`` for ``ho_ttt_tti`` consecutive TTIs.  Disabled, the
  serving cell is the instantaneous argmax (legacy).

Channel regimes:

* static (no mobility, no per-TTI fading, no power action): the radio chain
  (se, cqi, a) is read once from ``EpisodeStatic`` -- the scan body is
  MAC-only math;
* dynamic (``mobility_step_m`` set -- explicitly or via
  ``params.mobility_step_m`` (scenario presets with a baked-in mobility
  trajectory), ``per_tti_fading``, or a power ``action``): the radio chain
  is recomputed inside the scan from the pure ``sim.radio`` functions, so
  both paths share one implementation.  A non-None ``action`` is a
  per-episode (n_cells, n_freq) power matrix overriding ``static.P`` -- the
  RL power-control hook.

Mesh sharding (``mesh=``): the rollout runs under ``shard_map`` with the UE
axis of every per-UE tensor sharded over the named mesh axes (cells are
replicated).  The per-UE MAC math is embarrassingly parallel; the only
cross-shard traffic is the scheduler's per-cell reductions
(``mac.scheduler`` with ``ue_axis=``, reusing the mesh helpers and
cross-shard argmax of ``core.distributed``).  Per-UE PRNG draws are taken
from the *global* stream and sliced to the local block, so a sharded
episode matches the single-device rollout (asserted in
tests/test_radio_fns.py and gated in ``benchmarks/BENCH_sharded.json``):
*bitwise* for the integer-exact schedulers (rr, max_cqi) and to 1e-5 for
pf, whose cross-shard ``psum`` reorders a float reduction.  (Under bursty
traffic, pf's ulp-level residues can flip backlog-active masks and the
trajectories then diverge chaotically -- inherent to any reduction
reordering, not a sharding bug; the equivalence suite pins the
non-chaotic regimes.)

All mutable simulator state (positions, powers, fading, radio outputs)
enters the compiled episode as *arguments*, never as baked-in constants, so
mutating the graph between episodes behaves correctly.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PSpec

from repro.core.distributed import _axis_index, _global_best
from repro.mac import scheduler as mac_sched
from repro.obs.telemetry import Telemetry, tti_telemetry
from repro.sim import deploy, faults as sim_faults, mobility, radio


class EpisodeState(NamedTuple):
    """The full mutable state of a MAC episode, as an explicit pytree.

    Every field is a per-simulation array (no Python state), so the whole
    tuple can ride a ``lax.scan`` carry, be ``jax.vmap``ed over a batch
    axis (N parallel episodes), checkpointed, or handed to an external RL
    loop.  Constructed by ``CRRM.init_episode_state``; advanced by the pure
    ``step``/``rollout`` functions of :func:`make_episode_fns`.

    The two trailing leaves exist only under a birth-death churn process
    (``make_episode_fns(..., churn=ChurnConfig(...))`` -- DESIGN.md
    §Digital-twin-serving) and default to ``None`` otherwise, so legacy
    states keep their treedef (and every positional 10-argument
    construction site stays valid): ``active`` is the capacity-padded
    live-UE mask; ``fad`` the *carried* fading factor, needed because
    newborn UEs redraw their fading rows in-scan (``radio.churn_keys``)
    -- with churn off (or per-TTI fading on) fading stays in
    :class:`EpisodeStatic` exactly as before.  Seed both leaves with
    :func:`seed_churn_state`.

    ``cell_state`` exists only under the in-scan cell fault process
    (``make_episode_fns(..., faults=FaultConfig(...))`` -- DESIGN.md
    §Fault-injection-and-self-healing) and defaults to ``None``
    otherwise, same trace-time-treedef discipline.  It auto-seeds to
    all-UP at the jit boundary (``step``/``rollout`` attach it when the
    engine needs it), so legacy callers never touch it; seed a custom
    initial fault pattern with :func:`seed_fault_state`.
    """

    U: Any           # (n_ues, 3) positions
    backlog: Any     # (n_ues,) queued bits (inf = full buffer)
    pf_avg: Any      # (n_ues,) PF EWMA average delivered rate, bits/s
    rr_cursor: Any   # i32 scalar: round-robin rotation state
    key: Any         # PRNG key; per-TTI streams fold via radio.tti_keys
    harq_bits: Any   # (n_ues,) f32 pending transport-block bits (0 = idle)
    harq_retx: Any   # (n_ues,) i32 retransmission count of the pending TB
    serving: Any     # (n_ues,) i32 serving-cell index (A3 carried state)
    ttt: Any         # (n_ues,) i32 A3 time-to-trigger counters
    t: Any           # i32 scalar: TTI index (drives PRNG folds + traffic)
    active: Any = None   # (n_ues,) bool live-UE mask | None (no churn)
    fad: Any = None      # carried fading factor | None (no churn)
    cell_state: Any = None   # (n_cells,) i32 fault codes | None (no faults)


class EpisodeStatic(NamedTuple):
    """Per-episode radio inputs: everything the step reads but never writes.

    The cached single-shot radio chain (``se``/``cqi``/``a`` -- used
    verbatim in the fully-static regime) plus the graph roots the dynamic
    regimes recompute from.  Read off the graph by ``CRRM.episode_static()``
    or rebuilt purely (per topology draw) by ``CrrmEnv.reset`` via
    ``radio.radio_forward``.
    """

    se: Any          # (n_ues, n_freq) spectral efficiency
    cqi: Any         # (n_ues, n_freq)
    a: Any           # (n_ues,) i32 attachment
    C: Any           # (n_cells, 3) cell positions
    P: Any           # (n_cells, n_freq) tx power
    bore: Any        # (n_cells,) sector boresights
    fad: Any         # (n_ues, n_cells[, n_freq]) fading factor


class EpisodeFns(NamedTuple):
    """The pure episode API for one engine configuration (jit-compiled).

    ``step(static, state, action=None) -> (state, tput)`` advances one TTI;
    ``rollout(static, state, n_tti, action=None) -> (state, tput)`` scans
    ``n_tti`` TTIs (``tput`` stacked to (n_tti, n_ues)).  ``action`` is an
    optional (n_cells, n_freq) power matrix overriding ``static.P`` (a
    trace-time switch: None compiles the legacy program).  Both functions
    are pure and vmap over ``state``/``action`` for batched episodes
    (single-device configurations; a mesh-sharded bundle spans the devices
    instead of vmapping).

    Built with ``telemetry=True`` both functions return one extra value --
    a :class:`repro.obs.telemetry.Telemetry` of per-TTI KPIs (stacked to
    (n_tti, ...) by ``rollout``): ``step -> (state, tput, telem)``,
    ``rollout -> (state, tput, telem)``.  Telemetry rides the scan as an
    *output*, never a carry, and is computed purely from intermediates the
    step already produced, so the trajectory is bit-identical either way.

    ``rollout_donated`` is the same rollout compiled with the *state*
    buffers donated (``jit(..., donate_argnums=)``): at million-UE scale
    the :class:`EpisodeState` carry is gigabytes, and donation lets XLA
    reuse the input buffers for the output state instead of holding both
    alive across the scan.  Same program, same jit cache discipline (the
    CompileCounter no-retrace gate covers it); the one behavioural
    difference is that the passed ``state`` is consumed -- callers that
    re-time the same state across reps (the benches' default) must keep
    using ``rollout``, and chained callers thread the returned state:
    ``state, tput = fns.rollout_donated(static, state, n)``.
    """

    step: Any
    rollout: Any
    rollout_donated: Any = None


def harq_fail_prob(bler, comb_gain_db, retx):
    """Conditional failure probability of HARQ attempt number ``retx``.

    ``retx`` prior (failed) copies are soft-combined, boosting effective
    SINR by ``comb_gain_db`` dB each; in the Rayleigh outage regime
    P(fail) ~ theta/SNR, so the conditional BLER divides by the linear gain
    per retransmission: ``bler / 10^(retx * gain_db / 10)``.  Monotone
    non-increasing in ``retx`` (tested in tests/test_mac_engine.py).
    """
    gain = 10.0 ** (comb_gain_db / 10.0)
    return jnp.clip(bler * gain ** (-retx.astype(jnp.float32)), 0.0, 1.0)


def a3_handover(a, ttt, rsrp_wb, hyst_db, ttt_tti):
    """One TTI of the A3 trigger: (serving, time-to-trigger) -> updated.

    Event A3 enters when the best neighbour's wideband RSRP exceeds the
    serving cell's by ``hyst_db``; the counter must stay entered for
    ``ttt_tti`` consecutive TTIs before the UE hands over to that
    neighbour.  Leaving the condition resets the counter (3GPP 38.331
    semantics, collapsed to one measurement per TTI).
    """
    serving = jnp.take_along_axis(rsrp_wb, a[:, None], axis=1)[:, 0]
    best = jnp.argmax(rsrp_wb, axis=1).astype(a.dtype)
    best_val = rsrp_wb.max(axis=1)
    hyst = 10.0 ** (hyst_db / 10.0)
    entered = (best_val > serving * hyst) & (best != a)
    ttt = jnp.where(entered, ttt + 1, 0)
    fire = ttt >= ttt_tti
    a = jnp.where(fire, best, a)
    ttt = jnp.where(fire, 0, ttt)
    return a, ttt


def stationary_served_tput(params, n_cells: int, se, cqi, a, backlog,
                           ue_axis=None):
    """Pure twin of the graph's Schedule -> ServedThroughput chain.

    The single-shot served throughput at the stationary alpha-fair point
    -- what ``CRRM.init_episode_state`` seeds the PF EWMA with by querying
    the graph.  This function computes the same numbers from explicit
    arrays, so a topology-resampling env ``reset`` can seed the PF state
    inside jit/vmap without a graph (tested identical in
    tests/test_radio_fns.py), and a mesh-built ``CRRM`` inside
    ``shard_map`` (``ue_axis`` names the UE mesh axes: the per-cell
    reductions cross shards).
    """
    p = params
    active = (backlog[:, None] > 0.0) & (se > 0.0)
    log_w = mac_sched.pf_log_weights_stationary(se, p.fairness_p)
    alloc = mac_sched.allocate(p.scheduler_policy, active, cqi, a, n_cells,
                               p.rb_per_chunk, jnp.int32(0), log_w, ue_axis)
    bits = mac_sched.served_bits(alloc, se, backlog,
                                 p.subband_bandwidth_Hz / p.n_rb, p.tti_s)
    return (bits / p.tti_s).sum(axis=1)


#: trace-time record of the incremental chain's dirty-row budget: one
#: ``(shards, rows_per_shard)`` entry per traced program that patches
#: mover rows (``rows_per_shard`` is the length of the padded index
#: vector each shard recomputes and scatters every TTI)
_ROW_BUDGETS: list = []


def row_budgets() -> list:
    """The ``(shards, rows_per_shard)`` entries traced so far in this
    process, oldest first (read the entries a trace added)."""
    return list(_ROW_BUDGETS)


def scatter_born(dst, idx, fresh, n_born):
    """Scatter per-newborn *fresh* rows at the padded born-index vector.

    Unlike the idempotent-recompute scatters of the dirtiness convention,
    these write NEW values, so the row-0 padding of ``radio.dirty_indices``
    would corrupt row 0 whenever it is not itself a newborn.  Every padded
    slot is therefore re-aimed at ``idx[0]`` and writes exactly what slot 0
    writes there (``fresh[0]`` when any birth happened; the row's current
    value when none) -- all duplicate writes are identical, so the scatter
    is deterministic, and a zero-birth TTI is a bitwise no-op.
    """
    k = idx.shape[0]
    sel = jnp.arange(k, dtype=jnp.int32) < n_born
    idx = jnp.where(sel, idx, idx[0])
    base = jnp.where(n_born > 0, fresh[0], dst[idx[0]])
    write = jnp.where(sel.reshape((k,) + (1,) * (fresh.ndim - 1)),
                      fresh, base)
    return dst.at[idx].set(write)


def seed_churn_state(state, static, params, *, per_tti_fading: bool = False,
                     active=None) -> EpisodeState:
    """Attach the churn leaves to a legacy :class:`EpisodeState`.

    ``active`` seeds the live-UE mask (default: every capacity slot live;
    the birth-death process then relaxes toward its M/M/inf stationary
    occupancy).  The carried-fading leaf is seeded from ``static.fad``
    exactly when the engine will carry it (Rayleigh on, per-TTI fading
    off) -- the same trace-time rule ``make_episode_fns`` applies, so the
    treedefs agree.
    """
    n = state.U.shape[0]
    if active is None:
        active = jnp.ones((n,), bool)
    fad = (static.fad
           if params.rayleigh_fading and not per_tti_fading else None)
    return state._replace(active=active, fad=fad)


def seed_fault_state(state, n_cells: int = None,
                     cell_state=None) -> EpisodeState:
    """Attach the fault leaf to a legacy :class:`EpisodeState`.

    ``cell_state`` seeds the per-cell fault codes (``sim.faults.UP`` /
    ``SLEEP`` / ``DOWN``); default all-UP.  Only needed for a *custom*
    initial fault pattern (e.g. a test seeding a dark cell): a ``None``
    leaf auto-seeds to all-UP inside ``step``/``rollout``.
    """
    if cell_state is None:
        cell_state = sim_faults.init_cell_state(n_cells)
    return state._replace(cell_state=jnp.asarray(cell_state, jnp.int32))


def make_episode_fns(params, n_ues: int, n_cells: int,
                     radio_cfg: "radio.RadioConfig", traffic_step, *,
                     mobility_step_m=None, per_tti_fading: bool = False,
                     use_harq=None, mesh=None, ue_axis=("ue",),
                     cell_axis=None, radio_mode: str = "dense",
                     mobility_move_frac=None, inc_backend=None,
                     telemetry: bool = False, churn=None,
                     relax=None, faults=None) -> EpisodeFns:
    """Build the pure ``step``/``rollout`` functions for one configuration.

    ``params`` is a ``CRRM_parameters``; ``radio_cfg`` the hashable pure-
    radio configuration (``radio.config_from_params``) and ``traffic_step``
    the traffic model's arrival function -- both pure, so the returned
    functions are too.  ``use_harq`` forces the HARQ state machine on/off
    regardless of ``harq_bler`` (None = auto: on iff ``harq_bler > 0``);
    forcing it on at ``harq_bler=0`` is the equivalence-testing hook -- the
    machine must then reproduce the fast path bit-exactly.

    ``mesh`` runs both functions under ``shard_map`` with the UE axis of
    every per-UE array sharded over the ``ue_axis`` mesh axes (``n_ues``
    must divide evenly).  Callers pass *global* arrays exactly as in the
    single-device case; sharding is an execution detail.

    ``cell_axis`` (requires ``mesh``) additionally shards the *cell*
    dimension over the named mesh axes -- the UE×cell mesh of DESIGN.md
    §Million-UE-scaling.  ``RadioStatic``-shaped leaves (``C``/``P``/
    ``bore`` and the cell columns of ``fad``) become per-shard blocks of
    ``n_cells // m_shards`` cells; the dense interference total psums
    across cell shards, attachment and A3 run through the cross-shard
    argmax (``core.distributed._global_best`` -- lowest global index
    wins ties, exactly ``jnp.argmax``), and the serving row is an
    owning-shard gather + psum.  Per-UE leaves stay replicated along the
    cell axes, so the scheduler's per-cell reductions (global
    ``n_cells``-sized bins keyed by the global attachment) are untouched.
    Equivalence contract vs single device: attachment/serving/positions
    bitwise, float outputs to 1e-5 (the psum reorders the per-cell
    interference sum) -- the same contract the UE-only mesh carries for
    pf (tests/test_smart_update_scan.py, subprocess case).  That per-UE
    bound holds at test sizes; with thousands of UEs per cell the
    reordered sums move per-UE rates by more than 1e-5 while network
    KPIs stay within it (DESIGN.md §Million-UE-scaling).

    ``inc_backend`` routes the incremental mode's dirty-row recompute:
    ``None``/``"xla"`` is the legacy ``radio.radio_update_rows``;
    ``"pallas"`` streams the gathered dirty slab through the fused
    kernel (``radio.radio_update_rows_fused`` -- VMEM-resident
    gain/RSRP, interpret mode on CPU) and raises where the kernel
    cannot express the regime (handover tables, cell-sharded meshes,
    non-stock sector patterns); ``"auto"`` picks Pallas exactly when
    expressible and the default backend is a TPU
    (``radio.pallas_available``), else XLA.

    The trace-time feature switches (mobility / per-TTI fading / HARQ /
    handover / per-RB grid / ``radio_mode`` / ``mobility_move_frac``) are
    baked here; ``n_tti`` and the presence of an ``action`` specialise via
    the jit cache on the returned functions.

    ``radio_mode="incremental"`` carries a ``radio.RadioState`` alongside
    the MAC carry and recomputes only the *dirty* UE rows of the radio
    chain per TTI (DESIGN.md §Smart-update-in-scan): with
    ``mobility_move_frac`` set, exactly that fraction of UEs walks per TTI
    (``sim.mobility.window_movers``) and only their rows re-run
    D→G→RSRP→SINR→CQI→SE; a power ``action`` is scan-constant, so its
    cell dirt collapses into one prepare-time ``radio.radio_init`` and
    the scan body is then MAC-only.  Equivalent to ``"dense"`` within the
    sharded gate's 1e-5 (bit-exact in the non-handover regimes);
    incompatible with ``per_tti_fading`` (every row dirty every TTI --
    dense IS the smart update there).

    ``mobility_move_frac`` also applies to the dense mode (the control
    arm of the smart-update benchmark): the same window-mover draw, with
    the full chain recomputed -- so dense and incremental trajectories
    are comparable at identical dirtiness.

    ``telemetry`` is a fourth trace-time switch: True adds a per-TTI
    :class:`repro.obs.telemetry.Telemetry` scan *output* to both returned
    functions (see :class:`EpisodeFns`); False (the default) compiles the
    exact legacy program -- telemetry touches no carry slot and draws no
    PRNG, so the trajectory is bit-identical either way (gated in
    tests/test_telemetry.py).  Under a mesh every KPI is psum-reduced
    inside the shard_map body, so each shard returns global numbers.

    ``churn`` (a ``sim.mobility.ChurnConfig``) is the digital-twin
    birth-death switch (DESIGN.md §Digital-twin-serving): the UE axis
    becomes *capacity-padded* -- ``state.active`` masks the live
    population, UEs arrive (Poisson, fresh positions and fading rows
    drawn from the dedicated ``radio.churn_keys`` streams) and depart
    inside the compiled scan with no retracing.  Inactive rows are
    structurally idle: their demand is masked out of every scheduler, so
    they draw zero RBs and zero throughput, and their MAC state is zeroed
    on departure.  Geometry is then dynamic even without mobility (births
    move rows), so the radio chain recomputes per TTI (dense) or patches
    newborn rows through the carried ``radio.RadioState`` (incremental).
    Churn is single-host (``mesh`` raises) -- the twin serves unsharded.

    Both returned functions also accept ``fairness_p=None``: a traced
    scalar overriding ``params.fairness_p`` in the PF weight law -- the
    twin server's live scheduler-control knob (None compiles the baked
    constant, i.e. the legacy program).

    ``relax`` (a ``radio.RelaxConfig``) is the differentiable-CRRM switch
    (DESIGN.md §RL-and-differentiability): the recomputed radio/MAC chain
    softens its three non-differentiable points (argmax attachment, the
    CQI staircase, max_cqi winner-take-all) so ``jax.grad`` through
    ``rollout`` w.r.t. a power ``action`` is exact for the relaxed
    program.  A trace-time switch like every other axis: ``relax=None``
    compiles the bitwise legacy program (pinned in tests/test_rl.py).
    The relaxations only reach the chain that is *recomputed* per TTI,
    i.e. they are meaningful with a power ``action`` (or per-TTI fading /
    mobility); single-device dense mode only -- ``mesh``, ``churn`` and
    ``radio_mode="incremental"`` raise.

    ``faults`` (a ``sim.faults.FaultConfig``) is the in-scan cell fault
    switch (DESIGN.md §Fault-injection-and-self-healing): each cell
    walks a per-TTI Markov outage/sleep chain (its own PRNG lineage,
    ``radio.fault_keys``, so fault-free trajectories stay bitwise) and
    the per-TTI tx power is masked by the per-cell fault multiplier --
    a DOWN cell's RSRP column is an exact zero, so attachment, A3 and
    SINR route around it through the unmodified radio chain.  The
    per-cell codes ride the carry as ``EpisodeState.cell_state``
    (auto-seeded all-UP; :func:`seed_fault_state` for custom patterns).
    Composes with churn, ``vmap``, handover, both radio modes and the
    UE×cell mesh; in incremental mode fault transitions re-derive the
    per-UE outputs from the carried gain matrices
    (``radio.radio_update_cells``) under a real ``lax.cond`` -- a
    fault-free TTI pays only the transition draw.  Incompatible with
    ``relax`` (the outage mask is a hard discontinuity) and with the
    fused Pallas backend (which never materialises the carried gains).
    """
    p = params
    cfg = radio_cfg
    tti_s, beta = p.tti_s, p.pf_ewma
    n_freq, rb_chunk = p.n_freq, p.rb_per_chunk
    rb_bw = p.subband_bandwidth_Hz / p.n_rb     # physical RB bandwidth
    policy, bler = p.scheduler_policy, p.harq_bler
    harq_on = bler > 0.0 if use_harq is None else bool(use_harq)
    max_retx, comb_db = p.harq_max_retx, p.harq_comb_gain_db
    ho_on = p.ho_enabled
    hyst_db, ttt_tti = p.ho_hysteresis_db, p.ho_ttt_tti
    noise_w = p.chunk_noise_W
    attach_on_mean = p.rayleigh_fading and p.attach_ignores_fading
    static_geom = mobility_step_m is None
    if radio_mode not in ("dense", "incremental"):
        raise ValueError(f"radio_mode must be 'dense' or 'incremental'; "
                         f"got {radio_mode!r}")
    incremental = radio_mode == "incremental"
    if incremental and per_tti_fading:
        raise ValueError(
            "radio_mode='incremental' is incompatible with per_tti_fading: "
            "a per-TTI fading redraw dirties every UE row every TTI, so "
            "the dense recompute IS the minimal update")
    frac_on = (mobility_step_m is not None and mobility_move_frac is not None
               and mobility_move_frac < 1.0)
    n_move = (max(1, int(round(mobility_move_frac * n_ues))) if frac_on
              else n_ues)
    churn_on = churn is not None
    faults_on = faults is not None
    if faults_on and relax is not None:
        raise ValueError(
            "faults= is incompatible with relax=: the outage tx mask is a "
            "hard discontinuity (a dark cell's RSRP column is exactly "
            "zero), so there is no useful gradient through a fault "
            "transition; differentiate a fault-free configuration instead")
    if churn_on and mesh is not None:
        raise ValueError(
            "episode_fns(mesh=..., churn=...) is unsupported: birth-death "
            "churn is single-host because newborn UEs scatter fresh "
            "position/fading rows into the capacity-padded active mask, "
            "and that scatter does not cross shard boundaries "
            "(sim.mobility.birth_death_step draws global rows; a shard "
            "cannot write a newborn born on another shard's block -- "
            "ROADMAP 'mesh-sharded churn' tracks the cross-shard newborn "
            "scatter).  Either drop mesh= and serve the twin unsharded, "
            "or pass churn=None for a mesh-sharded fixed population.")
    if relax is not None:
        if mesh is not None:
            raise ValueError(
                "relax= (differentiable relaxations) is single-device: "
                "the soft allocator shares pf's segment reductions but "
                "has no cross-shard collectives; drop mesh= (shrink the "
                "problem) or relax=None")
        if churn_on:
            raise ValueError(
                "relax= is incompatible with churn=: the birth-death "
                "scatter writes discrete rows (no gradient path through "
                "births); differentiate a fixed population instead")
        if radio_mode == "incremental":
            raise ValueError(
                "relax= requires radio_mode='dense': the incremental "
                "path carries hard argmax attachment in its RadioState "
                "(the dirty-row patching is integer gather/scatter); "
                "pass radio_mode='dense' when differentiating")
    # the fading factor is *carried* state exactly when newborns must
    # redraw their rows into an otherwise-static fading tensor
    fad_carried = churn_on and p.rayleigh_fading and not per_tti_fading
    max_birth = churn.max_arrivals_per_tti if churn_on else 0
    nb_backlog = churn.newborn_backlog_bits if churn_on else 0.0

    def use_rs(power_act: bool) -> bool:
        """Does this specialisation run on a RadioState?  Incremental mode
        with something to update: in-scan mobility dirt, birth-death row
        churn, or a power action whose chain is initialised once at
        prepare time.  The state is *carried* only when the scan mutates
        it (mobility or churn); a static-geometry action chain is
        loop-invariant and rides the hoisted constants instead (a
        pass-through carry would defeat XLA's loop-invariant hoisting
        of the downstream MAC subexpressions -- measured 2x per TTI).
        Fault transitions mutate the state too (radio_update_cells), so
        faults always carry it."""
        return incremental and (not static_geom or power_act or churn_on
                                or faults_on)

    # -- mesh layout (None = single device, the exact legacy program) ------
    if mesh is not None:
        ue_axes = (ue_axis,) if isinstance(ue_axis, str) else tuple(ue_axis)
        n_shards = 1
        for ax in ue_axes:
            n_shards *= mesh.shape[ax]
        if n_ues % n_shards:
            raise ValueError(
                f"n_ues={n_ues} must divide evenly over the {n_shards} "
                f"shards of mesh axes {ue_axes}")
    else:
        ue_axes, n_shards = None, 1
        if cell_axis is not None:
            raise ValueError("cell_axis= requires mesh= (the cell dimension "
                             "shards over named mesh axes)")
    if cell_axis is not None:
        cell_axes = ((cell_axis,) if isinstance(cell_axis, str)
                     else tuple(cell_axis))
        m_shards = 1
        for ax in cell_axes:
            m_shards *= mesh.shape[ax]
        if n_cells % m_shards:
            raise ValueError(
                f"n_cells={n_cells} must divide evenly over the {m_shards} "
                f"shards of mesh axes {cell_axes}")
    else:
        cell_axes, m_shards = None, 1
    m_loc = n_cells // m_shards      # cells owned by one shard

    # -- incremental dirty-row backend (trace-time route) ------------------
    if inc_backend not in (None, "auto", "xla", "pallas"):
        raise ValueError(f"inc_backend must be None, 'auto', 'xla' or "
                         f"'pallas'; got {inc_backend!r}")
    inc_fused = False
    if incremental and inc_backend in ("auto", "pallas"):
        if ho_on:
            reason = ("handover regimes carry per-candidate-cell tables "
                      "(se_all) the streaming kernel never materialises")
        elif faults_on:
            reason = ("cell fault transitions re-derive per-UE outputs "
                      "from carried gain matrices (G) the streaming "
                      "kernel never materialises")
        elif cell_axes is not None:
            reason = ("the fused kernel's attachment argmax spans all "
                      "cells, but a cell-sharded shard holds only its "
                      "cell block")
        else:
            reason = radio.pallas_unsupported_reason(cfg, None)
        if inc_backend == "pallas":
            if reason is not None:
                raise ValueError(
                    f"inc_backend='pallas' cannot express this "
                    f"configuration: {reason}")
            inc_fused = True
        else:
            inc_fused = reason is None and radio.pallas_available()

    n_loc = n_ues // n_shards        # rows owned by one shard (= n_ues unsharded)

    def local_offset():
        """Global UE index of this shard's first row (0 unsharded)."""
        return 0 if ue_axes is None else _axis_index(ue_axes) * n_loc

    def local_rows(x):
        """Slice a global-UE-axis array to this shard's contiguous block.

        Per-UE randomness is always drawn at *global* shape from the
        episode's key stream and then sliced, so shard s consumes exactly
        the rows it would own on a single device -- this is what makes the
        sharded rollout match the single-device one.  Identity when
        unsharded.
        """
        if ue_axes is None:
            return x
        return jax.lax.dynamic_slice_in_dim(x, local_offset(), n_loc, axis=0)

    def unfaded_gain(U, C, bore):
        return radio.pathgains(cfg, U, C, bore)

    def local_cols(x, axis=1):
        """Slice a global-cell-axis array to this shard's cell block
        (identity without cell sharding)."""
        if cell_axes is None:
            return x
        return jax.lax.dynamic_slice_in_dim(
            x, _axis_index(cell_axes) * m_loc, m_loc, axis=axis)

    def draw_fading(key):
        """Fresh per-TTI fading (global draw, local row/col slice when
        sharded -- shard (s, c) consumes exactly the block it would own
        on a single device, which is what keeps the mesh bit-equivalent)."""
        return local_cols(local_rows(
            radio.draw_fading(cfg, key, n_ues, n_cells)))

    def faded_rsrp(G0, P, fad):
        return radio.rsrp(radio.apply_fading(G0, fad), P)

    def attach(R_like):
        """``radio.attachment`` on a (possibly cell-sharded) RSRP tensor:
        the global argmax cell index, cross-shard via ``_global_best``
        (lowest global index wins ties, exactly ``jnp.argmax``)."""
        if cell_axes is None:
            return radio.attachment(R_like)
        meas = R_like.sum(axis=2)
        _, a, _ = _global_best(meas.max(axis=1),
                               meas.argmax(axis=1).astype(jnp.int32),
                               m_loc, cell_axes)
        return a

    def cell_take_rows(X, a):
        """Serving-cell row ``X[i, a_i, ...]`` under a *global* ``a``.

        Cell-sharded: the owning shard gathers its local column, every
        other shard contributes an exact zero, and a psum re-replicates
        the row -- bitwise the single-device ``take_along_axis`` (zeros
        add exactly).  Identity-shaped gather when unsharded.
        """
        if cell_axes is None:
            sel = a.reshape((-1, 1) + (1,) * (X.ndim - 2))
            return jnp.take_along_axis(X, sel, axis=1)[:, 0]
        my = _axis_index(cell_axes)
        col = jnp.clip(a - my * m_loc, 0, m_loc - 1)
        sel = col.reshape((-1, 1) + (1,) * (X.ndim - 2))
        rows = jnp.take_along_axis(X, sel, axis=1)[:, 0]
        mine = (a >= my * m_loc) & (a < (my + 1) * m_loc)
        mask = mine.reshape((-1,) + (1,) * (X.ndim - 2))
        return jax.lax.psum(jnp.where(mask, rows, jnp.zeros_like(rows)),
                            cell_axes)

    def a3_step(a, ttt, meas_wb):
        """:func:`a3_handover` on a (possibly cell-sharded) wideband
        measurement matrix.  Serving value via owning-shard gather + psum
        (exact), best neighbour via the cross-shard argmax -- the A3
        decisions are bitwise the single-device ones."""
        if cell_axes is None:
            return a3_handover(a, ttt, meas_wb, hyst_db, ttt_tti)
        serving = cell_take_rows(meas_wb[:, :, None], a)[:, 0]
        best_val, best, _ = _global_best(
            meas_wb.max(axis=1), meas_wb.argmax(axis=1).astype(a.dtype),
            m_loc, cell_axes)
        hyst = 10.0 ** (hyst_db / 10.0)
        entered = (best_val > serving * hyst) & (best != a)
        ttt = jnp.where(entered, ttt + 1, 0)
        fire = ttt >= ttt_tti
        a = jnp.where(fire, best, a)
        ttt = jnp.where(fire, 0, ttt)
        return a, ttt

    def sinr_chain(R, a, meas=None):
        """(se, cqi, a) for serving assignment ``a``.

        With ``relax.soft_attach`` the wanted/interference split softens
        to the temperature-softmax combination over per-cell RSRP
        (``radio.soft_attach_sinr``, fed the same ``meas`` matrix the
        hard argmax ranks); the returned ``a`` stays the hard i32 index
        either way -- schedulers gather with it.  ``relax=None`` is the
        bitwise legacy chain (``se_chain_relaxed`` degenerates to
        ``se_chain``).  Cell-sharded: owning-shard wanted gather + the
        psummed interference total (1e-5-class float reorder, the
        documented mesh contract).
        """
        if relax is not None and relax.soft_attach:
            m = meas if meas is not None else R.sum(axis=-1)
            gamma = radio.soft_attach_sinr(R, m, relax.attach_tau, noise_w)
        elif cell_axes is not None:
            w = cell_take_rows(R, a)
            total = jax.lax.psum(R.sum(axis=1), cell_axes)
            gamma = radio.sinr_from_wu(w, total - w, noise_w)
        else:
            gamma, _, _ = radio.sinr(R, a, noise_w)
        se, cqi = radio.se_chain_relaxed(cfg, gamma, relax)
        return se, cqi, a

    def gather_serving(se_all, cqi_all, a):
        """(se, cqi) rows of the per-candidate-cell tables at serving
        ``a`` -- the two-gather handover read shared by the hoisted dense
        tables and the incremental RadioState (owning-shard gather + psum
        when the tables are cell-sharded)."""
        return cell_take_rows(se_all, a), cell_take_rows(cqi_all, a)

    # -- incremental (smart-update-in-scan) helpers ------------------------
    def inc_fad(static):
        """The fading tensor the incremental chain consumes: ``None`` on
        the unfaded channel (``G0 * ones == G0`` bitwise; eliding the
        ones gather/multiply is pure profit on the 100k-row hot path,
        and a mesh-built static holds no ones at all)."""
        return static.fad if p.rayleigh_fading else None

    def init_rs(static, U, action, fad=None, pmul=None):
        """Prepare-time ``radio.RadioState``: the everything-dirty base
        case, computed once outside the scan.  A power ``action`` is
        scan-constant, so this is also where its cell dirt is absorbed
        (the scan body then only patches mobility rows).  ``fad``
        overrides the static fading tensor (the churn regimes' carried
        leaf); ``pmul`` the *seeded* fault multiplier (a custom-seeded
        dark cell must be dark from TTI 0, before its first
        transition).  Fault regimes keep the gain matrices
        (``with_gain``) so a fault transition can re-derive every
        per-UE output without re-running geometry+pathloss."""
        P = static.P if action is None else action
        if pmul is not None:
            P = P * local_cols(pmul, axis=0)[:, None]
        f = fad if fad is not None else inc_fad(static)
        return radio.radio_init(cfg, U, static.C, static.bore,
                                f, P, with_tables=ho_on,
                                with_gain=faults_on, cell_axis=cell_axes)

    def walk(U, k_mob):
        """This TTI's walk of the local rows: ``(U, window rows)``.

        ``mobility_move_frac`` set: the exact-count window-mover draw
        (global draw, per-shard reconstruction), with the window's local
        rows (``radio.window_rows``, this shard's contiguous block as the
        (offset, n_loc) restriction) moved in place by position.  Unset:
        the legacy every-UE walk (window None = all rows dirty) -- the
        PR-4 stream, bit-untouched.
        """
        if frac_on:
            start, d = mobility.window_movers(k_mob, n_ues, n_move,
                                              mobility_step_m)
            win = radio.window_rows(start, n_move, n_ues,
                                    offset=local_offset(), n_loc=n_loc)
            return mobility.walk_window(U, d, win, p.extent_m), win
        d = local_rows(mobility.walk_steps(k_mob, n_ues, mobility_step_m))
        return mobility.apply_walk(U, d, p.extent_m), None

    def inc_channel(static, rs, U, P, k_mob, fad):
        """One incremental TTI of the radio chain: move, patch, read.

        Only the moved rows re-run D→G→RSRP→SINR→CQI→SE
        (``radio.radio_update_rows`` -- or its fused-kernel twin under
        ``inc_backend`` -- on the window's rows, taken and written back by
        position); everything else is a carried value that a dense
        recompute would reproduce bit-identically.  Returns the updated
        ``(U, rs)`` plus the local dirty-row count (dead code unless
        telemetry is on).
        """
        n_dirty = jnp.int32(0)
        if mobility_step_m is not None:
            with jax.named_scope("mobility"):
                U, win = walk(U, k_mob)
                if win is None:          # every row moved: the whole block
                    win = radio.window_rows(0, n_loc, n_loc)
                n_dirty = win.count
                _ROW_BUDGETS.append((n_shards, win.size))
            with jax.named_scope("radio"):
                if inc_fused:
                    rs = radio.radio_update_rows_fused(
                        cfg, rs, U, static.C, static.bore, fad, P, win)
                else:
                    rs = radio.radio_update_rows(cfg, rs, U, static.C,
                                                 static.bore, fad, P, win,
                                                 cell_axis=cell_axes)
        return U, rs, n_dirty

    def allocate(se, cqi, a, buf, avg, cursor, harq_pending, act, fair):
        demand = (buf[:, None] > 0.0) | harq_pending[:, None]
        if act is not None:
            # churn: inactive capacity slots are structurally idle -- no
            # policy ever grants them an RB, whatever their stale state
            demand = demand & act[:, None]
        active = demand & (se > 0.0)
        fp = p.fairness_p if fair is None else fair
        if relax is not None and relax.soft_sched and policy == "max_cqi":
            # winner-take-all softened to a temperature softmax over the
            # (relaxed) SE -- the third RelaxConfig gate; pf is already
            # smooth and rr is CQI-independent, so they pass through
            return mac_sched.allocate_max_cqi_soft(active, se, a, n_cells,
                                                   rb_chunk, relax.sched_tau)
        log_w = mac_sched.pf_log_weights_ewma(rb_bw * se, avg[:, None], fp)
        return mac_sched.allocate(policy, active, cqi, a, n_cells, rb_chunk,
                                  cursor, log_w, ue_axes,
                                  differentiable=relax is not None)

    def harq_step(k_harq, tb_new, hbits, hretx, granted):
        """One TTI of every UE's stop-and-wait process.

        Pending UEs retransmit their stored TB (no new buffer drain) --
        but only when the scheduler actually granted them RBs this TTI
        (``granted``); an ungranted pending TB waits, state unchanged.
        Fresh TBs enter the machine on failure and drop after
        ``max_retx`` retransmissions.  The retx TB is delivered at its
        stored size (real HARQ retransmits the same TB; the grant-size
        mismatch is absorbed by the soft-combining abstraction).

        The fifth return is the TTI's KPI tuple
        ``(acks, nacks, retx, dropped_bits)`` -- computed from the masks
        the machine already holds, so it is dead code (XLA DCE) unless
        telemetry consumes it.
        """
        pending = hbits > 0.0
        tb = jnp.where(pending, hbits, tb_new)
        attempting = granted & (tb > 0.0)
        attempt = jnp.where(pending, hretx, 0)
        p_fail = harq_fail_prob(bler, comb_db, attempt)
        u = local_rows(jax.random.uniform(k_harq, (n_ues,)))
        ok = (u >= p_fail) & attempting
        fail = ~ok & attempting
        n_fail = attempt + 1
        keep = (fail & (n_fail <= max_retx)) | (pending & ~granted)
        delivered = jnp.where(ok, tb, 0.0)
        stats = (ok.sum().astype(jnp.int32),
                 fail.sum().astype(jnp.int32),
                 (pending & attempting).sum().astype(jnp.int32),
                 jnp.where(fail & (n_fail > max_retx), tb, 0.0).sum())
        hbits = jnp.where(keep, tb, 0.0)
        hretx = jnp.where(keep, jnp.where(fail, n_fail, hretx), 0)
        return delivered, pending, hbits, hretx, stats

    def prepare(static, U, power_act: bool):
        """Hoistable constants of the static-geometry regime.

        Everything here is loop-invariant: ``rollout`` evaluates it once,
        outside the scan.  With a power ``action`` the P-dependent tables
        are skipped (the per-TTI chain recomputes from the action); only
        the unfaded gain -- pure geometry -- survives hoisting.
        """
        h = {}
        if use_rs(power_act):
            # the incremental path hoists through its RadioState instead
            return h
        if churn_on:
            # births move rows: nothing U-dependent is loop-invariant
            return h
        if static_geom and (per_tti_fading or ho_on or power_act
                            or faults_on):
            # static geometry: one unfaded gain/attachment pass, hoisted
            # out of the scan; only the fading factor varies per TTI.
            # Fault regimes hoist the gain too, but the P-dependent
            # tables cannot hoist: the fault mask changes P per TTI.
            h["G"] = unfaded_gain(U, static.C, static.bore)
            if not power_act and not faults_on:
                R_mean = radio.rsrp(h["G"], static.P)
                h["R_mean"] = R_mean
                h["a"] = attach(R_mean) if attach_on_mean else None
                R_faded = faded_rsrp(h["G"], static.P, static.fad)
                # A3 measures long-term RSRP iff association does (same
                # convention as the dynamic paths' R_meas); cell-sharded
                # it stays a local block -- a3_step gathers across shards
                h["meas_wb"] = (R_mean if attach_on_mean
                                else R_faded).sum(axis=-1)
                if ho_on:
                    # static channel + evolving serving cell: tabulate the
                    # SINR chain for EVERY candidate cell once, outside the
                    # scan -- per TTI the chain is then two gathers on
                    # (n_ue, n_freq) instead of an (n_ue, n_cell, n_freq)
                    # reduction.
                    total = R_faded.sum(axis=1)
                    if cell_axes is not None:
                        total = jax.lax.psum(total, cell_axes)
                    gamma_all = R_faded / (
                        noise_w + (total[:, None, :] - R_faded))
                    se_all, cqi_all = radio.se_chain(cfg, gamma_all)
                    h["cqi_all"], h["se_all"] = cqi_all, se_all
        return h

    def tti_step(h, static, state, action, rs=None, fair=None):
        """One pure TTI: (hoisted, static, state, action, radio-state) ->
        (state, tput, radio-state, telemetry).  ``rs`` is the incremental
        path's carried ``radio.RadioState`` (None on the dense paths,
        threaded unchanged); ``fair`` the traced fairness override (None =
        the baked constant); telemetry is None unless built with
        ``telemetry=True``."""
        power_act = action is not None
        U, buf, avg = state.U, state.backlog, state.pf_avg
        cursor, key = state.rr_cursor, state.key
        hbits, hretx, a_srv, ttt, t = (state.harq_bits, state.harq_retx,
                                       state.serving, state.ttt, state.t)
        prev_srv = a_srv
        P = action if power_act else static.P
        k_mob, k_fad, k_tr, k_harq = radio.tti_keys(key, t)
        n_dirty = jnp.int32(0) if incremental else None
        # -- birth-death churn: departures idle out, newborns take free
        # slots with fresh positions and fading rows (radio.churn_keys --
        # a separate stream lineage, so churn-off trajectories are
        # bit-untouched) ---------------------------------------------------
        act, fad_c, born = state.active, state.fad, None
        n_born = jnp.int32(0)
        if churn_on:
            with jax.named_scope("churn"):
                k_birth, k_death, k_pos, k_fadc = radio.churn_keys(key, t)
                act, born, n_born = mobility.birth_death_step(
                    k_birth, k_death, act, tti_s, churn)
                # departed rows idle out; reborn slots then reset fresh (a
                # slot can depart and be re-occupied within one TTI)
                buf = jnp.where(act, buf, 0.0)
                avg = jnp.where(act, avg, 0.0)
                hbits = jnp.where(act, hbits, 0.0)
                hretx = jnp.where(act, hretx, 0)
                ttt = jnp.where(act, ttt, 0)
                buf = jnp.where(born, nb_backlog, buf)
                avg = jnp.where(born, 0.0, avg)
                hbits = jnp.where(born, 0.0, hbits)
                hretx = jnp.where(born, 0, hretx)
                ttt = jnp.where(born, 0, ttt)
                born_idx = radio.dirty_indices(born, max_birth)
                U = scatter_born(
                    U, born_idx,
                    deploy.ppp_points(k_pos, max_birth, p.extent_m,
                                      z=p.h_ut_m),
                    n_born)
                if fad_carried:
                    fad_c = scatter_born(
                        fad_c, born_idx,
                        radio.draw_fading(cfg, k_fadc, max_birth, n_cells),
                        n_born)
        # -- cell faults: one Markov transition per TTI (radio.fault_keys
        # -- its own stream lineage, so fault-free trajectories are
        # bit-untouched), then the per-cell tx mask.  The draw is global
        # and replicated (every shard folds the same key), so cell_state
        # agrees across a mesh; only the P columns are local.
        cs, changed = state.cell_state, None
        if faults_on:
            with jax.named_scope("faults"):
                cs, changed = sim_faults.fault_step(
                    radio.fault_keys(key, t), cs, tti_s, faults)
                pmul = sim_faults.tx_multiplier(cs, faults)
                P = P * local_cols(pmul, axis=0)[:, None]
        # -- channel: incremental state (carried or hoisted), per-TTI
        # recompute, or the hoisted dense constants -------------------------
        r = rs if rs is not None else h.get("rs")
        if r is not None:
            f_inc = fad_c if fad_carried else inc_fad(static)
            if rs is not None:              # carried: mobility dirties rows
                U, r, n_dirty = inc_channel(static, r, U, P, k_mob, f_inc)
                with jax.named_scope("radio"):
                    if churn_on:
                        # patch the newborn rows (idempotent row
                        # recompute, so the row-0 padding of
                        # dirty_indices is safe here)
                        r = radio.radio_update_rows(cfg, r, U, static.C,
                                                    static.bore, f_inc, P,
                                                    born_idx)
                        n_dirty = n_dirty + n_born
                    if faults_on:
                        # a fault transition re-prices every UE against
                        # the masked P from the carried gains -- no
                        # geometry, no pathloss.  Single device: a real
                        # lax.cond, so a fault-free TTI pays only the
                        # transition draw (the predicate is a replicated
                        # scalar; under vmap the cond lowers to a
                        # select).  Mesh: call branch-free
                        # (radio_update_cells where-selects internally)
                        # -- collectives inside a cond branch are avoided.
                        def cell_upd(s):
                            return radio.radio_update_cells(
                                cfg, s, P, changed, cell_axis=cell_axes)
                        if mesh is None:
                            r = jax.lax.cond(jnp.any(changed), cell_upd,
                                             lambda s: s, r)
                        else:
                            r = cell_upd(r)
                rs = r
            if ho_on:
                with jax.named_scope("attach"):
                    if churn_on:
                        # newborns attach instantaneously to their best
                        # cell
                        a_srv = jnp.where(
                            born,
                            jnp.argmax(r.meas, axis=1).astype(a_srv.dtype),
                            a_srv)
                    a_srv, ttt = a3_step(a_srv, ttt, r.meas)
                a_use = a_srv
                with jax.named_scope("link"):
                    se, cqi = gather_serving(r.se_all, r.cqi_all, a_use)
            else:
                se, cqi, a_use = r.se, r.cqi, r.a
        elif mobility_step_m is not None or churn_on:
            # random-walk displacement, clamped at the region border
            # (global draw, local slice when sharded); with churn alone
            # the geometry still changes per TTI (births move rows), so
            # the full chain recomputes from the current U
            if mobility_step_m is not None:
                with jax.named_scope("mobility"):
                    U, _ = walk(U, k_mob)
            with jax.named_scope("radio"):
                G0 = unfaded_gain(U, static.C, static.bore)
                fad = (draw_fading(k_fad) if per_tti_fading
                       else (fad_c if fad_carried else static.fad))
                R = faded_rsrp(G0, P, fad)
                R_meas = radio.rsrp(G0, P) if attach_on_mean else R
            with jax.named_scope("attach"):
                a_inst = attach(R_meas)
        elif per_tti_fading or power_act or faults_on:
            with jax.named_scope("radio"):
                fad = draw_fading(k_fad) if per_tti_fading else static.fad
                R = faded_rsrp(h["G"], P, fad)
            if power_act or faults_on:
                # the fault mask (like a power action) changes P per
                # TTI, so measurement and attachment recompute from the
                # hoisted gain
                with jax.named_scope("radio"):
                    R_meas = radio.rsrp(h["G"], P) if attach_on_mean else R
                with jax.named_scope("attach"):
                    a_inst = attach(R_meas)
            else:
                R_meas = h["R_mean"] if attach_on_mean else R
                with jax.named_scope("attach"):
                    a_inst = h["a"] if attach_on_mean else attach(R)
        else:
            R = R_meas = a_inst = None   # fully static radio chain

        # -- serving cell: A3 carried state, or instantaneous argmax ------
        # (the incremental branch above already resolved se/cqi/a_use)
        if r is None:
            if ho_on:
                with jax.named_scope("attach"):
                    meas_wb = (R_meas.sum(axis=-1) if R_meas is not None
                               else h["meas_wb"])
                    if churn_on:
                        a_srv = jnp.where(
                            born,
                            jnp.argmax(meas_wb, axis=1).astype(a_srv.dtype),
                            a_srv)
                    a_srv, ttt = a3_step(a_srv, ttt, meas_wb)
                a_use = a_srv
                with jax.named_scope("link"):
                    if R is not None:
                        se, cqi, _ = sinr_chain(R, a_use, meas=meas_wb)
                    else:
                        # static channel, evolving attachment: gather from
                        # the hoisted all-cells SINR-chain tables
                        se, cqi = gather_serving(h["se_all"], h["cqi_all"],
                                                 a_use)
            elif R is not None:
                with jax.named_scope("link"):
                    se, cqi, a_use = sinr_chain(R, a_inst,
                                                meas=R_meas.sum(axis=-1))
            else:
                se, cqi, a_use = static.se, static.cqi, static.a
        if faults_on and not ho_on:
            # track the instantaneous attachment in the serving leaf so
            # outage-driven reattachment is observable (telemetry's
            # reattach_events) and survives chunk boundaries
            a_srv = a_use

        # -- MAC: traffic -> grant -> HARQ -> drain ------------------------
        with jax.named_scope("traffic"):
            arrivals = local_rows(traffic_step(k_tr, t))
            if churn_on:
                arrivals = jnp.where(act, arrivals, 0.0)
            buf = buf + arrivals
        with jax.named_scope("harq"):
            harq_pending = (hbits > 0.0) if harq_on else \
                jnp.zeros_like(buf, dtype=bool)
        with jax.named_scope("sched"):
            alloc = allocate(se, cqi, a_use, buf, avg, cursor, harq_pending,
                             act, fair)
            drainable = jnp.where(harq_pending, 0.0, buf)
            tb_new = mac_sched.served_bits(
                alloc, se, drainable, rb_bw, tti_s,
                floor=1e-6 if relax is not None else 1e-30).sum(1)
        hstats = None
        with jax.named_scope("harq"):
            if harq_on:
                bits, _, hbits, hretx, hstats = harq_step(
                    k_harq, tb_new, hbits, hretx, alloc.sum(axis=1) > 0.0)
            elif bler > 0.0:   # HARQ-lite: lost blocks stay queued -> retx
                bits = tb_new * local_rows(jax.random.bernoulli(
                    k_harq, 1.0 - bler, (n_ues,))).astype(tb_new.dtype)
            else:
                bits = tb_new
        with jax.named_scope("sched"):
            # clamp: served_bits <= backlog only up to float rounding
            if harq_on:
                buf = jnp.maximum(buf - tb_new, 0.0)  # drain on first tx
            else:
                buf = jnp.maximum(buf - bits, 0.0)
            tput = bits / tti_s
            avg = (1.0 - beta) * avg + beta * tput
        state = EpisodeState(U, buf, avg, cursor + rb_chunk, key,
                             hbits, hretx, a_srv, ttt, t + 1,
                             active=act, fad=fad_c, cell_state=cs)
        telem = None
        if telemetry:
            with jax.named_scope("telemetry"):
                # KPIs only from values computed above: no PRNG, no carry.
                if hstats is None:
                    acks = (bits > 0.0).sum().astype(jnp.int32)
                    nacks = (((tb_new > 0.0) & (bits == 0.0)).sum()
                             .astype(jnp.int32) if bler > 0.0
                             else jnp.int32(0))
                    hstats = (acks, nacks, jnp.int32(0), jnp.float32(0.0))
                ho_fired = ((a_srv != prev_srv).sum().astype(jnp.int32)
                            if ho_on else jnp.int32(0))
                n_act = act.sum().astype(jnp.int32) if churn_on else None
                # cells_down is computed from the *replicated* cell_state --
                # identical on every shard, so tti_telemetry must not psum
                # it; reattach_events is a per-UE count (psums over ue_axes)
                n_down = ((cs == sim_faults.DOWN).sum().astype(jnp.int32)
                          if faults_on else None)
                reatt = ((a_srv != prev_srv).sum().astype(jnp.int32)
                         if faults_on else None)
                telem = tti_telemetry(n_cells, n_ues, a_use, alloc, bits, tput,
                                      buf, hstats, ho_fired, n_dirty, ue_axes,
                                      n_act, cells_down=n_down,
                                      reattached=reatt)
        return state, tput, rs, telem

    def setup(static, state, action):
        """(hoisted constants, carried RadioState) for one specialisation.

        The incremental modes split on loop-variance: a mobility (or
        churn) episode's RadioState mutates per TTI (scan carry ``rs0``);
        a static-geometry action chain is computed once and *closed over*
        (``h["rs"]``) so XLA hoists every downstream loop-invariant
        subexpression exactly as it does for the dense hoisted tables.
        """
        with jax.named_scope("call_setup"):
            h = prepare(static, state.U, action is not None)
            rs0 = None
            if use_rs(action is not None):
                if static_geom and not churn_on and not faults_on:
                    h["rs"] = init_rs(static, state.U, action)
                else:
                    pmul0 = (sim_faults.tx_multiplier(state.cell_state,
                                                      faults)
                             if faults_on else None)
                    rs0 = init_rs(static, state.U, action,
                                  fad=state.fad if fad_carried else None,
                                  pmul=pmul0)
        return h, rs0

    def norm_state(state):
        """Auto-seed the fault leaf at the jit boundary: a fault-enabled
        engine fed a legacy state (``cell_state=None``) starts all-UP --
        trace-time, so legacy treedefs keep compiling the legacy program
        and callers never thread the leaf by hand."""
        if faults_on and state.cell_state is None:
            return state._replace(
                cell_state=sim_faults.init_cell_state(n_cells))
        return state

    # ------------------------------------------------------- single device
    if mesh is None:
        def step(static, state, action=None, fairness_p=None):
            state = norm_state(state)
            h, rs0 = setup(static, state, action)
            state, tput, _, telem = tti_step(h, static, state, action, rs0,
                                             fairness_p)
            return (state, tput, telem) if telemetry else (state, tput)

        def rollout(static, state, n_tti, action=None, fairness_p=None):
            state = norm_state(state)
            h, rs0 = setup(static, state, action)

            def body(carry, _):
                s, rs = carry
                s, tput, rs, telem = tti_step(h, static, s, action, rs,
                                              fairness_p)
                return (s, rs), ((tput, telem) if telemetry else tput)

            (state, _), ys = jax.lax.scan(body, (state, rs0), None,
                                          length=n_tti)
            if telemetry:
                tput, telem = ys
                return state, tput, telem
            return state, ys

        return EpisodeFns(
            step=jax.jit(step),
            rollout=jax.jit(rollout, static_argnums=(2,)),
            rollout_donated=jax.jit(rollout, static_argnums=(2,),
                                    donate_argnums=(1,)))

    # ------------------------------------------------------- mesh sharded
    # pytree-structured PartitionSpecs: UE axes shard every per-UE leaf;
    # cell axes (when named) shard the RadioStatic-shaped leaves, else the
    # cells are replicated (cell_axes=None leaves the specs verbatim)
    ue = PSpec(ue_axes)
    mesh_axes = ue_axes if cell_axes is None else ue_axes + cell_axes
    fad_spec = (PSpec(ue_axes, cell_axes, None)
                if p.rayleigh_fading and p.n_rb_subbands > 1
                else PSpec(ue_axes, cell_axes))
    static_specs = EpisodeStatic(
        se=PSpec(ue_axes, None), cqi=PSpec(ue_axes, None), a=ue,
        C=PSpec(cell_axes, None), P=PSpec(cell_axes, None),
        bore=PSpec(cell_axes), fad=fad_spec)

    def specs_of(static):
        """``static_specs`` for this static: a mesh-built (unfaded) static
        carries ``fad=None``, and shard_map matches treedefs exactly."""
        if static.fad is None:
            return static_specs._replace(fad=None)
        return static_specs
    state_specs = EpisodeState(
        U=PSpec(ue_axes, None), backlog=ue, pf_avg=ue, rr_cursor=PSpec(),
        key=PSpec(None), harq_bits=ue, harq_retx=ue, serving=ue, ttt=ue,
        t=PSpec(),
        # the fault codes are replicated (every shard draws the identical
        # transition from the replicated key); None leaves stay None --
        # shard_map matches treedefs exactly
        cell_state=PSpec(None) if faults_on else None)
    # telemetry leaves leave the shard_map fully replicated: every KPI is
    # psum-reduced inside tti_telemetry, so each shard holds the global
    # value.  The None leaf (dirty_rows outside incremental mode) must be
    # None in the spec tree too -- shard_map matches treedefs exactly.
    telem_specs = Telemetry(
        served_bits=PSpec(None), granted_rb=PSpec(None),
        harq_acks=PSpec(), harq_nacks=PSpec(), harq_retx=PSpec(),
        dropped_bits=PSpec(), ho_events=PSpec(), buffer_bits=PSpec(),
        jain=PSpec(), dirty_rows=PSpec() if incremental else None,
        cells_down=PSpec() if faults_on else None,
        reattach_events=PSpec() if faults_on else None)
    # stacked (n_tti, ...) variant for the rollout's scan output
    telem_stack_specs = Telemetry(
        served_bits=PSpec(None, None), granted_rb=PSpec(None, None),
        harq_acks=PSpec(None), harq_nacks=PSpec(None),
        harq_retx=PSpec(None), dropped_bits=PSpec(None),
        ho_events=PSpec(None), buffer_bits=PSpec(None),
        jain=PSpec(None), dirty_rows=PSpec(None) if incremental else None,
        cells_down=PSpec(None) if faults_on else None,
        reattach_events=PSpec(None) if faults_on else None)

    def revar(state):
        """Re-establish the claimed replication of the scalar carry slots.

        The scan carry is typed device-varying as a whole
        (``lax.pcast(..., to="varying")``), but the scalar slots (cursor,
        key, t) evolve identically on every shard; a ``pmax`` both proves
        and restores their replication so they can leave the shard_map
        under a replicated out-spec.
        """
        fix = lambda x: jax.lax.pmax(x, mesh_axes)
        out = state._replace(rr_cursor=fix(state.rr_cursor),
                             key=fix(state.key), t=fix(state.t))
        if faults_on:   # identical on every shard, same as the scalars
            out = out._replace(cell_state=fix(out.cell_state))
        return out

    def sharded(fn, in_specs, out_specs):
        # replication checking must be off: the traffic models' poisson
        # sampler carries a while_loop, for which jax's rep-checker has no
        # rule.  The kwarg spelling differs across jax versions.
        for kw in ({"check_rep": False}, {"check_vma": False}, {}):
            try:
                return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                  out_specs=out_specs, **kw)
            except TypeError:       # pragma: no cover - version dependent
                continue

    def extra_layout(action, fairness_p):
        """(specs, args) for the optional trailing shard_map inputs: the
        power action (replicated (n_cells, n_freq)) then the fairness
        scalar (replicated) -- each present iff passed, so the disabled
        combination compiles the exact legacy program."""
        specs, args = (), ()
        if action is not None:
            specs, args = specs + (PSpec(None, None),), args + (action,)
        if fairness_p is not None:
            specs, args = specs + (PSpec(),), args + (fairness_p,)
        return specs, args

    def split_extra(has_act, extra):
        act = extra[0] if has_act else None
        fp = extra[-1] if len(extra) > int(has_act) else None
        return act, fp

    def step(static, state, action=None, fairness_p=None):
        has_act = action is not None

        def one(static, state, *extra):
            act, fp = split_extra(has_act, extra)
            state = jax.tree_util.tree_map(
                lambda x: jax.lax.pcast(x, mesh_axes, to="varying"), state)
            h, rs0 = setup(static, state, act)
            state, tput, _, telem = tti_step(h, static, state, act, rs0, fp)
            if telemetry:
                return revar(state), tput, telem
            return revar(state), tput

        extra_specs, extra_args = extra_layout(action, fairness_p)
        out_specs = ((state_specs, ue, telem_specs) if telemetry
                     else (state_specs, ue))
        f = sharded(one, (specs_of(static), state_specs) + extra_specs,
                    out_specs)
        return f(static, norm_state(state), *extra_args)

    def rollout(static, state, n_tti, action=None, fairness_p=None):
        has_act = action is not None

        def roll(static, state, *extra):
            act, fp = split_extra(has_act, extra)
            init = jax.tree_util.tree_map(
                lambda x: jax.lax.pcast(x, mesh_axes, to="varying"), state)
            h, rs0 = setup(static, init, act)

            def body(carry, _):
                s, rs = carry
                s, tput, rs, telem = tti_step(h, static, s, act, rs, fp)
                return (s, rs), ((tput, telem) if telemetry else tput)

            (state, _), ys = jax.lax.scan(body, (init, rs0), None,
                                          length=n_tti)
            if telemetry:
                tput, telem = ys
                return revar(state), tput, telem
            return revar(state), ys

        extra_specs, extra_args = extra_layout(action, fairness_p)
        out_specs = ((state_specs, PSpec(None, ue_axes), telem_stack_specs)
                     if telemetry else (state_specs, PSpec(None, ue_axes)))
        f = sharded(roll, (specs_of(static), state_specs) + extra_specs,
                    out_specs)
        return f(static, norm_state(state), *extra_args)

    return EpisodeFns(
        step=jax.jit(step),
        rollout=jax.jit(rollout, static_argnums=(2,)),
        rollout_donated=jax.jit(rollout, static_argnums=(2,),
                                donate_argnums=(1,)))


def episode_fns_for(sim, *, mobility_step_m=None, per_tti_fading=False,
                    use_harq=None, mesh=None, ue_axis=("ue",),
                    cell_axis=None, radio_mode=None,
                    mobility_move_frac=None, inc_backend=None,
                    telemetry: bool = False, churn=None,
                    relax=None, faults=None) -> EpisodeFns:
    """The :func:`make_episode_fns` bundle for ``sim``, cached on it.

    Keyed by the trace-time switches only -- ``n_tti`` and the presence of
    a power action specialise through the jit cache of the returned
    functions, so repeat episodes of any length reuse one ``EpisodeFns``.
    ``mobility_step_m=None`` falls back to the simulator's
    ``params.mobility_step_m`` (scenario presets with a baked-in mobility
    trajectory); pass ``0`` to force the static-geometry program.
    ``radio_mode``/``mobility_move_frac``/``faults`` fall back to the
    corresponding ``CRRM_parameters`` fields the same way (``faults=0``
    forces the fault-free program on a faulted preset).
    """
    if mobility_step_m is None:
        mobility_step_m = getattr(sim.params, "mobility_step_m", None)
    if not mobility_step_m:          # 0 / None -> static geometry
        mobility_step_m = None
    if radio_mode is None:
        radio_mode = getattr(sim.params, "radio_mode", "dense")
    if mobility_move_frac is None:
        mobility_move_frac = getattr(sim.params, "mobility_move_frac", None)
    if faults is None:
        faults = getattr(sim.params, "faults", None)
    if not faults:                   # 0 / False -> fault-free program
        faults = None
    ue_axis = (ue_axis,) if isinstance(ue_axis, str) else tuple(ue_axis)
    if isinstance(cell_axis, str):
        cell_axis = (cell_axis,)
    elif cell_axis is not None:
        cell_axis = tuple(cell_axis)
    cache_key = (mobility_step_m, per_tti_fading, use_harq, mesh, ue_axis,
                 cell_axis, radio_mode, mobility_move_frac, inc_backend,
                 telemetry, churn, relax, faults)
    cache = sim.__dict__.setdefault("_episode_fns_cache", {})
    if cache_key not in cache:
        cache[cache_key] = make_episode_fns(
            sim.params, sim.n_ues, sim.n_cells, sim.radio_config(),
            sim._traffic_step, mobility_step_m=mobility_step_m,
            per_tti_fading=per_tti_fading, use_harq=use_harq,
            mesh=mesh, ue_axis=ue_axis, cell_axis=cell_axis,
            radio_mode=radio_mode, mobility_move_frac=mobility_move_frac,
            inc_backend=inc_backend, telemetry=telemetry,
            churn=churn, relax=relax, faults=faults)
    return cache[cache_key]


def run_episode(sim, n_tti: int, key=None, mobility_step_m=None,
                per_tti_fading: bool = False, sync_state: bool = True,
                use_harq=None, mesh=None, radio_mode=None,
                mobility_move_frac=None, telemetry: bool = False,
                churn=None, faults=None):
    """Run ``n_tti`` TTIs; returns (n_tti, n_ues) delivered throughput
    (bits/s) -- or ``(tput, telem)`` with ``telemetry=True``, where
    ``telem`` is the stacked per-TTI :class:`repro.obs.telemetry.Telemetry`
    (``repro.obs.summarize`` reduces it to a KPI dict).

    A thin wrapper over the functional API: ``sim.init_episode_state(key)``
    -> ``rollout`` -> ``sim.sync_episode_state``.  The PF average-rate
    state is seeded from the single-shot graph's served throughput (the
    stationary alpha-fair point), so a full-buffer PF episode starts --
    and, with a static channel, stays -- at the legacy ``ThroughputNode``
    fixed point.  ``sync_state`` (legacy; functional callers thread
    :class:`EpisodeState` instead) writes the final buffers / PF state /
    positions / HARQ processes / serving cells back into the graph so
    subsequent single-shot queries and episodes continue from the episode's
    end state.  ``mesh`` runs the rollout shard_mapped over the UE axis.
    """
    fns = episode_fns_for(sim, mobility_step_m=mobility_step_m,
                          per_tti_fading=per_tti_fading, use_harq=use_harq,
                          mesh=mesh, radio_mode=radio_mode,
                          mobility_move_frac=mobility_move_frac,
                          telemetry=telemetry, churn=churn, faults=faults)
    state = sim.init_episode_state(key)
    static = sim.episode_static()
    if churn is not None:
        state = seed_churn_state(state, static, sim.params,
                                 per_tti_fading=per_tti_fading)
    telem = None
    if telemetry:
        state, tput, telem = fns.rollout(static, state, n_tti)
    else:
        state, tput = fns.rollout(static, state, n_tti)
    if mobility_step_m is None:
        mobility_step_m = getattr(sim.params, "mobility_step_m", None)
    if sync_state:
        sim.sync_episode_state(state, positions=bool(mobility_step_m))
    return (tput, telem) if telemetry else tput
