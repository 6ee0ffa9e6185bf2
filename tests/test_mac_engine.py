"""Engine-level invariants: HARQ state machine, A3 handover, per-RB link
adaptation, determinism, and wideband-equivalence regressions (ISSUE 2)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.crrm import CRRM
from repro.core.params import CRRM_parameters
from repro.mac import engine as mac_engine
from repro.sim import scenarios
from repro.sim.radio import RelaxConfig

import engine_regimes


def _sim(**kw):
    base = dict(n_ues=30, n_cells=4, seed=7, pathloss_model_name="UMa",
                power_W=10.0)
    base.update(kw)
    return CRRM(CRRM_parameters(**base))


# ------------------------------------------------------------------- HARQ
def test_harq_fail_prob_monotone_in_retx():
    """Soft combining: conditional BLER non-increasing, delivery monotone."""
    retx = jnp.arange(8)
    p = np.asarray(mac_engine.harq_fail_prob(0.6, 3.0, retx))
    assert (np.diff(p) < 0).all(), p           # strictly better per combine
    assert ((0 <= p) & (p <= 1)).all()
    # zero combining gain: plain stop-and-wait, constant conditional BLER
    p0 = np.asarray(mac_engine.harq_fail_prob(0.6, 0.0, retx))
    np.testing.assert_allclose(p0, 0.6, rtol=1e-6)


def test_harq_soft_combining_raises_delivered_throughput():
    """More combining gain -> fewer residual losses -> more delivered bits."""
    t_lo = np.asarray(_sim(harq_bler=0.5, harq_comb_gain_db=0.0,
                           harq_max_retx=3).run_episode(300)).mean()
    t_hi = np.asarray(_sim(harq_bler=0.5, harq_comb_gain_db=6.0,
                           harq_max_retx=3).run_episode(300)).mean()
    assert t_hi > t_lo * 1.05, (t_lo, t_hi)


def test_harq_served_bits_never_exceed_offered_traffic():
    """Delivered bits <= offered bits, with every loss path engaged."""
    sim = _sim(traffic_model="poisson", harq_bler=0.4, harq_max_retx=2,
               traffic_params=dict(arrival_rate_hz=0.0))
    offered = np.full(30, 5e4, np.float32)
    sim.set_backlog(offered)
    tput = np.asarray(sim.run_episode(n_tti=300))
    delivered = tput.sum(axis=0) * sim.params.tti_s
    # in-flight/dropped TBs make delivery strictly partial, never excess
    assert (delivered <= offered + 1.0).all()
    assert delivered.sum() > 0.0


def test_harq_retx_count_never_exceeds_max():
    """Walk the machine TTI by TTI; the carried retx state stays bounded."""
    for max_retx in (0, 1, 3):
        sim = _sim(n_ues=20, harq_bler=0.7, harq_max_retx=max_retx, seed=3)
        key = jax.random.PRNGKey(0)
        for t in range(40):
            sim.run_episode(n_tti=1, key=jax.random.fold_in(key, t))
            retx = np.asarray(sim._harq_retx)
            assert ((0 <= retx) & (retx <= max_retx)).all(), (max_retx, retx)
            if max_retx == 0:      # no retx allowed: nothing ever pending
                assert (np.asarray(sim._harq_bits) == 0).all()


def _harq_sim(name):
    """A poisson-traffic base network, or a registry scenario shrunk to
    24 UEs x 6 cells -- both at bler=0."""
    if name == "base":
        return _sim(traffic_model="poisson", seed=5)
    return CRRM(scenarios.make_scenario(name, n_ues=24, n_cells=6,
                                        harq_bler=0.0))


@pytest.mark.parametrize("radio_mode", ["dense", "incremental"])
@pytest.mark.parametrize("name", ("base",) + scenarios.scenario_names())
def test_harq_bler_zero_machine_is_bitexact_with_fast_path(name, radio_mode):
    """The HARQ machine at bler=0 must reproduce the HARQ-free (PR-1)
    episode bit-exactly -- same grants, same drains, same PRNG streams --
    on every registry scenario and in both radio modes."""
    key = jax.random.PRNGKey(42)
    a, b = _harq_sim(name), _harq_sim(name)
    t_fast = np.asarray(a.run_episode(n_tti=100, key=key, use_harq=False,
                                      radio_mode=radio_mode))
    t_machine = np.asarray(b.run_episode(n_tti=100, key=key, use_harq=True,
                                         radio_mode=radio_mode))
    np.testing.assert_array_equal(t_fast, t_machine)
    np.testing.assert_array_equal(np.asarray(a.get_backlog()),
                                  np.asarray(b.get_backlog()))


def test_harq_ungranted_retx_waits_without_delivering():
    """A pending TB needs RBs to retransmit: under max_cqi winner-take-all
    the losing UE's process must stall -- no zero-resource delivery, no
    retx-count advance."""
    ue = np.array([[50.0, 0.0, 1.5], [400.0, 0.0, 1.5]], np.float32)
    cell = np.array([[0.0, 0.0, 25.0]], np.float32)
    sim = CRRM(CRRM_parameters(
        n_ues=2, ue_positions=ue, cell_positions=cell,
        pathloss_model_name="UMa", power_W=10.0, scheduler_policy="max_cqi",
        harq_bler=0.5, harq_max_retx=3))
    # UE 1 (far, lower CQI) has a pending TB; UE 0 wins the whole grid
    sim._harq_bits = jnp.asarray([0.0, 1234.0], jnp.float32)
    sim._harq_retx = jnp.asarray([0, 2], jnp.int32)
    tput = np.asarray(sim.run_episode(n_tti=1))
    assert tput[0, 1] == 0.0                       # no bits without a grant
    assert float(np.asarray(sim._harq_bits)[1]) == 1234.0   # still pending
    assert int(np.asarray(sim._harq_retx)[1]) == 2          # no attempt


def test_power_mutators_respect_rb_subband_grid():
    """set_power_matrix / set_cell_power keep the documented per-subband
    semantics when the grid is split into CQI subbands."""
    kw = dict(n_ues=12, n_cells=3, n_subbands=2, n_rb=12, n_rb_subbands=4,
              seed=4, pathloss_model_name="UMa")
    sim = CRRM(CRRM_parameters(power_W=10.0, **kw))
    pw = np.full((3, 2), 7.0, np.float32)
    sim.set_power_matrix(pw)                       # documented shape
    ref = CRRM(CRRM_parameters(power_matrix=pw, **kw))
    np.testing.assert_allclose(np.asarray(sim.P._data),
                               np.asarray(ref.P._data))
    np.testing.assert_allclose(np.asarray(sim.get_UE_throughputs()),
                               np.asarray(ref.get_UE_throughputs()),
                               rtol=1e-6)
    sim.set_cell_power(1, 1, 3.0)                  # subband index, not chunk
    P = np.asarray(sim.P._data)
    np.testing.assert_allclose(P[1, 4:], 3.0 / 4)  # subband 1 -> chunks 4..7
    np.testing.assert_allclose(P[1, :4], 7.0 / 4)   # subband 0 untouched
    with pytest.raises(ValueError, match="power matrix"):
        sim.set_power_matrix(np.ones((3, 3), np.float32))


def test_harq_legacy_lite_path_still_selectable():
    """use_harq=False keeps PR-1's Bernoulli HARQ-lite thinning."""
    sim = _sim(n_ues=40, harq_bler=0.5, seed=9)
    ref = _sim(n_ues=40, harq_bler=0.0, seed=9)
    t = float(np.asarray(sim.run_episode(400, use_harq=False)).mean())
    t0 = float(np.asarray(ref.run_episode(400)).mean())
    assert 0.35 < t / t0 < 0.65


def test_harq_recovers_throughput_vs_no_retx():
    """Retransmissions recover most of what Bernoulli dropping loses."""
    kw = dict(n_ues=40, seed=9, harq_bler=0.6, harq_comb_gain_db=6.0)
    t_machine = float(np.asarray(
        _sim(harq_max_retx=3, **kw).run_episode(400)).mean())
    t_drop = float(np.asarray(
        _sim(harq_max_retx=0, **kw).run_episode(400)).mean())
    assert t_machine > t_drop * 1.2, (t_machine, t_drop)


# -------------------------------------------------------------- handover
def test_a3_handover_hysteresis_and_ttt():
    """Unit semantics: margin gates entry, TTT gates firing, reset works."""
    a = jnp.zeros(1, jnp.int32)
    ttt = jnp.zeros(1, jnp.int32)
    weak = jnp.asarray([[1.0, 1.5]])     # +1.8 dB < 3 dB hysteresis
    strong = jnp.asarray([[1.0, 2.5]])   # +4 dB  > 3 dB hysteresis

    a1, t1 = mac_engine.a3_handover(a, ttt, weak, 3.0, 2)
    assert int(a1[0]) == 0 and int(t1[0]) == 0   # below margin: no entry

    a1, t1 = mac_engine.a3_handover(a, ttt, strong, 3.0, 2)
    assert int(a1[0]) == 0 and int(t1[0]) == 1   # entered, not yet fired
    a2, t2 = mac_engine.a3_handover(a1, t1, strong, 3.0, 2)
    assert int(a2[0]) == 1 and int(t2[0]) == 0   # fired after TTT TTIs
    # condition lapses mid-TTT: counter resets
    a3, t3 = mac_engine.a3_handover(a1, t1, weak, 3.0, 2)
    assert int(a3[0]) == 0 and int(t3[0]) == 0


def test_handover_fires_in_scan_and_respects_hysteresis():
    """A UE parked next to cell B but serving from cell A hands over inside
    the episode iff the A3 margin clears the hysteresis."""
    ue = np.array([[900.0, 0.0, 1.5]], np.float32)       # close to cell B
    cells = np.array([[0.0, 0.0, 25.0], [1000.0, 0.0, 25.0]], np.float32)

    def run(hyst_db):
        sim = CRRM(CRRM_parameters(
            n_ues=1, ue_positions=ue, cell_positions=cells,
            pathloss_model_name="UMa", power_W=10.0, ho_enabled=True,
            ho_hysteresis_db=hyst_db, ho_ttt_tti=3))
        sim._ho_serving = jnp.zeros(1, jnp.int32)        # pin serving to A
        sim.run_episode(n_tti=10)
        return int(np.asarray(sim._ho_serving)[0])

    assert run(3.0) == 1        # B is ~20+ dB stronger: hands over
    assert run(80.0) == 0       # absurd hysteresis: never triggers


def test_handover_ttt_delays_the_switch():
    ue = np.array([[900.0, 0.0, 1.5]], np.float32)
    cells = np.array([[0.0, 0.0, 25.0], [1000.0, 0.0, 25.0]], np.float32)
    sim = CRRM(CRRM_parameters(
        n_ues=1, ue_positions=ue, cell_positions=cells,
        pathloss_model_name="UMa", power_W=10.0, ho_enabled=True,
        ho_hysteresis_db=3.0, ho_ttt_tti=6))
    sim._ho_serving = jnp.zeros(1, jnp.int32)
    sim.run_episode(n_tti=5)                  # < TTT: must not have fired
    assert int(np.asarray(sim._ho_serving)[0]) == 0
    sim.run_episode(n_tti=5)                  # TTT satisfied across episodes
    assert int(np.asarray(sim._ho_serving)[0]) == 1


def test_handover_off_keeps_legacy_attachment():
    """ho_enabled=False episodes never deviate from the PR-1 engine."""
    key = jax.random.PRNGKey(3)
    a = _sim(seed=2)
    b = _sim(seed=2, ho_enabled=True, ho_hysteresis_db=0.0, ho_ttt_tti=1)
    t_off = np.asarray(a.run_episode(50, key=key))
    t_on = np.asarray(b.run_episode(50, key=key))
    # static channel, serving already the argmax: HO never fires, and the
    # HO-enabled program must converge on the same fixed point
    np.testing.assert_allclose(t_on, t_off, rtol=1e-5)


# ------------------------------------------- determinism and equivalence
def test_run_episode_is_bitwise_reproducible():
    key = jax.random.PRNGKey(123)
    kw = dict(n_ues=25, n_cells=4, seed=1, traffic_model="poisson",
              rayleigh_fading=True, harq_bler=0.3, ho_enabled=True,
              n_rb_subbands=4, pathloss_model_name="UMa", power_W=10.0)
    t1 = np.asarray(CRRM(CRRM_parameters(**kw)).run_episode(
        60, key=key, per_tti_fading=True, sync_state=False))
    t2 = np.asarray(CRRM(CRRM_parameters(**kw)).run_episode(
        60, key=key, per_tti_fading=True, sync_state=False))
    np.testing.assert_array_equal(t1, t2)


def test_per_rb_flat_channel_matches_wideband():
    """n_rb_subbands > 1 on a flat channel == the wideband engine (1e-5):
    the per-RB machinery must cost resolution only, not change physics.
    Full-buffer traffic keeps every UE active, so the comparison is free of
    the chaotic active-mask flips that 1-ulp backlog residues cause."""
    key = jax.random.PRNGKey(7)
    kw = dict(n_ues=30, n_cells=4, seed=7, scheduler_policy="pf",
              fairness_p=0.5, pathloss_model_name="UMa", power_W=10.0)
    wb = CRRM(CRRM_parameters(n_rb_subbands=1, **kw))
    rb = CRRM(CRRM_parameters(n_rb_subbands=4, **kw))
    t_wb = np.asarray(wb.run_episode(80, key=key))
    t_rb = np.asarray(rb.run_episode(80, key=key))
    np.testing.assert_allclose(t_rb, t_wb, rtol=1e-5, atol=1e-2)


def test_wideband_special_case_reproduces_legacy_fixed_point():
    """n_rb_subbands=1 + harq_bler=0 + handover off: the tentpole's
    acceptance gate -- the engine still lands on the PR-1 (legacy
    ThroughputNode) full-buffer PF fixed point."""
    sim = _sim(n_ues=50, n_cells=7, n_rb_subbands=1, harq_bler=0.0)
    legacy = np.asarray(sim.get_UE_throughputs())
    tput = np.asarray(sim.run_episode(n_tti=50))
    np.testing.assert_allclose(tput[-1], legacy, rtol=1e-5, atol=1e-2)


def test_per_rb_max_cqi_exploits_frequency_selectivity():
    """The point of per-RB CQI: on a frequency-selective channel the
    opportunistic scheduler rides each chunk's fading peak, while a
    channel-blind equal split averages over the fades."""
    kw = dict(n_ues=20, n_cells=3, seed=5, rayleigh_fading=True,
              n_rb_subbands=12, coherence_rb=1,
              pathloss_model_name="UMa", power_W=10.0)
    key = jax.random.PRNGKey(11)
    mx = CRRM(CRRM_parameters(scheduler_policy="max_cqi", **kw))
    rr = CRRM(CRRM_parameters(scheduler_policy="rr", **kw))
    t_mx = np.asarray(mx.run_episode(150, key=key, per_tti_fading=True))
    t_rr = np.asarray(rr.run_episode(150, key=key, per_tti_fading=True))
    assert t_mx.mean() > t_rr.mean() * 1.2, (t_rr.mean(), t_mx.mean())


def test_per_rb_episode_is_one_compiled_scan():
    sim = _sim(n_ues=20, n_rb_subbands=4, rayleigh_fading=True,
               harq_bler=0.2, ho_enabled=True)
    sim.get_served_throughputs()
    before = sim.update_counts()
    sim.run_episode(n_tti=50, per_tti_fading=True)
    after = sim.update_counts()
    assert after == before, "episode leaked per-TTI graph updates"


def test_everything_on_episode_is_finite_and_syncs_state():
    """Mobility + per-TTI selective fading + HARQ + handover + per-RB in
    one scan: finite output, bounded HARQ state, serving cells valid."""
    sim = _sim(n_ues=25, n_cells=7, n_rb_subbands=6, coherence_rb=2,
               rayleigh_fading=True, harq_bler=0.3, ho_enabled=True,
               traffic_model="poisson", seed=1)
    tput = np.asarray(sim.run_episode(n_tti=40, mobility_step_m=50.0,
                                      per_tti_fading=True))
    assert tput.shape == (40, 25) and np.isfinite(tput).all()
    assert (tput >= 0).all()
    serving = np.asarray(sim._ho_serving)
    assert ((0 <= serving) & (serving < sim.n_cells)).all()
    retx = np.asarray(sim._harq_retx)
    assert ((0 <= retx) & (retx <= sim.params.harq_max_retx)).all()


def test_add_traffic_accumulates_duplicate_indices():
    """Duplicate UE indices in one add_traffic call must sum, not last-win."""
    sim = _sim(n_ues=10, traffic_model="poisson")
    sim.set_backlog(np.zeros(10, np.float32))
    sim.add_traffic([4, 4, 7], [100.0, 200.0, 50.0])
    backlog = np.asarray(sim.get_backlog())
    assert backlog[4] == 300.0 and backlog[7] == 50.0
    assert backlog.sum() == 350.0


def test_unknown_policy_rejected():
    with pytest.raises(ValueError, match="unknown scheduler policy"):
        from repro.mac import scheduler as mac_sched
        mac_sched.allocate("bogus", jnp.ones((2, 1), bool),
                           jnp.ones((2, 1), jnp.int32),
                           jnp.zeros(2, jnp.int32), 1, 4, 0,
                           jnp.zeros((2, 1)))


_INC = dict(radio_mode="incremental")
_RELAX = RelaxConfig()

#: (id, CRRM_parameters overrides, episode_fns switches, the reason the
#: error must state); ``mesh`` names the axes of a one-device mesh
_REJECTIONS = [
    ("bad_radio_mode", {}, dict(radio_mode="fancy"),
     r"radio_mode must be 'dense' or 'incremental'; got 'fancy'"),
    ("incremental_per_tti_fading", dict(rayleigh_fading=True),
     dict(per_tti_fading=True, **_INC),
     r"per-TTI fading redraw dirties every UE row"),
    ("faults_relax", {}, dict(faults=engine_regimes.FAULTS, relax=_RELAX),
     r"outage tx mask is a hard discontinuity"),
    ("relax_mesh", {}, dict(relax=_RELAX, mesh=("ue",)),
     r"relax= \(differentiable relaxations\) is single-device"),
    ("relax_churn", {}, dict(relax=_RELAX, churn=engine_regimes.CHURN),
     r"no gradient path through births"),
    ("relax_incremental", {}, dict(relax=_RELAX, **_INC),
     r"relax= requires radio_mode='dense'"),
    ("churn_mesh", {}, dict(churn=engine_regimes.CHURN, mesh=("ue",)),
     r"birth-death churn is single-host"),
    ("cell_axis_without_mesh", {}, dict(cell_axis=("cell",)),
     r"cell_axis= requires mesh="),
    ("bad_inc_backend", {}, dict(inc_backend="cuda", **_INC),
     r"inc_backend must be None, 'auto', 'xla' or 'pallas'; got 'cuda'"),
    ("pallas_handover", dict(ho_enabled=True),
     dict(inc_backend="pallas", **_INC),
     r"cannot express this configuration: handover regimes"),
    ("pallas_faults", {},
     dict(inc_backend="pallas", faults=engine_regimes.FAULTS, **_INC),
     r"cannot express this configuration: cell fault transitions"),
    ("pallas_cell_axis", {},
     dict(inc_backend="pallas", mesh=("ue", "cell"), cell_axis=("cell",),
          **_INC),
     r"cannot express this configuration: the fused kernel's attachment"),
]


@pytest.mark.parametrize("params_kw,fns_kw,reason",
                         [pytest.param(*c[1:], id=c[0]) for c in _REJECTIONS])
def test_episode_fns_rejects_incompatible_switches(params_kw, fns_kw,
                                                   reason):
    """Every switch combination make_episode_fns documents as unsupported
    raises a ValueError that says why, before anything is traced."""
    fns_kw = dict(fns_kw)
    if "mesh" in fns_kw:
        axes = fns_kw["mesh"]
        fns_kw["mesh"] = jax.sharding.Mesh(
            np.asarray(jax.devices()[:1]).reshape((1,) * len(axes)), axes)
    with pytest.raises(ValueError, match=reason):
        _sim(**params_kw).episode_fns(**fns_kw)


def test_graph_sees_per_rb_spectral_efficiency():
    """Graph blocks resolve SE/CQI/alloc on the (n_ue, n_freq) grid and the
    RB budget is conserved at chunk granularity."""
    sim = _sim(n_ues=24, n_cells=3, n_rb=12, n_rb_subbands=4,
               coherence_rb=3, rayleigh_fading=True)
    se = np.asarray(sim.get_spectral_efficiency())
    assert se.shape == (24, 4)
    # frequency selectivity is visible: chunks differ for some UE
    assert (se.std(axis=1) > 0).any()
    alloc = np.asarray(sim.get_schedule())
    a = np.asarray(sim.get_attachment())
    for j in range(sim.n_cells):
        got = alloc[a == j].sum(axis=0)
        assert (got <= sim.params.rb_per_chunk + 1e-3).all()
