"""ISSUE 4: the pure-functional radio chain (sim.radio), graph-vs-
radio_forward bit-exactness, the unified fading/key conventions, the
mesh-sharded episode engine, and topology-batched env resets."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.crrm import CRRM
from repro.core.params import CRRM_parameters
from repro.mac import engine as mac_engine
from repro.sim import fading as fading_mod
from repro.sim import radio, scenarios


def _shrink(name, **kw):
    """Scenario shrunk for CI; keeps the preset's sectoring/fading knobs."""
    base = dict(n_ues=20, n_cells=6)
    base.update(kw)
    return scenarios.make_scenario(name, **base)


# ----------------------------------------------- graph == radio_forward
@pytest.mark.parametrize("name", scenarios.scenario_names())
@pytest.mark.parametrize("n_rb_subbands", [1, 4])
def test_radio_forward_bitexact_with_graph(name, n_rb_subbands):
    """The tentpole acceptance: one pure radio_forward call reproduces
    every graph-node query BIT-exactly, for every registered scenario at
    wideband and per-RB fading resolution.  (Both paths dispatch the
    shared radio.*_jit executables, so this is equality by construction,
    not tolerance.)"""
    sim = CRRM(_shrink(name, n_rb_subbands=n_rb_subbands))
    out = radio.radio_forward(sim.radio_static(), sim.U._data,
                              fad=sim.fading._data)
    for got, want in [(out.G, sim.get_pathgains()),
                      (out.rsrp, sim.get_RSRP()),
                      (out.a, sim.get_attachment()),
                      (out.gamma, sim.get_SINR()),
                      (out.cqi, sim.get_CQI()),
                      (out.mcs, sim.get_MCS()),
                      (out.se, sim.get_spectral_efficiency())]:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_radio_forward_wideband_reporting_matches_graph():
    """The cqi_report knob flows through RadioConfig identically."""
    kw = dict(n_ues=16, n_cells=3, seed=5, pathloss_model_name="UMa",
              power_W=10.0, rayleigh_fading=True, n_rb_subbands=4,
              coherence_rb=1, cqi_report="wideband")
    sim = CRRM(CRRM_parameters(**kw))
    out = radio.radio_forward(sim.radio_static(), sim.U._data,
                              fad=sim.fading._data)
    np.testing.assert_array_equal(np.asarray(out.cqi),
                                  np.asarray(sim.get_CQI()))
    np.testing.assert_array_equal(np.asarray(out.se),
                                  np.asarray(sim.get_spectral_efficiency()))


def test_radio_forward_power_override_and_jit_vmap():
    """P= overrides the static power matrix; the call jits and vmaps."""
    sim = CRRM(_shrink("dense_urban"))
    rs = sim.radio_static()
    half = rs.P * 0.5
    out = radio.radio_forward(rs, sim.U._data, fad=sim.fading._data, P=half)
    sim.set_power_matrix(half)
    np.testing.assert_allclose(np.asarray(out.se),
                               np.asarray(sim.get_spectral_efficiency()))
    # vmap over a batch of position fields = batched topologies
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    Us = jax.vmap(lambda k: jnp.concatenate(
        [jax.random.uniform(k, (sim.n_ues, 2), maxval=1000.0),
         jnp.full((sim.n_ues, 1), 1.5)], axis=1))(keys)
    batched = jax.jit(jax.vmap(lambda U: radio.radio_forward(rs, U)))(Us)
    assert batched.se.shape == (3, sim.n_ues, rs.P.shape[1])
    assert np.isfinite(np.asarray(batched.se)).all()


def test_radio_static_is_a_pytree_with_static_config():
    sim = CRRM(_shrink("indoor_hotspot"))
    rs = sim.radio_static()
    leaves, treedef = jax.tree_util.tree_flatten(rs)
    assert len(leaves) == 3                       # C, P, bore
    rs2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert rs2.cfg == rs.cfg                      # config rides the treedef


# -------------------------------------------- fading / key conventions
def test_resample_fading_uses_the_one_documented_draw():
    """CRRM.resample_fading == radio.draw_fading == the legacy stream
    (seeded benches must not move)."""
    for kw, legacy in [
        (dict(rayleigh_fading=True),
         lambda k, s: fading_mod.rayleigh_power(k, (s.n_ues, s.n_cells))),
        (dict(rayleigh_fading=True, n_rb_subbands=4, coherence_rb=3),
         lambda k, s: fading_mod.subband_rayleigh_power(
             k, s.n_ues, s.n_cells,
             s.params.n_subbands * s.params.n_rb, s.params.coherence_rb,
             s.params.n_freq)),
    ]:
        sim = CRRM(CRRM_parameters(n_ues=8, n_cells=3, seed=1,
                                   pathloss_model_name="UMa", **kw))
        key = jax.random.PRNGKey(9)
        sim.resample_fading(key)
        want = legacy(key, sim)
        np.testing.assert_array_equal(np.asarray(sim.fading._data),
                                      np.asarray(want))
        got = radio.draw_fading(sim.radio_config(), key, sim.n_ues,
                                sim.n_cells)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_tti_and_episode_key_conventions_are_pinned():
    """The documented key-splitting convention must never drift: seeded
    episodes (and the committed BENCH records) depend on these streams."""
    key = jax.random.PRNGKey(3)
    for t in (0, 7):
        got = radio.tti_keys(key, t)
        want = [jax.random.fold_in(key, 4 * t + i) for i in range(4)]
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    np.testing.assert_array_equal(
        np.asarray(radio.episode_key(5)),
        np.asarray(jax.random.fold_in(jax.random.PRNGKey(5), 0x6d6163)))
    k1, k2, k3 = radio.reset_keys(key)
    np.testing.assert_array_equal(
        np.stack([np.asarray(k1), np.asarray(k2), np.asarray(k3)]),
        np.asarray(jax.random.split(key, 3)))


def test_stationary_served_tput_matches_graph_seed():
    """The pure PF-seed twin == what init_episode_state reads off the
    graph (what topology-resampling resets rely on)."""
    for name in ("dense_urban", "rural_macro", "indoor_hotspot"):
        sim = CRRM(_shrink(name))
        pure = mac_engine.stationary_served_tput(
            sim.params, sim.n_cells, sim.get_spectral_efficiency(),
            sim.get_CQI(), sim.get_attachment(), sim.get_backlog())
        np.testing.assert_allclose(np.asarray(pure),
                                   np.asarray(sim.get_served_throughputs()),
                                   rtol=1e-6)


# -------------------------------------------------- mesh-sharded engine
def test_mesh_episode_on_trivial_mesh_matches_plain_rollout():
    """The shard_map code path (collectives and all) on a 1-device mesh
    must reproduce the plain rollout -- in-process coverage of the mesh
    branches; the real 2-device equivalence runs in a subprocess below."""
    mesh = jax.make_mesh((1,), ("ue",))
    for kw in (dict(scheduler_policy="rr", harq_bler=0.3),
               dict(scheduler_policy="max_cqi", rayleigh_fading=True,
                    n_rb_subbands=4),
               dict(scheduler_policy="pf", fairness_p=0.5, ho_enabled=True,
                    mobility_step_m=20.0)):
        base = dict(n_ues=16, n_cells=3, seed=3, pathloss_model_name="UMa",
                    power_W=10.0, traffic_model="poisson",
                    traffic_params=dict(arrival_rate_hz=300.0,
                                        packet_size_bits=12_000.0))
        base.update(kw)
        a, b = CRRM(CRRM_parameters(**base)), CRRM(CRRM_parameters(**base))
        key = jax.random.PRNGKey(0)
        f1, f2 = a.episode_fns(), b.episode_fns(mesh=mesh)
        s1, t1 = f1.rollout(a.episode_static(), a.init_episode_state(key),
                            20)
        s2, t2 = f2.rollout(b.episode_static(), b.init_episode_state(key),
                            20)
        np.testing.assert_allclose(np.asarray(t1), np.asarray(t2),
                                   rtol=1e-5, atol=1e-2)
        _, o1 = f1.step(a.episode_static(), a.init_episode_state(key))
        _, o2 = f2.step(b.episode_static(), b.init_episode_state(key))
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                                   rtol=1e-5, atol=1e-2)


_SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import sys
sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np
from repro.core.crrm import CRRM
from repro.core.params import CRRM_parameters

mesh = jax.make_mesh((2,), ("ue",))
po = dict(traffic_model="poisson",
          traffic_params=dict(arrival_rate_hz=300.0,
                              packet_size_bits=12_000.0))

# (desc, bitwise?, per_tti_fading, param overrides).  rr / max_cqi are
# integer-exact across shards -> bitwise; pf's cross-shard psum reorders a
# float sum -> 1e-5 on the non-chaotic full-buffer regime (see engine
# docstring).
CASES = [
    ("rr_poisson_harq", True, False,
     dict(scheduler_policy="rr", harq_bler=0.3, **po)),
    ("max_cqi_selective", True, True,
     dict(scheduler_policy="max_cqi", rayleigh_fading=True,
          n_rb_subbands=4)),
    ("ho_mobility_rr", True, False,
     dict(scheduler_policy="rr", ho_enabled=True, rayleigh_fading=True,
          mobility_step_m=20.0, **po)),
    ("pf_full_buffer_fading", False, True,
     dict(scheduler_policy="pf", fairness_p=0.5, rayleigh_fading=True)),
]
for desc, bitwise, ptf, kw in CASES:
    base = dict(n_ues=64, n_cells=7, seed=3, pathloss_model_name="UMa",
                power_W=10.0)
    base.update(kw)
    a, b = CRRM(CRRM_parameters(**base)), CRRM(CRRM_parameters(**base))
    key = jax.random.PRNGKey(0)
    f1 = a.episode_fns(per_tti_fading=ptf)
    f2 = b.episode_fns(per_tti_fading=ptf, mesh=mesh)
    s1, t1 = f1.rollout(a.episode_static(), a.init_episode_state(key), 50)
    s2, t2 = f2.rollout(b.episode_static(), b.init_episode_state(key), 50)
    t1, t2 = np.asarray(t1), np.asarray(t2)
    if bitwise:
        np.testing.assert_array_equal(t1, t2, err_msg=desc)
    else:
        np.testing.assert_allclose(t2, t1, rtol=1e-5, atol=1e-2,
                                   err_msg=desc)
    for l1, l2 in zip(jax.tree_util.tree_leaves(s1),
                      jax.tree_util.tree_leaves(s2)):
        np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                                   rtol=1e-5, atol=1e-3, err_msg=desc)
    print("OK", desc)

# an indivisible UE count must be rejected up front
sim = CRRM(CRRM_parameters(n_ues=9, n_cells=3, pathloss_model_name="UMa"))
try:
    sim.episode_fns(mesh=mesh)
except ValueError as e:
    assert "divide evenly" in str(e)
    print("OK divisibility")
else:
    raise AssertionError("indivisible n_ues accepted")
print("ALL_OK")
"""


@pytest.mark.slow
def test_sharded_episode_matches_single_device_two_device_mesh():
    """ISSUE-4 acceptance: shard_mapped episodes on a 2-device host mesh
    match the single-device rollout (bitwise for rr/max_cqi, 1e-5 for
    pf).  XLA device count must be forced before jax initialises, so this
    runs in a fresh subprocess."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT],
                         capture_output=True, text=True, timeout=900,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))), env=env)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    assert "ALL_OK" in out.stdout


# ------------------------------------------- scenario mobility preset
def test_dense_urban_mobile_bakes_in_mobility():
    """The preset carries its trajectory: run_episode moves every UE
    without an explicit mobility_step_m argument."""
    p = scenarios.make_scenario("dense_urban_mobile", n_ues=12, n_cells=3,
                                n_sectors=1)
    assert p.mobility_step_m == 5.0 and p.ho_enabled
    sim = CRRM(p)
    U0 = np.asarray(sim.U._data).copy()
    tput = np.asarray(sim.run_episode(n_tti=10))
    assert np.isfinite(tput).all()
    U1 = np.asarray(sim.U._data)                  # synced back (moved)
    assert (np.abs(U1[:, :2] - U0[:, :2]) > 0).any()
    assert np.abs(U1[:, :2] - U0[:, :2]).max() <= 10 * 5.0 + 1e-4
    # an explicit 0 forces the static-geometry program back on
    sim2 = CRRM(scenarios.make_scenario("dense_urban_mobile", n_ues=12,
                                        n_cells=3, n_sectors=1))
    U2 = np.asarray(sim2.U._data).copy()
    sim2.run_episode(n_tti=5, mobility_step_m=0)
    np.testing.assert_array_equal(np.asarray(sim2.U._data), U2)
