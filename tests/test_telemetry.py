"""ISSUE 6 tentpole: in-scan KPI telemetry.

The load-bearing claim is *structural no-op when off*: building the
episode functions with ``telemetry=True`` must reproduce the
``telemetry=False`` trajectory bit-exactly -- across every registry
scenario, under ``vmap`` and on a 2-device mesh -- while returning the
per-TTI KPI stack.  Plus the KPI semantics themselves (dirty-row counts,
HARQ/handover/fairness bounds) and the retrace/compile counter.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.crrm import CRRM
from repro.core.params import CRRM_parameters
from repro.obs import CompileCounter, RetraceWatch, Telemetry, summarize
from repro.sim import scenarios

import engine_regimes


def _shrink(name, **kw):
    base = dict(n_ues=24, n_cells=6)
    base.update(kw)
    return scenarios.make_scenario(name, **base)


def _pair(params):
    return CRRM(params), CRRM(params)


# ------------------------------------------ on == off, bitwise, everywhere
#: the engine switches that put a scenario into each regime ("dense" is
#: the scenario's own preset)
REGIMES = {"dense": {},
           "incremental": dict(radio_mode="incremental",
                               **engine_regimes.MOVERS),
           **engine_regimes.CHURN_FAULTS}


@pytest.mark.parametrize("name,regime",
                         engine_regimes.cases(REGIMES, "dense"))
def test_telemetry_is_structural_noop_across_scenarios(name, regime):
    """Acceptance: telemetry=True reproduces the telemetry=False
    trajectory AND final state bit-exactly on every registry scenario,
    in every switch regime, with the per-TTI KPI stack returned."""
    a, b = _pair(_shrink(name))
    key = jax.random.PRNGKey(0)
    kw = REGIMES[regime]
    f_off, f_on = a.episode_fns(**kw), b.episode_fns(telemetry=True, **kw)
    s0a = engine_regimes.start(a, key, kw)
    s0b = engine_regimes.start(b, key, kw)
    s1, t1 = f_off.rollout(a.episode_static(), s0a, 15)
    s2, t2, telem = f_on.rollout(b.episode_static(), s0b, 15)
    np.testing.assert_array_equal(np.asarray(t2), np.asarray(t1))
    assert (jax.tree_util.tree_structure(s1)
            == jax.tree_util.tree_structure(s2))
    for l1, l2 in zip(jax.tree_util.tree_leaves(s1),
                      jax.tree_util.tree_leaves(s2)):
        np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
    assert isinstance(telem, Telemetry)
    assert telem.jain.shape == (15,)
    assert telem.served_bits.shape == (15, b.n_cells)
    assert telem.granted_rb.shape == (15, b.n_cells)
    # the regime's processes really ran: a UE was born, a cell went dark
    if "churn" in kw:
        assert (np.diff(np.asarray(telem.active_ues)) > 0).any()
    if "faults" in kw:
        assert np.asarray(telem.cells_down).any()


def test_telemetry_kpis_are_consistent_with_trajectory():
    """Per-cell served bits must sum to the delivered throughput, buffer
    occupancy must equal the finite backlog, Jain stays in (0, 1]."""
    sim = CRRM(_shrink("dense_urban"))
    key = jax.random.PRNGKey(3)
    fns = sim.episode_fns(telemetry=True)
    state, tput, telem = fns.rollout(sim.episode_static(),
                                     sim.init_episode_state(key), 20)
    tti_s = sim.params.tti_s
    np.testing.assert_allclose(
        np.asarray(telem.served_bits).sum(axis=1),
        np.asarray(tput).sum(axis=1) * tti_s, rtol=1e-5)
    backlog = np.asarray(state.backlog)
    occupancy = np.where(np.isfinite(backlog), backlog, 0.0).sum()
    np.testing.assert_allclose(np.asarray(telem.buffer_bits)[-1],
                               occupancy, rtol=1e-6)
    jain = np.asarray(telem.jain)
    assert ((jain >= 0.0) & (jain <= 1.0 + 1e-6)).all()
    # poisson traffic at these shapes delivers something every TTI
    assert (np.asarray(telem.harq_acks) >= 0).all()
    kpis = summarize(telem, tti_s=tti_s)
    assert kpis["served_mbits"] > 0.0
    assert 0.0 <= kpis["mean_jain"] <= 1.0


def test_telemetry_harq_counters():
    """With the stop-and-wait machine on, NACKs and retx must both occur
    at bler=0.3 over a long window, and acks+nacks bounds the attempts."""
    sim = CRRM(CRRM_parameters(
        n_ues=16, n_cells=4, seed=3, pathloss_model_name="UMa",
        power_W=10.0, harq_bler=0.3, harq_max_retx=2))
    fns = sim.episode_fns(telemetry=True)
    _, _, telem = fns.rollout(sim.episode_static(),
                              sim.init_episode_state(jax.random.PRNGKey(0)),
                              60)
    nacks = np.asarray(telem.harq_nacks).sum()
    retx = np.asarray(telem.harq_retx).sum()
    assert nacks > 0, "bler=0.3 x 60 TTIs must NACK"
    assert retx > 0, "NACKed TBs must retransmit"
    # every retx attempt was once a pending (previously NACKed) TB
    assert retx <= nacks


def test_telemetry_handover_counter_fires_under_mobility():
    """Fast walkers + zero hysteresis + 1-TTI TTT: A3 must fire, and the
    counter must match the serving-cell trajectory's change count."""
    sim = CRRM(CRRM_parameters(
        n_ues=32, n_cells=6, seed=3, pathloss_model_name="UMa",
        power_W=10.0, ho_enabled=True, ho_hysteresis_db=0.0, ho_ttt_tti=1,
        mobility_step_m=150.0))
    fns = sim.episode_fns(telemetry=True)
    state0 = sim.init_episode_state(jax.random.PRNGKey(2))
    state, _, telem = fns.rollout(sim.episode_static(), state0, 80)
    ho = np.asarray(telem.ho_events)
    assert (ho >= 0).all()
    assert ho.sum() > 0, "fast walkers at 0 dB hysteresis must hand over"
    # cross-check against a stepwise serving-cell trajectory
    step_state, changes = state0, 0
    for _ in range(80):
        prev = np.asarray(step_state.serving)
        step_state, _, _ = fns.step(sim.episode_static(), step_state)
        changes += int((np.asarray(step_state.serving) != prev).sum())
    assert int(ho.sum()) == changes


# ------------------------------------------------------------- dirty rows
def test_dirty_row_counter_equals_mover_window():
    """radio_mode=incremental with mobility_move_frac: every TTI's
    dirty_rows equals the window size max(1, round(frac * n_ues))."""
    n_ues = 24
    for frac in (0.1, 0.25):
        sim = CRRM(_shrink("dense_urban_twin", n_ues=n_ues,
                           mobility_move_frac=frac))
        fns = sim.episode_fns(telemetry=True)
        _, _, telem = fns.rollout(
            sim.episode_static(),
            sim.init_episode_state(jax.random.PRNGKey(0)), 10)
        expect = max(1, int(round(frac * n_ues)))
        assert telem.dirty_rows is not None
        np.testing.assert_array_equal(np.asarray(telem.dirty_rows),
                                      np.full(10, expect, np.int32))


def test_dirty_rows_is_none_outside_incremental_mode():
    sim = CRRM(_shrink("dense_urban"))
    fns = sim.episode_fns(telemetry=True)
    _, _, telem = fns.rollout(sim.episode_static(),
                              sim.init_episode_state(jax.random.PRNGKey(0)),
                              5)
    assert telem.dirty_rows is None


# ------------------------------------------------------------ env + vmap
def test_env_step_returns_info_dict_and_matches_plain_env():
    from repro.env import CrrmEnv

    mk = dict(scenario="dense_urban",
              scenario_overrides=dict(n_ues=24, n_cells=6),
              episode_tti=20, tti_per_step=10)
    env0 = CrrmEnv(**mk)
    env1 = CrrmEnv(telemetry=True, **mk)
    key = jax.random.PRNGKey(0)
    s0, _ = env0.reset(key)
    s1, _ = env1.reset(key)
    s0, obs0, rew0, done0 = env0.step(s0)
    s1, obs1, rew1, done1, info = env1.step(s1)
    np.testing.assert_array_equal(np.asarray(obs1.tput),
                                  np.asarray(obs0.tput))
    assert float(rew1) == float(rew0)
    telem = info["telemetry"]
    assert telem.jain.shape == (10,)


def test_env_batched_telemetry_under_vmap_is_structural_noop():
    """vmapped batch: telemetry leaves gain the batch axis and the
    trajectory still matches the telemetry-off batch bit-exactly."""
    from repro.env import CrrmEnv

    mk = dict(scenario="dense_urban_mobile",
              scenario_overrides=dict(n_ues=24, n_cells=6),
              episode_tti=16, tti_per_step=8)
    env0 = CrrmEnv(**mk)
    env1 = CrrmEnv(telemetry=True, **mk)
    keys = jax.random.split(jax.random.PRNGKey(1), 4)
    s0, _ = env0.reset_batch(keys)
    s1, _ = env1.reset_batch(keys)
    s0, obs0, rew0, _ = env0.step_batch(s0)
    s1, obs1, rew1, _, info = env1.step_batch(s1)
    np.testing.assert_array_equal(np.asarray(obs1.tput),
                                  np.asarray(obs0.tput))
    np.testing.assert_array_equal(np.asarray(rew1), np.asarray(rew0))
    telem = info["telemetry"]
    assert telem.jain.shape == (4, 8)
    assert telem.served_bits.shape == (4, 8, 6)
    kpis = summarize(telem, tti_s=env1.params.tti_s)
    assert kpis["served_mbits"] > 0.0


def test_gym_adapter_surfaces_kpis_in_info():
    gymnasium = pytest.importorskip("gymnasium")  # noqa: F841
    from repro.env import CrrmEnv
    from repro.env.gym_adapter import make_gym_env

    env = CrrmEnv(scenario="dense_urban",
                  scenario_overrides=dict(n_ues=16, n_cells=4),
                  episode_tti=10, tti_per_step=5, telemetry=True)
    genv = make_gym_env(env, seed=0)
    genv.reset()
    _, _, _, _, info = genv.step(genv.action_space.sample())
    assert "kpis" in info and "telemetry" in info
    assert isinstance(info["kpis"]["served_mbits"], float)


# ------------------------------------------------------------- 2-dev mesh
_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import sys
sys.path.insert(0, "src")
import jax, numpy as np
from repro.core.crrm import CRRM
from repro.core.params import CRRM_parameters

mesh = jax.make_mesh((2,), ("ue",))
base = dict(n_ues=64, n_cells=7, seed=3, pathloss_model_name="UMa",
            power_W=10.0, scheduler_policy="rr", harq_bler=0.1,
            traffic_model="poisson",
            traffic_params=dict(arrival_rate_hz=300.0,
                                packet_size_bits=12_000.0))
kw = dict(mobility_step_m=20.0, mobility_move_frac=0.125,
          radio_mode="incremental")
key = jax.random.PRNGKey(0)

# sharded: telemetry on == off bitwise (the structural-no-op claim holds
# under shard_map too)
a, b = CRRM(CRRM_parameters(**base)), CRRM(CRRM_parameters(**base))
f_off = a.episode_fns(mesh=mesh, **kw)
f_on = b.episode_fns(mesh=mesh, telemetry=True, **kw)
s1, t1 = f_off.rollout(a.episode_static(), a.init_episode_state(key), 30)
s2, t2, telem = f_on.rollout(b.episode_static(),
                             b.init_episode_state(key), 30)
np.testing.assert_array_equal(np.asarray(t2), np.asarray(t1))
for l1, l2 in zip(jax.tree_util.tree_leaves(s1),
                  jax.tree_util.tree_leaves(s2)):
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
print("OK sharded noop")

# psum correctness: the sharded KPIs are GLOBAL -- they match the
# single-device telemetry (integer counters bitwise; float KPIs to the
# sharded suite's usual 1e-5, pf-free rr regime here)
c = CRRM(CRRM_parameters(**base))
_, t3, telem1 = c.episode_fns(telemetry=True, **kw).rollout(
    c.episode_static(), c.init_episode_state(key), 30)
np.testing.assert_array_equal(np.asarray(t3), np.asarray(t1))
for name in ("harq_acks", "harq_nacks", "harq_retx", "ho_events",
             "dirty_rows"):
    np.testing.assert_array_equal(
        np.asarray(getattr(telem, name)), np.asarray(getattr(telem1, name)),
        err_msg=name)
# 12.5% of 64 UEs -> 8 dirty rows per TTI, globally, on both layouts
np.testing.assert_array_equal(np.asarray(telem.dirty_rows),
                              np.full(30, 8, np.int32))
for name in ("served_bits", "granted_rb", "dropped_bits", "buffer_bits",
             "jain"):
    np.testing.assert_allclose(
        np.asarray(getattr(telem, name)), np.asarray(getattr(telem1, name)),
        rtol=1e-5, atol=1e-3, err_msg=name)
print("ALL_OK")
"""


@pytest.mark.slow
def test_telemetry_on_two_device_mesh():
    """Acceptance: telemetry under shard_map is (a) still a structural
    no-op and (b) psum-reduced to the same global KPIs as one device."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _MESH_SCRIPT],
                         capture_output=True, text=True, timeout=900,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))), env=env)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    assert "ALL_OK" in out.stdout


# -------------------------------------------------------- retrace counter
def test_compile_counter_catches_shape_polymorphic_calls():
    """The profiling satellite: a jitted fn fed varying shapes recompiles
    per call and the counter must see it; steady-state calls must not."""
    f = jax.jit(lambda x: (x * 2.0).sum())
    f(jnp.zeros(4))                       # pay the first compile outside
    with CompileCounter() as steady:
        for _ in range(3):
            f(jnp.ones(4))
    with CompileCounter() as poly:
        for n in (5, 6, 7):               # classic silent-retrace bug
            f(jnp.ones(n))
    if not steady.supported:
        pytest.skip("jax.monitoring compile events unavailable")
    assert steady.count == 0
    assert poly.count >= 3


def test_retrace_watch_on_engine_executables():
    sim = CRRM(_shrink("dense_urban"))
    fns = sim.episode_fns(telemetry=True)
    static, state = sim.episode_static(), sim.init_episode_state()
    fns.rollout(static, state, 5)         # warm the one expected entry
    watch = RetraceWatch(rollout=fns.rollout)
    for _ in range(3):
        state, _, _ = fns.rollout(static, state, 5)
    watch.assert_stable()                 # steady state: no new traces
    fns.rollout(static, state, 7)         # a new n_tti IS a new trace
    assert watch.retraces().get("rollout", 0) >= 1
