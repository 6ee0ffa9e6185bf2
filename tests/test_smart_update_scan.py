"""ISSUE 5: the incremental (smart-update) radio path inside the compiled
TTI engine -- dense-vs-incremental equivalence across registry scenarios,
under vmap and on a 2-device mesh, the shared dirtiness convention, and
the window-mover mobility regime."""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.crrm import CRRM
from repro.core.params import CRRM_parameters
from repro.sim import mobility, radio, scenarios

import engine_regimes

ROOT = Path(__file__).resolve().parents[1]


def _shrink(name, **kw):
    base = dict(n_ues=24, n_cells=6)
    base.update(kw)
    return scenarios.make_scenario(name, **base)


def _pair(params):
    """Two identical sims (separate graphs, shared nothing)."""
    return CRRM(params), CRRM(params)


# -------------------------------------------- dense == incremental (scan)
#: the engine switches of each regime on top of the scenario's preset;
#: "action" rolls a fixed power action over static geometry
REGIMES = {"movers": engine_regimes.MOVERS,
           **engine_regimes.CHURN_FAULTS,
           "action": dict(mobility_step_m=0)}


@pytest.mark.parametrize("name,regime",
                         engine_regimes.cases(REGIMES, "movers"))
def test_incremental_matches_dense_across_scenarios(name, regime):
    """Tentpole acceptance: the incremental rollout reproduces the dense
    rollout on every registry scenario in every switch regime -- 25%
    per-TTI window movers, birth-death churn, cell faults, both, and a
    scan-constant power action (the sharded gate's 1e-5, bit-exact
    positions)."""
    a, b = _pair(_shrink(name))
    key = jax.random.PRNGKey(0)
    kw = REGIMES[regime]
    f1 = a.episode_fns(radio_mode="dense", **kw)
    f2 = b.episode_fns(radio_mode="incremental", **kw)
    s0a = engine_regimes.start(a, key, kw)
    s0b = engine_regimes.start(b, key, kw)
    act = (jnp.asarray(a.P._data) * 0.6,) if regime == "action" else ()
    s1, t1 = f1.rollout(a.episode_static(), s0a, 20, *act)
    s2, t2 = f2.rollout(b.episode_static(), s0b, 20, *act)
    np.testing.assert_allclose(np.asarray(t2), np.asarray(t1),
                               rtol=1e-5, atol=1e-2)
    np.testing.assert_array_equal(np.asarray(s2.U), np.asarray(s1.U))
    np.testing.assert_allclose(np.asarray(s2.pf_avg), np.asarray(s1.pf_avg),
                               rtol=1e-5, atol=1e-2)
    np.testing.assert_array_equal(np.asarray(s2.serving),
                                  np.asarray(s1.serving))


def test_incremental_full_mobility_matches_legacy_dense():
    """mobility_move_frac=None: every UE moves on the legacy PR-4 draw;
    the incremental path must consume the identical stream (all rows
    dirty every TTI) and reproduce the dense trajectory."""
    a, b = _pair(CRRM_parameters(
        n_ues=16, n_cells=4, seed=3, pathloss_model_name="UMa",
        power_W=10.0, scheduler_policy="rr"))
    key = jax.random.PRNGKey(1)
    f1 = a.episode_fns(mobility_step_m=20.0)
    f2 = b.episode_fns(mobility_step_m=20.0, radio_mode="incremental")
    s1, t1 = f1.rollout(a.episode_static(), a.init_episode_state(key), 10)
    s2, t2 = f2.rollout(b.episode_static(), b.init_episode_state(key), 10)
    np.testing.assert_array_equal(np.asarray(s2.U), np.asarray(s1.U))
    np.testing.assert_allclose(np.asarray(t2), np.asarray(t1),
                               rtol=1e-5, atol=1e-2)


def test_incremental_action_matches_dense_per_tti_recompute():
    """A scan-constant power action through the incremental path (one
    prepare-time radio_init) equals the dense per-TTI recompute."""
    a, b = _pair(CRRM_parameters(
        n_ues=20, n_cells=5, seed=3, pathloss_model_name="UMa",
        power_W=10.0, traffic_model="poisson", scheduler_policy="pf",
        traffic_params=dict(arrival_rate_hz=300.0,
                            packet_size_bits=12_000.0)))
    key = jax.random.PRNGKey(0)
    act = jnp.asarray(a.P._data) * 0.6
    f1, f2 = a.episode_fns(), b.episode_fns(radio_mode="incremental")
    s1, t1 = f1.rollout(a.episode_static(), a.init_episode_state(key),
                        15, act)
    s2, t2 = f2.rollout(b.episode_static(), b.init_episode_state(key),
                        15, act)
    np.testing.assert_allclose(np.asarray(t2), np.asarray(t1),
                               rtol=1e-5, atol=1e-2)
    # step() agrees too (per-call init, same values)
    _, o1 = f1.step(a.episode_static(), a.init_episode_state(key), act)
    _, o2 = f2.step(b.episode_static(), b.init_episode_state(key), act)
    np.testing.assert_allclose(np.asarray(o2), np.asarray(o1),
                               rtol=1e-5, atol=1e-2)


def test_incremental_vmaps_over_batched_episodes():
    """N seeds, one vmapped incremental program == N dense episodes."""
    p = _shrink("dense_urban_twin", n_ues=16, n_cells=6)
    a, b = _pair(p)
    keys = jax.random.split(jax.random.PRNGKey(4), 3)
    st_a, st_b = a.episode_static(), b.episode_static()
    f1, f2 = a.episode_fns(radio_mode="dense"), b.episode_fns()
    batch = jax.tree_util.tree_map(
        lambda *x: jnp.stack(x), *[a.init_episode_state(k) for k in keys])
    _, t1 = jax.jit(jax.vmap(lambda s: f1.rollout(st_a, s, 10)))(batch)
    _, t2 = jax.jit(jax.vmap(lambda s: f2.rollout(st_b, s, 10)))(batch)
    np.testing.assert_allclose(np.asarray(t2), np.asarray(t1),
                               rtol=1e-5, atol=1e-2)


def test_env_incremental_action_matches_dense_env():
    """CrrmEnv(radio_mode='incremental'): the action step that is 3x the
    passive cost on the dense path costs one chain init here -- same
    observations/rewards either way."""
    from repro.env import CrrmEnv
    kw = dict(n_ues=24, n_cells=4, seed=3, pathloss_model_name="UMa",
              power_W=10.0, traffic_model="poisson", scheduler_policy="pf",
              traffic_params=dict(arrival_rate_hz=300.0,
                                  packet_size_bits=12_000.0))
    e1 = CrrmEnv(CRRM_parameters(**kw), episode_tti=40, tti_per_step=20)
    e2 = CrrmEnv(CRRM_parameters(**kw), episode_tti=40, tti_per_step=20,
                 radio_mode="incremental")
    key = jax.random.PRNGKey(0)
    s1, _ = e1.reset(key)
    s2, _ = e2.reset(key)
    act = 0.8 * e1.uniform_action()
    for _ in range(2):
        s1, o1, r1, d1 = e1.step(s1, act)
        s2, o2, r2, d2 = e2.step(s2, act)
        np.testing.assert_allclose(np.asarray(o2.tput), np.asarray(o1.tput),
                                   rtol=1e-5, atol=1e-2)
        np.testing.assert_allclose(float(r2), float(r1), rtol=1e-5)
        assert bool(d1) == bool(d2)


# ------------------------------------------------ the dirtiness convention
def test_dirty_indices_matches_pad_indices_convention():
    """The traced mask compaction and the host-side power-of-two buckets
    are two faces of one convention: valid-index padding, idempotent
    recompute, no masking."""
    mask = jnp.zeros(16, bool).at[jnp.array([3, 7, 11])].set(True)
    idx = radio.dirty_indices(mask, 8)
    assert idx.shape == (8,)
    np.testing.assert_array_equal(np.asarray(idx[:3]), [3, 7, 11])
    assert set(np.asarray(idx[3:]).tolist()) == {0}      # valid-row padding
    host = radio.pad_indices([3, 7, 11])
    assert host.shape == (4,) and host[-1] == 3          # repeated valid idx
    from repro.core.graph import pad_indices as graph_pad
    assert graph_pad is radio.pad_indices                # ONE implementation


def test_radio_update_rows_is_idempotent_under_padding():
    """Padded (repeated / row-0) indices scatter bit-identical values --
    the property both smart-update surfaces rely on."""
    sim = CRRM(_shrink("indoor_hotspot", n_ues=12, n_cells=4))
    rs = sim.radio_static()
    U, fad = sim.U._data, sim.fading._data
    st = radio.radio_init(rs.cfg, U, rs.C, rs.bore, fad, rs.P)
    idx = jnp.array([5, 5, 0, 0, 0, 0], jnp.int32)       # pure padding
    st2 = radio.radio_update_rows(rs.cfg, st, U, rs.C, rs.bore, fad,
                                  rs.P, idx)
    for a, b in zip(st, st2):
        if a is not None:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_radio_update_cell_mask_applies_power_delta():
    """Dirty cell columns re-derive every UE's outputs from the carried
    gains -- equal to a full init under the new power matrix; an all-False
    mask is a branch-free no-op."""
    sim = CRRM(_shrink("rural_macro", n_ues=12, n_cells=4))
    rs = sim.radio_static()
    U, fad = sim.U._data, sim.fading._data
    st = radio.radio_init(rs.cfg, U, rs.C, rs.bore, fad, rs.P,
                          with_gain=True)
    P2 = rs.P.at[1].mul(0.25)
    mask = jnp.zeros(rs.P.shape[0], bool).at[1].set(True)
    got = radio.radio_update(rs, st, U, jnp.zeros(U.shape[0], bool),
                             dirty_cell_mask=mask, budget=1, fad=fad, P=P2)
    want = radio.radio_init(rs.cfg, U, rs.C, rs.bore, fad, P2,
                            with_gain=True)
    np.testing.assert_allclose(np.asarray(got.se), np.asarray(want.se),
                               rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(got.a), np.asarray(want.a))
    noop = radio.radio_update(rs, st, U, jnp.zeros(U.shape[0], bool),
                              dirty_cell_mask=jnp.zeros_like(mask),
                              budget=1, fad=fad, P=P2)
    np.testing.assert_array_equal(np.asarray(noop.se), np.asarray(st.se))


# ------------------------------------------------------ window-mover regime
def test_window_movers_exact_count_and_bounds():
    """At most round(frac * n) movers per TTI; non-movers hold position;
    movers stay inside the region."""
    p = CRRM_parameters(n_ues=40, n_cells=3, seed=1, extent_m=500.0,
                        pathloss_model_name="UMa", power_W=10.0,
                        mobility_step_m=10.0, mobility_move_frac=0.2,
                        scheduler_policy="rr")
    sim = CRRM(p)
    fns = sim.episode_fns()
    state = sim.init_episode_state(jax.random.PRNGKey(0))
    st = sim.episode_static()
    for _ in range(4):
        U0 = np.asarray(state.U)
        state, _ = fns.step(st, state)
        U1 = np.asarray(state.U)
        moved = (np.abs(U1[:, :2] - U0[:, :2]).sum(axis=1) > 0)
        assert moved.sum() <= 8                     # = round(0.2 * 40)
        assert (U1[:, :2] >= 0).all() and (U1[:, :2] <= 500.0).all()
    start, d = mobility.window_movers(jax.random.PRNGKey(7), 40, 8, 10.0)
    rows = jnp.arange(40)
    disp, mask = mobility.window_displacements(start, d, rows, 40)
    assert int(mask.sum()) == 8
    np.testing.assert_array_equal(np.asarray(disp[~np.asarray(mask)]), 0.0)


# ------------------------------------ the mover window's rows, by position
# (n_move, shards, start) on a 24-row field: one block, with the window at
# 0, the middle, n - n_move, one past it (wrapping) and n - 1, or covering
# it; four blocks of 6, each missed, entered, left or covered, and padded
# with row 0 wherever the window leaves slots of a block's slab empty
ROW_CASES = ([(5, 1, s) for s in (0, 12, 19, 20, 23)]
             + [(24, 1, 7), (4, 4, 0), (4, 4, 4), (4, 4, 22), (8, 4, 3),
                (8, 4, 20)])


@pytest.mark.parametrize("fused", [False, True], ids=["xla", "fused"])
@pytest.mark.parametrize("n_move,shards,start", ROW_CASES)
def test_window_rows_patch_matches_index_patch(n_move, shards, start,
                                               fused):
    """A window's rows taken and written back by slices
    (``radio.window_rows``) patch every block's a/se/cqi bitwise as the
    index form does (``window_indices`` + gather + ``.at[idx].set``), from
    the same slab of rows (row 0 at the padding).  Each block's row 0 is
    moved too, so its carried value is stale unless the padding rewrites
    it, as the index form's padded writes do."""
    sim = CRRM(_shrink("dense_urban"))
    rs = sim.radio_static()
    U, fad = sim.U._data, sim.fading._data
    n = U.shape[0]
    n_loc = n // shards
    st = radio.radio_init(rs.cfg, U, rs.C, rs.bore, fad, rs.P)
    moved = (start + np.arange(n_move)) % n
    U2 = (U.at[moved].add(jnp.array([35.0, -20.0, 0.0], U.dtype))
          .at[::n_loc].add(jnp.array([400.0, 250.0, 0.0], U.dtype)))
    patch = (radio.radio_update_rows_fused if fused
             else radio.radio_update_rows)
    for s in range(shards):
        blk = slice(s * n_loc, (s + 1) * n_loc)
        st_b = radio.RadioState(*(None if x is None else x[blk] for x in st))
        idx, count = radio.window_indices(jnp.int32(start), n_move, n,
                                          offset=s * n_loc, n_loc=n_loc)
        win = radio.window_rows(jnp.int32(start), n_move, n,
                                offset=s * n_loc, n_loc=n_loc)
        assert win.size == idx.shape[0] and int(win.count) == int(count)
        slab = np.asarray(win.take(U2[blk]))
        assert sorted(map(tuple, slab)) == sorted(
            map(tuple, np.asarray(U2[blk][idx])))
        want = patch(rs.cfg, st_b, U2[blk], rs.C, rs.bore, fad[blk], rs.P,
                     idx)
        got = patch(rs.cfg, st_b, U2[blk], rs.C, rs.bore, fad[blk], rs.P,
                    win)
        for field, w, g in zip(radio.RadioState._fields, want, got):
            assert (w is None) == (g is None), field
            if w is not None:
                np.testing.assert_array_equal(
                    np.asarray(g), np.asarray(w),
                    err_msg=f"{field}, block {s}")


def _mmtc_params(**kw):
    """``uma_mmtc.movers20``'s deployment at a test size."""
    cfg = json.loads((ROOT / "bench" / "configs" / "uma_mmtc.json")
                     .read_text())["CRRM_parameters"]
    cfg.update(n_ues=200, traffic_model="full_buffer", seed=5, **kw)
    return CRRM_parameters(**cfg)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("frac", [0.2, 0.8])
def test_window_rollout_matches_index_path(frac, backend):
    """``movers20``'s incremental rollout, through the XLA rows or the
    fused kernel (interpret mode), takes the mover window by slices and
    ends bitwise where the index path (``window_oracle``) ends: outputs and
    every leaf of the final state.  At 80% movers most TTIs wrap the
    window around the field's end."""
    from window_oracle import index_path
    p = _mmtc_params(mobility_step_m=20.0, mobility_move_frac=frac)
    a, b = _pair(p)
    kw = dict(radio_mode="incremental", inc_backend=backend)
    key = jax.random.PRNGKey(9)
    before = radio.window_lowerings()
    s1, t1 = a.episode_fns(**kw).rollout(a.episode_static(),
                                         a.init_episode_state(key), 10)
    assert radio.window_lowerings()["slices"] > before["slices"]
    with index_path():
        s2, t2 = b.episode_fns(**kw).rollout(b.episode_static(),
                                             b.init_episode_state(key), 10)
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
    for field, x, y in zip(s1._fields, s1, s2):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=field)


def test_mask_and_churn_patches_stay_indexed():
    """Dirty rows from a mask (``radio_update`` without a window) and
    churn births keep the index gather and scatter: no slices."""
    from repro.sim.mobility import ChurnConfig
    sim = CRRM(_shrink("dense_urban"))
    rs = sim.radio_static()
    U = sim.U._data
    st = radio.radio_init(rs.cfg, U, rs.C, rs.bore, None, rs.P)
    before = radio.window_lowerings()
    radio.radio_update(rs, st, U, jnp.arange(U.shape[0]) % 5 == 0,
                       budget=6)
    churn = ChurnConfig(arrival_rate_hz=400.0, mean_lifetime_s=0.1,
                        max_arrivals_per_tti=4)
    fns = sim.episode_fns(radio_mode="incremental", churn=churn)
    from repro.mac.engine import seed_churn_state
    state = seed_churn_state(sim.init_episode_state(jax.random.PRNGKey(1)),
                             sim.episode_static(), sim.params)
    fns.rollout.lower(sim.episode_static(), state, 3)
    after = radio.window_lowerings()
    assert after["slices"] == before["slices"]
    assert after["indexed"] >= before["indexed"] + 2


# ---------------------------------------- fused dirty-row backend (ISSUE 9)
@pytest.mark.parametrize("name", scenarios.scenario_names())
def test_fused_rows_match_xla_rows_across_scenarios(name):
    """The dirty-row Pallas kernel variant (interpret mode on CPU) patches
    rows bitwise-identically to ``radio_update_rows`` on every registry
    scenario's O(n_ue) chain -- same gather, same scatter, the gain/RSRP
    math fused into VMEM tiles in between."""
    sim = CRRM(_shrink(name))
    rs = sim.radio_static()
    U, fad = sim.U._data, sim.fading._data
    st = radio.radio_init(rs.cfg, U, rs.C, rs.bore, fad, rs.P)
    idx = jnp.array([3, 7, 11, 19, 19, 0, 0, 0], jnp.int32)  # padded
    U2 = U.at[jnp.array([3, 7, 11, 19])].add(
        jnp.array([30.0, -12.0, 0.0], U.dtype))
    got_x = radio.radio_update_rows(rs.cfg, st, U2, rs.C, rs.bore, fad,
                                    rs.P, idx)
    got_f = radio.radio_update_rows_fused(rs.cfg, st, U2, rs.C, rs.bore,
                                          fad, rs.P, idx)
    for field, x, f in zip(radio.RadioState._fields, got_x, got_f):
        assert (x is None) == (f is None), field
        if x is not None:
            np.testing.assert_array_equal(np.asarray(f), np.asarray(x),
                                          err_msg=field)


def test_fused_rows_reject_table_and_gain_carries():
    """HO tables / carried gains need O(n_cell)-per-row outputs the
    streaming accumulator never materialises; the fused variant refuses
    rather than silently dropping them."""
    sim = CRRM(_shrink("handover_stress"))
    rs = sim.radio_static()
    U, fad = sim.U._data, sim.fading._data
    idx = jnp.zeros(4, jnp.int32)
    st = radio.radio_init(rs.cfg, U, rs.C, rs.bore, fad, rs.P,
                          with_tables=True)
    with pytest.raises(ValueError, match="se_all"):
        radio.radio_update_rows_fused(rs.cfg, st, U, rs.C, rs.bore, fad,
                                      rs.P, idx)


def test_engine_inc_backend_pallas_matches_xla():
    """inc_backend="pallas" (the fused dirty-row kernel, interpret mode on
    CPU) rolls out bitwise-identically to the XLA row recompute; "pallas"
    raises on inexpressible configurations (handover tables) with a
    diagnostic, and "auto" falls back to XLA there instead."""
    a, b = _pair(_shrink("dense_urban"))
    kw = dict(mobility_step_m=25.0, mobility_move_frac=0.25)
    key = jax.random.PRNGKey(0)
    f1 = a.episode_fns(radio_mode="incremental", inc_backend="xla", **kw)
    f2 = b.episode_fns(radio_mode="incremental", inc_backend="pallas", **kw)
    s1, t1 = f1.rollout(a.episode_static(), a.init_episode_state(key), 8)
    s2, t2 = f2.rollout(b.episode_static(), b.init_episode_state(key), 8)
    np.testing.assert_array_equal(np.asarray(t2), np.asarray(t1))
    np.testing.assert_array_equal(np.asarray(s2.U), np.asarray(s1.U))

    ho = CRRM(_shrink("handover_stress"))
    with pytest.raises(ValueError, match="cannot express"):
        ho.episode_fns(radio_mode="incremental", inc_backend="pallas")
    ho.episode_fns(radio_mode="incremental", inc_backend="auto")  # falls back


# ------------------------------------- donated rollout executable (ISSUE 9)
def test_rollout_donated_matches_rollout_and_does_not_retrace():
    """``rollout_donated`` is the same program with the state buffers
    donated: bitwise-equal outputs, and re-invoking it with the returned
    (same-shape) state compiles nothing new."""
    from repro.obs.profile import CompileCounter
    a, b = _pair(_shrink("dense_urban_twin"))
    key = jax.random.PRNGKey(0)
    fns = a.episode_fns()
    static = a.episode_static()
    _, t_ref = fns.rollout(static, a.init_episode_state(key), 8)
    state, t1 = fns.rollout_donated(static, b.init_episode_state(key), 8)
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t_ref))
    with CompileCounter() as c:
        state, t2 = fns.rollout_donated(static, state, 8)
        jax.block_until_ready((state, t2))
    if c.supported:
        assert c.count == 0, f"donated rollout retraced: {c.count} compiles"


# -------------------------------------------------- 2-device mesh equivalence
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
            power_W=10.0, rayleigh_fading=True, attach_ignores_fading=True,
            scheduler_policy="rr", ho_enabled=True,
            traffic_model="poisson",
            traffic_params=dict(arrival_rate_hz=300.0,
                                packet_size_bits=12_000.0))
kw = dict(mobility_step_m=20.0, mobility_move_frac=0.125)
a, b = CRRM(CRRM_parameters(**base)), CRRM(CRRM_parameters(**base))
key = jax.random.PRNGKey(0)
f1 = a.episode_fns(radio_mode="incremental", **kw)
f2 = b.episode_fns(radio_mode="incremental", mesh=mesh, **kw)
s1, t1 = f1.rollout(a.episode_static(), a.init_episode_state(key), 40)
s2, t2 = f2.rollout(b.episode_static(), b.init_episode_state(key), 40)
np.testing.assert_allclose(np.asarray(t2), np.asarray(t1), rtol=1e-5,
                           atol=1e-2)
for l1, l2 in zip(jax.tree_util.tree_leaves(s1),
                  jax.tree_util.tree_leaves(s2)):
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), rtol=1e-5,
                               atol=1e-3)
print("OK incremental sharded")
# sharded incremental == sharded dense (same mesh)
c = CRRM(CRRM_parameters(**base))
f3 = c.episode_fns(radio_mode="dense", mesh=mesh, **kw)
s3, t3 = f3.rollout(c.episode_static(), c.init_episode_state(key), 40)
np.testing.assert_allclose(np.asarray(t2), np.asarray(t3), rtol=1e-5,
                           atol=1e-2)
print("ALL_OK")
"""


@pytest.mark.slow
def test_incremental_on_two_device_mesh_matches_single_device():
    """Acceptance: the incremental path under shard_map on a 2-device
    host mesh matches both the single-device incremental rollout and the
    sharded dense rollout (subprocess: device count must be forced before
    jax initialises)."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _MESH_SCRIPT],
                         capture_output=True, text=True, timeout=900,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))), env=env)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    assert "ALL_OK" in out.stdout


# ----------------------------------------- UE x cell mesh (ISSUE 9 tentpole)
_UECELL_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import sys
sys.path.insert(0, "src")
import jax, numpy as np
from repro.core.crrm import CRRM
from repro.sim import scenarios

mesh = jax.make_mesh((1, 2), ("ue", "cell"))
kw = dict(mobility_step_m=20.0, mobility_move_frac=0.25)
key = jax.random.PRNGKey(0)

def roll(sim, n_tti, **ekw):
    fns = sim.episode_fns(**ekw)
    return fns.rollout(sim.episode_static(), sim.init_episode_state(key),
                       n_tti)

def check(name, mode, n_tti=8):
    base = scenarios.make_scenario(name, n_ues=24, n_cells=6)
    s1, t1 = roll(CRRM(base), n_tti, radio_mode=mode, **kw)
    s2, t2 = roll(CRRM(base), n_tti, radio_mode=mode, mesh=mesh,
                  cell_axis=("cell",), **kw)
    np.testing.assert_allclose(np.asarray(t2), np.asarray(t1),
                               rtol=1e-5, atol=1e-2)
    np.testing.assert_array_equal(np.asarray(s2.U), np.asarray(s1.U))
    np.testing.assert_array_equal(np.asarray(s2.serving),
                                  np.asarray(s1.serving))
    for l1, l2 in zip(jax.tree_util.tree_leaves(s1),
                      jax.tree_util.tree_leaves(s2)):
        np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                                   rtol=1e-5, atol=1e-3)
    print("OK", name, mode)

# the tentpole contract: every registry scenario, incremental (the
# dirty-row chain runs radio_init AND radio_update_rows under cell
# sharding); one dense case covers the dense cell-sharded chain
for name in scenarios.scenario_names():
    check(name, "incremental")
check("dense_urban", "dense")
print("ALL_OK")
"""


@pytest.mark.slow
def test_episode_on_ue_by_cell_mesh_matches_single_device():
    """ISSUE 9 acceptance: a UE x cell mesh episode (cells sharded over a
    2-device host mesh) reproduces the single-device rollout on every
    registry scenario within the established equivalence contract
    (throughput/state 1e-5, attachment/serving/positions bitwise)."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _UECELL_MESH_SCRIPT],
                         capture_output=True, text=True, timeout=900,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))), env=env)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    assert "ALL_OK" in out.stdout
