"""ISSUE 10: fault injection and self-healing -- the in-scan cell
outage/sleep Markov process (fault-free bitwise pin, dark-cell physics,
reattachment, dense==incremental under outages, churn/vmap/mesh
composition) and the crash-safe twin server (guard, watchdog rollback
with bitwise resume, checkpoint CRC validation + corrupt-step fallback,
backend degradation, graceful TwinServerDown)."""
import math
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
from repro.robust import guard
from repro.robust.watchdog import (ChunkTimeout, TwinServerDown,
                                   WatchdogConfig, run_with_timeout)
from repro.sim import scenarios
from repro.sim.faults import DOWN, SLEEP, UP, FaultConfig, tx_multiplier
from repro.sim.mobility import ChurnConfig
from repro.train import checkpoint as ckpt
from repro.twin.server import TwinServer

STORM = FaultConfig(outage_rate_hz=20.0, mean_outage_s=0.03,
                    sleep_rate_hz=20.0, mean_sleep_s=0.02,
                    sleep_atten_db=10.0)
FROZEN = FaultConfig(outage_rate_hz=0.0, mean_outage_s=1.0,
                     sleep_rate_hz=0.0, mean_sleep_s=1.0)


def _params(**kw):
    base = dict(n_ues=24, n_cells=6, n_sectors=1, seed=5,
                pathloss_model_name="UMa", power_W=10.0,
                scheduler_policy="pf", traffic_model="poisson",
                traffic_params=dict(arrival_rate_hz=300.0,
                                    packet_size_bits=12_000.0))
    base.update(kw)
    return CRRM_parameters(**base)


def _roll(params, n_tti=20, key=None, telemetry=False, **fns_kw):
    sim = CRRM(params)
    fns = sim.episode_fns(telemetry=telemetry, **fns_kw)
    state = sim.init_episode_state(
        key if key is not None else jax.random.PRNGKey(0))
    return fns.rollout(sim.episode_static(), state, n_tti)


# ------------------------------------------------- fault-process invariants
def test_zero_rate_faults_bitwise_equal_off():
    """The fault PRNG lineage is its own stream: arming the fault process
    at zero transition rates must leave the trajectory BITWISE identical
    to faults-off (the compensation path never fires, and no other
    stream shifted)."""
    p = _params(mobility_step_m=10.0)
    s_off, t_off = _roll(p, faults=None)
    s_on, t_on = _roll(p, faults=FROZEN)
    np.testing.assert_array_equal(np.asarray(t_on), np.asarray(t_off))
    for name in ("U", "backlog", "pf_avg", "serving", "harq_bits"):
        np.testing.assert_array_equal(np.asarray(getattr(s_on, name)),
                                      np.asarray(getattr(s_off, name)))
    assert s_off.cell_state is None
    np.testing.assert_array_equal(np.asarray(s_on.cell_state),
                                  np.full(p.n_cells, UP))


def test_scenario_faults_off_override_restores_legacy_treedef():
    """faults=None override on a faulted preset compiles the legacy
    program: no cell_state leaf, same treedef as any pre-fault episode."""
    base = scenarios.make_scenario("outage_storm", n_ues=16, n_cells=6,
                                   faults=None)
    assert base.faults is None
    s, _ = _roll(base, n_tti=4)
    assert s.cell_state is None


def test_down_cell_is_dark():
    """A cell seeded DOWN (frozen chain: it never repairs) serves zero
    bits, is granted zero RBs and is nobody's serving cell -- the outage
    acts purely through the tx-power mask and the existing radio path."""
    p = _params(n_ues=32, n_cells=5)
    sim = CRRM(p)
    fns = sim.episode_fns(telemetry=True, faults=FROZEN)
    state = sim.init_episode_state(jax.random.PRNGKey(1))
    dark = 2
    cs = np.full(p.n_cells, UP)
    cs[dark] = DOWN
    state = mac_engine.seed_fault_state(state, cell_state=cs)
    s, t, telem = fns.rollout(sim.episode_static(), state, 25)
    served = np.asarray(telem.served_bits)       # (n_tti, n_cells)
    granted = np.asarray(telem.granted_rb)
    assert served[:, dark].sum() == 0.0, "a DOWN cell delivered bits"
    assert granted[:, dark].sum() == 0.0, "a DOWN cell was granted RBs"
    assert not (np.asarray(s.serving) == dark).any(), \
        "a UE ended the episode attached to a DOWN cell"
    assert served.sum() > 0.0, "the network died with one cell out"
    np.testing.assert_array_equal(np.asarray(s.cell_state), cs)


def test_sleep_cell_attenuated_not_dark():
    """SLEEP is a soft fault: the cell keeps serving (it can still be
    attached) but at sleep_atten_db lower tx power -- its served share
    drops vs the fault-free run instead of vanishing."""
    p = _params(n_ues=48, n_cells=5, seed=2)
    sim = CRRM(p)
    asleep = 1
    cs = np.full(p.n_cells, UP)
    cs[asleep] = SLEEP
    deep = FaultConfig(outage_rate_hz=0.0, mean_outage_s=1.0,
                       sleep_rate_hz=0.0, mean_sleep_s=1.0,
                       sleep_atten_db=30.0)

    def served_share(cell_state):
        fns = sim.episode_fns(telemetry=True, faults=deep)
        state = sim.init_episode_state(jax.random.PRNGKey(0))
        state = mac_engine.seed_fault_state(state, cell_state=cell_state)
        _, _, telem = fns.rollout(sim.episode_static(), state, 25)
        served = np.asarray(telem.served_bits)
        return served[:, asleep].sum(), served.sum()

    awake_bits, awake_total = served_share(np.full(p.n_cells, UP))
    sleep_bits, sleep_total = served_share(cs)
    assert awake_bits > 0.0 and sleep_total > 0.0
    assert sleep_bits < awake_bits, \
        "a 30 dB sleeping cell served no less than awake"
    m = np.asarray(tx_multiplier(jnp.asarray(cs), deep))
    assert m[asleep] == pytest.approx(1e-3)
    assert m[[0, 2, 3, 4]].tolist() == [1.0] * 4


def test_reattachment_conservation_under_storm():
    """Per-TTI attachment (non-HO) must never leave a UE on a DOWN cell:
    the zeroed RSRP column loses every argmax while any cell is up.
    Stepped TTI-by-TTI so each TTI's serving is checked against that
    TTI's fault state."""
    p = _params(n_ues=32, n_cells=5, seed=3)
    sim = CRRM(p)
    fns = sim.episode_fns(telemetry=True, faults=STORM)
    static = sim.episode_static()
    state = sim.init_episode_state(jax.random.PRNGKey(4))
    saw_down = 0
    for _ in range(60):
        state, _, _ = fns.step(static, state)
        cs = np.asarray(state.cell_state)
        srv = np.asarray(state.serving)
        if (cs == DOWN).any() and (cs != DOWN).any():
            saw_down += 1
            assert not (cs[srv] == DOWN).any(), \
                "a UE stayed attached to a DOWN cell"
    assert saw_down > 5, "storm never produced a mixed up/down TTI"


def test_dense_equals_incremental_under_storm():
    """The engine equivalence contract holds with the fault process on:
    the incremental path's gain-carry fault update reproduces the dense
    recompute (cell_state bitwise -- same single fault stream)."""
    base = scenarios.make_scenario("outage_storm", n_ues=24, n_cells=6)
    kw = dict(key=jax.random.PRNGKey(0), n_tti=20)
    s1, t1 = _roll(base, radio_mode="dense", **kw)
    s2, t2 = _roll(base, radio_mode="incremental", **kw)
    np.testing.assert_allclose(np.asarray(t2), np.asarray(t1),
                               rtol=1e-5, atol=1e-2)
    np.testing.assert_array_equal(np.asarray(s2.cell_state),
                                  np.asarray(s1.cell_state))
    np.testing.assert_array_equal(np.asarray(s2.serving),
                                  np.asarray(s1.serving))
    np.testing.assert_array_equal(np.asarray(s2.U), np.asarray(s1.U))


def test_faults_compose_with_churn_and_vmap():
    """Faults + birth-death churn in one compiled scan, vmapped over a
    batch of episodes: batched cell_state, per-episode divergence."""
    p = _params(n_ues=16, n_cells=4)
    sim = CRRM(p)
    churn = ChurnConfig(arrival_rate_hz=300.0, mean_lifetime_s=0.1,
                        max_arrivals_per_tti=4)
    fns = sim.episode_fns(churn=churn, faults=STORM)
    static = sim.episode_static()
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    states = jax.vmap(lambda k: mac_engine.seed_churn_state(
        sim.init_episode_state(k), static, sim.params))(keys)
    roll = jax.vmap(lambda s: fns.rollout(static, s, 15))
    s, t = roll(states)
    assert s.cell_state.shape == (3, p.n_cells)
    assert np.asarray(t).shape == (3, 15, p.n_ues)
    assert not np.array_equal(np.asarray(t)[0], np.asarray(t)[1]), \
        "vmapped episodes did not diverge"


def test_fault_params_validation():
    with pytest.raises(ValueError, match="FaultConfig"):
        _params(faults="storm")
    with pytest.raises(ValueError):
        _params(faults=FaultConfig(outage_rate_hz=-1.0))
    with pytest.raises(ValueError):
        # per-TTI probability above 1 at tti_s=1ms
        _params(faults=FaultConfig(outage_rate_hz=2000.0))


_MESH_FAULTS_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import sys
sys.path.insert(0, "src")
import jax, numpy as np
from repro.core.crrm import CRRM
from repro.sim import scenarios

key = jax.random.PRNGKey(0)
base = scenarios.make_scenario("outage_storm", n_ues=24, n_cells=6)

def roll(n_tti=10, **ekw):
    sim = CRRM(base)
    fns = sim.episode_fns(**ekw)
    return fns.rollout(sim.episode_static(), sim.init_episode_state(key),
                       n_tti)

for mode in ("dense", "incremental"):
    s1, t1 = roll(radio_mode=mode)
    for mesh, cell_axis in (
            (jax.make_mesh((2,), ("ue",)), None),
            (jax.make_mesh((1, 2), ("ue", "cell")), ("cell",))):
        s2, t2 = roll(radio_mode=mode, mesh=mesh, cell_axis=cell_axis)
        np.testing.assert_allclose(np.asarray(t2), np.asarray(t1),
                                   rtol=1e-5, atol=1e-2)
        np.testing.assert_array_equal(np.asarray(s2.cell_state),
                                      np.asarray(s1.cell_state))
        np.testing.assert_array_equal(np.asarray(s2.serving),
                                      np.asarray(s1.serving))
        print("OK", mode, cell_axis)
print("ALL_OK")
"""


@pytest.mark.slow
def test_faults_on_mesh_match_single_device():
    """The fault process composes with UE sharding and the UE x cell
    mesh: the replicated cell_state chain and the compensated
    attachment match the single-device rollout bitwise/1e-5."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _MESH_FAULTS_SCRIPT],
                         capture_output=True, text=True, timeout=900,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))), env=env)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    assert "ALL_OK" in out.stdout


# --------------------------------------------------------- guard invariants
def test_guard_accepts_healthy_carry():
    sim = CRRM(_params())
    fns = sim.episode_fns()
    s, _ = fns.rollout(sim.episode_static(),
                       sim.init_episode_state(jax.random.PRNGKey(0)), 5)
    assert bool(guard.carry_ok(s))
    assert guard.carry_violations(s) == []
    assert not guard.tree_has_nan(s)


def test_guard_trips_on_each_invariant():
    sim = CRRM(_params())
    s0 = sim.init_episode_state(jax.random.PRNGKey(0))
    for poisoned in (
            s0._replace(U=s0.U.at[0, 0].set(jnp.nan)),
            s0._replace(pf_avg=s0.pf_avg.at[1].set(-1.0)),
            s0._replace(harq_bits=s0.harq_bits.at[0].set(jnp.inf)),
            s0._replace(backlog=s0.backlog.at[2].set(-5.0)),
            s0._replace(t=jnp.int32(-1))):
        assert not bool(guard.carry_ok(poisoned))
        assert guard.carry_violations(poisoned) != []


def test_guard_allows_inf_backlog():
    """+inf backlog is the engine's legal full-buffer sentinel."""
    sim = CRRM(_params(traffic_model="full_buffer"))
    s0 = sim.init_episode_state(jax.random.PRNGKey(0))
    s = s0._replace(backlog=jnp.full_like(s0.backlog, jnp.inf))
    assert bool(guard.carry_ok(s))
    assert not guard.tree_has_nan(s)


def test_run_with_timeout():
    assert run_with_timeout(lambda: 41 + 1, None) == 42
    assert run_with_timeout(lambda: "fast", 5.0) == "fast"
    with pytest.raises(ZeroDivisionError):
        run_with_timeout(lambda: 1 / 0, 5.0)
    import time as _time
    with pytest.raises(ChunkTimeout):
        run_with_timeout(lambda: _time.sleep(2.0), 0.05)


# ----------------------------------------------- checkpoint hardening
def _tree(v):
    return {"w": jnp.full((4, 3), float(v)), "step": jnp.asarray(v)}


def test_save_refuses_nan_and_preserves_good_checkpoint(tmp_path):
    d = str(tmp_path)
    ckpt.save(d, 1, _tree(1))
    bad = {"w": jnp.full((4, 3), jnp.nan), "step": jnp.asarray(2)}
    with pytest.raises(ValueError, match="NaN"):
        ckpt.save(d, 2, bad, keep_last=1)
    with pytest.raises(ValueError, match="NaN"):
        ckpt.save_async(d, 2, bad, keep_last=1)
    # the refusal happened before any byte moved: step 1 intact + valid
    assert ckpt.all_steps(d) == [1]
    tree, _, step = ckpt.restore_latest_valid(d, _tree(0))
    assert step == 1


def test_save_allows_inf(tmp_path):
    """+inf is legal state (full-buffer backlog) -- only NaN is refused."""
    d = str(tmp_path)
    ckpt.save(d, 1, {"w": jnp.full(3, jnp.inf), "step": jnp.asarray(1)})
    assert ckpt.all_steps(d) == [1]


def _corrupt(d, step, nbytes=8, leaf="00000.npy"):
    path = os.path.join(d, f"step_{step:010d}", leaf)
    with open(path, "r+b") as f:
        f.seek(-nbytes, os.SEEK_END)
        f.write(b"\xff" * nbytes)


def test_restore_detects_crc_corruption(tmp_path):
    d = str(tmp_path)
    ckpt.save(d, 3, _tree(3))
    ckpt.restore(d, 3, _tree(0))                 # validates clean
    # hit data bytes of the (4, 3) leaf: the npy parses fine, only the
    # CRC can tell the payload was flipped
    _corrupt(d, 3, leaf="00001.npy")
    with pytest.raises(ckpt.CheckpointCorrupt, match="CRC"):
        ckpt.restore(d, 3, _tree(0))


def test_restore_detects_truncated_leaf(tmp_path):
    d = str(tmp_path)
    ckpt.save(d, 1, _tree(1))
    leaf = os.path.join(d, "step_0000000001", "00000.npy")
    data = open(leaf, "rb").read()
    with open(leaf, "wb") as f:
        f.write(data[:len(data) // 2])
    with pytest.raises(ckpt.CheckpointCorrupt):
        ckpt.restore(d, 1, _tree(0))


def test_restore_latest_valid_falls_back_past_corrupt(tmp_path):
    d = str(tmp_path)
    for s in (1, 2, 3):
        ckpt.save(d, s, _tree(s), keep_last=0)
    _corrupt(d, 3)
    tree, _, step = ckpt.restore_latest_valid(d, _tree(0))
    assert step == 2
    np.testing.assert_array_equal(np.asarray(tree["w"]),
                                  np.full((4, 3), 2.0))
    # every step corrupt -> CheckpointCorrupt, not silence
    _corrupt(d, 1)
    _corrupt(d, 2)
    with pytest.raises(ckpt.CheckpointCorrupt, match="no valid"):
        ckpt.restore_latest_valid(d, _tree(0))


# --------------------------------------------------- twin server watchdog
def _twin(tmpdir, watchdog=None, **kw):
    p = _params(n_ues=32, n_cells=5, seed=9, **kw.pop("params_kw", {}))
    churn = ChurnConfig(arrival_rate_hz=300.0, mean_lifetime_s=0.2,
                        max_arrivals_per_tti=4)
    return TwinServer(CRRM(p), churn, chunk_tti=10,
                      ckpt_dir=None if tmpdir is None else str(tmpdir),
                      watchdog=watchdog, **kw)


def test_watchdog_requires_ckpt_dir():
    with pytest.raises(ValueError, match="ckpt_dir"):
        _twin(None, watchdog=True)


def test_watchdog_nan_rollback_resumes_bitwise(tmp_path):
    """The self-healing acceptance: kill a chunk with a poisoned carry;
    the watchdog rolls back and the recovered trajectory is BITWISE the
    uninterrupted reference run."""
    ref = _twin(tmp_path / "ref")
    for _ in range(3):
        k_ref = ref.step_chunk()

    srv = _twin(tmp_path / "wd",
                watchdog=WatchdogConfig(max_retries=2, backoff_s=0.0,
                                        ckpt_every_chunks=1))
    srv.step_chunk()
    # poison between chunks: the next guarded chunk must trip + recover
    srv.state = srv.state._replace(U=srv.state.U.at[:, 0].set(jnp.nan))
    srv.step_chunk()
    k = srv.step_chunk()
    assert any("GuardViolation" in line for line in srv.fault_history)
    assert srv.t == ref.t
    assert k == k_ref, "recovered KPI summary diverged from reference"
    for a, b in zip(jax.tree_util.tree_leaves(srv.state),
                    jax.tree_util.tree_leaves(ref.state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_watchdog_survives_corrupt_latest_checkpoint(tmp_path):
    """Rollback falls through a corrupted newest step to the previous
    valid one and still resumes on the uninterrupted trajectory."""
    ref = _twin(tmp_path / "ref")
    for _ in range(3):
        ref.step_chunk()

    srv = _twin(tmp_path / "wd",
                watchdog=WatchdogConfig(max_retries=2, backoff_s=0.0,
                                        ckpt_every_chunks=1))
    srv.step_chunk()
    srv.step_chunk()
    _corrupt(srv.ckpt_dir, srv.t)                # newest checkpoint bad
    srv.state = srv.state._replace(U=srv.state.U.at[:, 0].set(jnp.nan))
    # rollback skips the corrupt step_20 to step_10; the recovery chunk
    # re-runs [10, 20), so one more chunk reaches the reference's t=30
    srv.step_chunk()
    assert srv.t == ref.t - srv.chunk_tti
    assert any("rolled back to t=10" in line for line in srv.fault_history)
    srv.step_chunk()
    assert srv.t == ref.t
    for a, b in zip(jax.tree_util.tree_leaves(srv.state),
                    jax.tree_util.tree_leaves(ref.state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_watchdog_chunk_timeout_recovers(tmp_path):
    """A hung chunk is abandoned at the wall-clock timeout, rolled back
    and re-run -- and the abandoned attempt's late result must never
    clobber the recovered trajectory (generation fencing)."""
    # warm the compile cache un-guarded, then arm the watchdog: the
    # timeout must measure a chunk, not the first-call compilation
    srv = _twin(tmp_path)
    srv.step_chunk()
    srv.watchdog = WatchdogConfig(max_retries=2, backoff_s=0.0,
                                  chunk_timeout_s=2.0,
                                  ckpt_every_chunks=1)
    srv.checkpoint()                             # rollback target
    t0 = srv.t
    real, armed = srv._chunk, {"on": True}

    def slow(static, state, power, fairness):
        if armed["on"]:
            armed["on"] = False
            import time as _t
            _t.sleep(4.0)
        return real(static, state, power, fairness)

    srv._chunk = slow
    srv.step_chunk()
    assert any("ChunkTimeout" in line for line in srv.fault_history)
    assert srv.t == t0 + srv.chunk_tti
    # the abandoned worker wakes mid-service and must be fenced off:
    # serve more chunks across its wake-up, then check continuity
    expect = srv.t
    for _ in range(3):
        import time as _t
        _t.sleep(0.6)
        srv.step_chunk()
        expect += srv.chunk_tti
        assert srv.t == expect, "an abandoned chunk clobbered the state"


def test_watchdog_gives_up_gracefully(tmp_path):
    srv = _twin(tmp_path,
                watchdog=WatchdogConfig(max_retries=1, backoff_s=0.0))
    srv.step_chunk()

    def explode(*a):
        raise RuntimeError("persistent kernel failure")

    srv._chunk = explode
    with pytest.raises(TwinServerDown) as ei:
        srv.step_chunk()
    assert len(ei.value.history) >= 2
    assert "persistent kernel failure" in str(ei.value)


def test_watchdog_degrades_pallas_to_xla(tmp_path):
    """A genuine chunk exception under inc_backend='auto' walks the
    degradation ladder: the chunk program is rebuilt on the XLA route
    (which also clears the injected failure) and serving continues."""
    srv = _twin(tmp_path, radio_mode="incremental", inc_backend="auto",
                watchdog=WatchdogConfig(max_retries=2, backoff_s=0.0,
                                        ckpt_every_chunks=1),
                params_kw=dict(mobility_step_m=10.0,
                               mobility_move_frac=0.25))
    t0 = srv.t
    srv.step_chunk()

    def explode(*a):
        raise RuntimeError("fused kernel fell over")

    srv._chunk = explode
    k = srv.step_chunk()                         # degrade + rollback
    assert srv.inc_backend == "xla"
    assert any("degrading" in line for line in srv.fault_history)
    assert srv.t == t0 + 2 * srv.chunk_tti
    assert all(math.isfinite(v) for v in k.values())


def test_twin_serves_fault_kpis(tmp_path):
    """A faulted twin (scenario-resolved FaultConfig) surfaces the
    outage KPIs in its chunk summaries and checkpoints/restores the
    fault leaf bitwise."""
    base = scenarios.make_scenario("outage_storm", n_ues=32, n_cells=6,
                                   faults=STORM)
    churn = ChurnConfig(arrival_rate_hz=300.0, mean_lifetime_s=0.2,
                        max_arrivals_per_tti=4)
    srv = TwinServer(CRRM(base), churn, chunk_tti=15,
                     ckpt_dir=str(tmp_path))
    k1 = srv.step_chunk()
    assert "mean_cells_down" in k1 and "reattach_events" in k1
    srv.checkpoint()
    k2 = srv.step_chunk()
    cs = np.asarray(srv.state.cell_state)
    srv.restore()
    k2b = srv.step_chunk()
    assert k2 == k2b, "restored faulted twin diverged"
    np.testing.assert_array_equal(np.asarray(srv.state.cell_state), cs)
