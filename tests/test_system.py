"""End-to-end behaviour tests for the paper's system.

A CRRM network lives through a mobility episode with power reconfiguration;
the smart engine must agree with the full-recompute engine at every step,
while doing strictly less work (the paper's core claim).
"""
import jax
import numpy as np

from repro.core.crrm import CRRM
from repro.core.params import CRRM_parameters
from repro.sim.mobility import random_moves


def test_full_episode_smart_vs_full():
    common = dict(n_ues=60, n_cells=21, n_sectors=3, n_subbands=2,
                  pathloss_model_name="UMa", power_W=10.0, seed=4,
                  fairness_p=0.3)
    smart = CRRM(CRRM_parameters(smart=True, **common))
    full = CRRM(CRRM_parameters(smart=False, **common))
    key = jax.random.PRNGKey(1)
    for step in range(5):
        key, k = jax.random.split(key)
        idx, xyz = random_moves(k, 60, 6, 3000.0)
        for sim in (smart, full):
            sim.move_UEs(np.asarray(idx), np.asarray(xyz))
        if step == 2:  # interference coordination event
            for sim in (smart, full):
                sim.set_cell_power(0, 0, 0.1)
        np.testing.assert_allclose(
            np.asarray(smart.get_UE_throughputs()),
            np.asarray(full.get_UE_throughputs()), rtol=1e-4, atol=1e-3)
    # the smart engine did row updates where the full engine recomputed
    s_counts = smart.update_counts()
    f_counts = full.update_counts()
    assert s_counts["D"][1] > 0          # row updates happened
    assert f_counts["D"][1] == 0         # control never row-updates
    assert s_counts["D"][0] < f_counts["D"][0]
