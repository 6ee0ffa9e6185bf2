"""The engine's switch regimes, as the contract tests sweep them.

A regime is a set of ``episode_fns`` switches laid over a registry
scenario's own preset.  :func:`cases` crosses the scenarios with a test's
regimes, leaving out the pairs whose preset already runs the regime, and
:func:`start` seeds the starting state the regime's switches need.
"""
import pytest

from repro.mac import engine as mac_engine
from repro.sim import scenarios
from repro.sim.faults import FaultConfig
from repro.sim.mobility import ChurnConfig

#: mean occupancy 600 Hz x 10 ms = 6 live UEs, under the 24-slot capacity
CHURN = ChurnConfig(arrival_rate_hz=600.0, mean_lifetime_s=0.01,
                    max_arrivals_per_tti=4)
FAULTS = FaultConfig(outage_rate_hz=20.0, mean_outage_s=0.03,
                     sleep_rate_hz=20.0, mean_sleep_s=0.02)
#: a quarter of the UEs walk per TTI, in the window-mover draw
MOVERS = dict(mobility_step_m=25.0, mobility_move_frac=0.25)
CHURN_FAULTS = {"churn": dict(churn=CHURN), "faults": dict(faults=FAULTS),
                "churn+faults": dict(churn=CHURN, faults=FAULTS)}

#: pairs whose preset already runs the regime's switches: dense_urban_twin
#: is incremental, outage_storm carries its own fault storm (whose
#: dense == incremental case is tests/test_faults.py's)
PRESET = {("dense_urban_twin", "incremental"), ("outage_storm", "faults"),
          ("outage_storm", "churn+faults")}


def cases(regimes, base):
    """(scenario, regime) parameters; cases of the ``base`` regime keep
    the scenario's name as their id."""
    return [pytest.param(name, regime,
                         id=name if regime == base else f"{name}-{regime}")
            for regime in regimes for name in scenarios.scenario_names()
            if (name, regime) not in PRESET]


def start(sim, key, switches):
    """The episode's starting state under ``switches``."""
    state = sim.init_episode_state(key)
    if "churn" in switches:
        state = mac_engine.seed_churn_state(state, sim.episode_static(),
                                            sim.params)
    return state
