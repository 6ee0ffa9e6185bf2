"""Chaos drill: prove the twin survives the failures we can script.

Chaos engineering's core claim is that recovery code you never exercise
is recovery code that does not work.  This module is the scripted drill
CI runs on every push (``python -m repro.robust.chaos --smoke``): a
:class:`~repro.twin.server.TwinServer` under the ``outage_storm`` cell
fault process and an armed watchdog is subjected to, in order,

1. **a poisoned carry** -- NaN written straight into the serving state's
   PF average between chunks (the guard must trip, the watchdog must
   roll back, and the resumed trajectory must be the uninterrupted one);
2. **a crashing chunk** -- the compiled chunk program replaced by one
   that raises (the forced-kernel-failure case: recovery must rebuild
   on the degraded ``xla`` route and keep serving);
3. **a corrupted latest checkpoint** -- bytes flipped in the newest
   step's leaf file (rollback must fall through to the previous valid
   step, not resurrect garbage).

The drill asserts the server recovers from all three, that the final KPI
summary is finite, and that the failure history recorded every injected
fault.  Exit code 0 + the ``CHAOS_OK`` line is the CI contract
(DESIGN.md §Fault-injection-and-self-healing).
"""
from __future__ import annotations

import math
import os
import tempfile

import jax.numpy as jnp

from repro.robust.watchdog import WatchdogConfig
from repro.train import checkpoint as ckpt


def _corrupt_latest(ckpt_dir: str) -> int:
    """Flip bytes in the newest step's first leaf; return that step."""
    step = ckpt.latest_step(ckpt_dir)
    leaf = os.path.join(ckpt_dir, f"step_{step:010d}", "00000.npy")
    with open(leaf, "r+b") as f:
        f.seek(-8, os.SEEK_END)
        f.write(b"\xff" * 8)
    return step


def drill(ckpt_dir: str, n_ues: int = 64, n_cells: int = 7,
          chunk: int = 20, verbose: bool = True) -> dict:
    """Run the full injection sequence; return the final KPI summary.

    Asserts internally -- an exception means the drill failed.  Small by
    default (CI-sized); the injections scale with nothing, so a larger
    twin drills identically.
    """
    from repro.core.crrm import CRRM
    from repro.sim.faults import FaultConfig
    from repro.sim.mobility import ChurnConfig
    from repro.sim.scenarios import make_scenario
    from repro.twin.server import TwinServer

    say = print if verbose else (lambda *a: None)
    sim = CRRM(make_scenario(
        "outage_storm", n_ues=n_ues, n_cells=n_cells,
        faults=FaultConfig(outage_rate_hz=8.0, mean_outage_s=0.02,
                           sleep_rate_hz=8.0, mean_sleep_s=0.02)))
    churn = ChurnConfig(arrival_rate_hz=300.0, mean_lifetime_s=0.2,
                        max_arrivals_per_tti=4)
    srv = TwinServer(
        sim, churn, chunk_tti=chunk, ckpt_dir=ckpt_dir, keep_last=4,
        watchdog=WatchdogConfig(max_retries=3, backoff_s=0.01,
                                ckpt_every_chunks=1))

    k = srv.step_chunk()                       # healthy storm chunk
    assert k["mean_cells_down"] > 0.0, "fault storm produced no outages"
    say(f"[chaos] storm serving: t={srv.t} "
        f"mean_cells_down={k['mean_cells_down']:.2f} "
        f"reattach_events={k['reattach_events']:.0f}")

    # -- injection 1: poisoned carry ------------------------------------
    # NaN positions survive the chunk (mobility is an additive walk) and
    # spread through pathgain -> SINR -> throughput; every row is
    # poisoned so churn rebirths (which redraw a slot's position) cannot
    # heal the carry before the guard sees it
    t_before = srv.t
    srv.state = srv.state._replace(
        U=srv.state.U.at[:, 0].set(jnp.nan))
    k = srv.step_chunk()                       # guard -> rollback -> retry
    assert srv.t == t_before + chunk, "NaN recovery lost TTIs"
    assert any("GuardViolation" in line for line in srv.fault_history), \
        "guard never tripped on the injected NaN"
    assert all(math.isfinite(v) for v in k.values()), \
        "post-recovery KPIs not finite"
    say(f"[chaos] survived injected NaN: t={srv.t}, "
        f"{len(srv.fault_history)} history lines")

    # -- injection 2: crashing chunk program ----------------------------
    real_chunk, boom = srv._chunk, {"armed": True}

    def _exploding(static, state, power, fairness):
        if boom["armed"]:
            boom["armed"] = False
            raise RuntimeError("injected kernel failure")
        return real_chunk(static, state, power, fairness)

    srv._chunk = _exploding
    t_before = srv.t
    k = srv.step_chunk()
    assert srv.t == t_before + chunk, "crash recovery lost TTIs"
    assert any("injected kernel failure" in line
               for line in srv.fault_history), "crash not recorded"
    say(f"[chaos] survived injected chunk crash: t={srv.t}")
    srv._chunk = real_chunk

    # -- injection 3: corrupted latest checkpoint -----------------------
    bad_step = _corrupt_latest(ckpt_dir)
    srv.state = srv.state._replace(
        U=srv.state.U.at[:, 0].set(jnp.nan))          # force a rollback
    k = srv.step_chunk()
    assert any("rolled back to t=" in line
               for line in srv.fault_history), "no rollback recorded"
    last_rb = [line for line in srv.fault_history if "rolled back" in line][-1]
    assert f"t={bad_step}" not in last_rb, \
        "rollback resurrected the corrupted checkpoint"
    assert all(math.isfinite(v) for v in k.values())
    say(f"[chaos] survived corrupt latest checkpoint "
        f"(step {bad_step} skipped): {last_rb}")

    # the drill must end able to serve cleanly
    k = srv.step_chunk()
    assert all(math.isfinite(v) for v in k.values())
    assert k["served_mbits"] > 0.0
    return k


def main(argv=None) -> None:
    import argparse

    from repro.compile_cache import enable_compile_cache
    from repro.obs.telemetry import format_summary

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: tiny twin, full injection sequence")
    ap.add_argument("--ues", type=int, default=64)
    ap.add_argument("--cells", type=int, default=7)
    ap.add_argument("--chunk", type=int, default=20)
    args = ap.parse_args(argv)
    enable_compile_cache()

    with tempfile.TemporaryDirectory() as td:
        kpis = drill(td, n_ues=args.ues, n_cells=args.cells,
                     chunk=args.chunk)
    print(format_summary(kpis))
    print("CHAOS_OK: twin survived NaN injection, chunk crash and "
          "checkpoint corruption")


if __name__ == "__main__":
    main()
