"""The digital-twin simulation server: continuous runs, served in chunks.

A network digital twin is not an episode: it runs for as long as the live
network it mirrors, absorbs measurement-driven control updates while
running, and must survive process death without losing (or worse,
perturbing) its trajectory.  :class:`TwinServer` provides exactly that over
the existing pure episode engine (DESIGN.md §Digital-twin-serving):

* **Chunked stepping** -- one jit-compiled ``rollout`` of ``chunk_tti``
  TTIs per call, with the carried :class:`~repro.mac.engine.EpisodeState`
  buffer *donated* back to the next chunk: steady-state serving allocates
  no new state per chunk.  Because every per-TTI PRNG stream folds on the
  *absolute* TTI counter (``radio.tti_keys`` / ``radio.churn_keys``), the
  trajectory is chunk-partition-invariant: N chunks of M TTIs reproduce
  one N*M-TTI run bitwise.
* **Birth-death churn** -- the engine's capacity-padded active-mask regime
  (``sim.mobility.ChurnConfig``): UEs arrive and depart inside the
  compiled scan, no retracing.
* **Live control** -- the per-cell power matrix and the scheduler fairness
  exponent are *always* passed as traced arguments of the chunk program,
  so :meth:`set_power` / :meth:`set_fairness` take effect at the next
  chunk boundary with **zero recompilation** (asserted with
  ``obs.profile.CompileCounter`` in tests/test_twin.py).
* **Checkpoint/restore** -- ``train.checkpoint`` (atomic, keep-k,
  optionally async) snapshots the full serving tuple: episode state +
  PRNG stream + TTI counter + the live controls.  A server killed
  mid-run and restored continues *bitwise* on the uninterrupted
  trajectory -- the resume-equivalence contract (tested in
  tests/test_twin.py, smoke-checked in CI via ``python -m
  repro.twin.server --smoke``).
* **Fault injection** -- pass ``faults=sim.faults.FaultConfig(...)`` (or
  bake it into the scenario preset, e.g. ``outage_storm``) and cells walk
  a Markov outage/sleep chain *inside* the compiled chunk; the twin's
  KPI summaries then carry ``mean_cells_down`` / ``reattach_events``.
* **Self-healing** -- arm ``watchdog=WatchdogConfig(...)`` (or ``True``)
  and :meth:`step_chunk` becomes a guarded loop
  (DESIGN.md §Fault-injection-and-self-healing): each chunk runs under an
  optional wall-clock timeout, the resulting carry is validated by the
  fused ``robust.guard.carry_ok`` check, and success auto-checkpoints on
  a cadence.  On NaN, exception or timeout the server recovers: if the
  failure is a genuine chunk exception (not a guard/timeout verdict)
  and a fused incremental backend is armed, it first degrades
  ``pallas -> xla``, rebuilding the chunk program, and logs a warning
  that names the exception (a run that degraded is not a Pallas run);
  it then rolls back to the newest checkpoint that still validates
  (``restore_latest_valid`` -- a corrupted latest step falls through to
  the previous good one), sleeps an exponential backoff and retries;
  ``max_retries`` consecutive failures stop the server gracefully with
  a :class:`~repro.robust.watchdog.TwinServerDown`
  carrying the full failure history.  Because every per-TTI PRNG stream
  folds on the absolute TTI counter, a recovered twin resumes *bitwise*
  on the uninterrupted trajectory (tests/test_faults.py; chaos drill:
  ``python -m repro.robust.chaos --smoke``).
"""
from __future__ import annotations

import logging
import time

import jax
import jax.numpy as jnp

from repro.mac import engine as mac_engine
from repro.obs import telemetry as obs_telemetry
from repro.robust import guard as robust_guard
from repro.robust.watchdog import (GuardViolation, TwinFault,
                                   TwinServerDown, WatchdogConfig,
                                   run_with_timeout)
from repro.sim.mobility import ChurnConfig
from repro.train import checkpoint as ckpt

log = logging.getLogger(__name__)


class TwinServer:
    """A continuously-running simulation twin, stepped in compiled chunks.

    ``sim`` is a built ``CRRM``; ``churn`` the birth-death process config
    (its ``max_arrivals_per_tti`` is also the per-TTI birth dirty-row
    budget).  ``chunk_tti`` sets the serving granularity: KPI summaries
    stream once per chunk, and control updates land at chunk boundaries.
    ``ckpt_dir`` enables :meth:`checkpoint` / :meth:`restore`.

    ``faults`` arms the in-scan cell fault process (defaults to the
    scenario's ``params.faults``; pass ``0`` to force it off).
    ``inc_backend`` routes the incremental radio mode's dirty-row
    recompute exactly as in ``episode_fns``; under a watchdog it is also
    the degradation ladder's starting rung.  ``watchdog`` (a
    :class:`~repro.robust.watchdog.WatchdogConfig`, or ``True`` for the
    defaults) turns :meth:`step_chunk` into the guarded self-healing loop
    -- it requires ``ckpt_dir`` (rollback needs somewhere to roll back
    to) and writes an initial checkpoint at t=0.
    """

    def __init__(self, sim, churn: ChurnConfig, *, chunk_tti: int = 100,
                 ckpt_dir=None, keep_last: int = 3,
                 per_tti_fading: bool = False, radio_mode=None, key=None,
                 faults=None, inc_backend=None, watchdog=None):
        self.sim, self.churn, self.chunk_tti = sim, churn, int(chunk_tti)
        self.ckpt_dir, self.keep_last = ckpt_dir, keep_last
        if faults is None:
            faults = getattr(sim.params, "faults", None)
        self.faults = faults or None
        self._fns_kw = dict(per_tti_fading=per_tti_fading,
                            radio_mode=radio_mode, telemetry=True,
                            churn=churn, faults=faults)
        self.inc_backend = inc_backend
        self._build(inc_backend)
        self.static = sim.episode_static()
        state = sim.init_episode_state(key)
        state = mac_engine.seed_churn_state(
            state, self.static, sim.params, per_tti_fading=per_tti_fading)
        if self.faults is not None:
            # seed the fault leaf eagerly so every checkpoint of this
            # server shares one tree structure (restore reads structure)
            state = mac_engine.seed_fault_state(state, sim.params.n_cells)
        self.state = state
        # live controls, always traced chunk inputs: updating them swaps
        # an array, never the compiled program
        self.power = jnp.asarray(self.static.P)
        self.fairness = jnp.float32(sim.params.fairness_p)

        if watchdog is True:
            watchdog = WatchdogConfig()
        self.watchdog = watchdog
        self.fault_history: list = []
        self._chunks_since_ckpt = 0
        # bumped by every rollback/restore: a timed-out chunk abandoned
        # on its worker thread must never commit a result computed from
        # pre-rollback state
        self._gen = 0
        if watchdog is not None:
            if ckpt_dir is None:
                raise ValueError("watchdog requires ckpt_dir: rollback "
                                 "needs a checkpoint to roll back to")
            self.checkpoint()            # the t=0 rollback target

    def _build(self, inc_backend) -> None:
        """(Re)build the episode fns + chunk program for ``inc_backend``.

        Called at construction and again by the watchdog's degradation
        ladder (``pallas -> xla``): the serving state is untouched, only
        the compiled program changes, so a degraded twin continues the
        same trajectory (dense == incremental == fused is an engine
        equivalence contract).
        """
        self.inc_backend = inc_backend
        self.fns = self.sim.episode_fns(inc_backend=inc_backend,
                                        **self._fns_kw)
        rollout, n = self.fns.rollout, self.chunk_tti

        def _chunk(static, state, power, fairness):
            return rollout(static, state, n, power, fairness)

        # donate the carried state: steady-state serving reuses the same
        # device buffers chunk after chunk
        self._chunk = jax.jit(_chunk, donate_argnums=(1,))

    # ------------------------------------------------------------- stepping
    @property
    def t(self) -> int:
        """The absolute TTI counter (drives every per-TTI PRNG fold)."""
        return int(self.state.t)

    def step_chunk(self):
        """Advance ``chunk_tti`` TTIs; return the chunk's KPI summary dict.

        The summary is ``obs.telemetry.summarize`` over the chunk's
        per-TTI telemetry stack plus the serving counters (``t``,
        ``active_ues``).  The returned dict is plain host data -- what a
        dashboard or calibration loop consumes.

        With a ``watchdog`` armed this is the guarded loop: timeout-
        wrapped chunk, fused carry validation, auto-checkpoint cadence,
        and on failure the degrade/rollback/backoff/retry ladder
        (module docstring) -- raising
        :class:`~repro.robust.watchdog.TwinServerDown` only after
        ``max_retries`` consecutive recoveries also failed.
        """
        if self.watchdog is None:
            return self._step_chunk_raw()
        return self._step_chunk_guarded()

    def _step_chunk_raw(self):
        gen = self._gen
        state, tput, telem = self._chunk(
            self.static, self.state, self.power, self.fairness)
        if gen != self._gen:
            # a rollback superseded this attempt while it ran (it timed
            # out and was abandoned): its result must not clobber the
            # restored state the retry is serving from
            raise RuntimeError("stale chunk result discarded "
                               "(superseded by a rollback)")
        self.state = state
        kpis = obs_telemetry.summarize(telem, tti_s=self.sim.params.tti_s)
        kpis["t"] = float(self.state.t)
        kpis["active_ues"] = float(self.state.active.sum())
        self.last_tput, self.last_telem = tput, telem
        return kpis

    def _step_chunk_guarded(self):
        wd = self.watchdog
        delay = wd.backoff_s
        for attempt in range(wd.max_retries + 1):
            try:
                kpis = run_with_timeout(self._step_chunk_raw,
                                        wd.chunk_timeout_s)
                if not bool(robust_guard.carry_ok(self.state)):
                    raise GuardViolation(
                        "carry invariants violated after chunk: "
                        + "; ".join(robust_guard.carry_violations(self.state)
                                    or ["(guard tripped, no host detail)"]))
            except Exception as e:  # noqa: BLE001 -- the watchdog's job
                self.fault_history.append(
                    f"attempt {attempt}: {type(e).__name__}: {e}")
                if (not isinstance(e, TwinFault)
                        and self.inc_backend in ("pallas", "auto")):
                    # degradation ladder: the fused chunk failed -- rebuild
                    # on the XLA route before retrying (same trajectory,
                    # different program), and say so
                    log.warning("twin chunk failed on inc_backend=%r "
                                "(%s: %s); rebuilding on 'xla'",
                                self.inc_backend, type(e).__name__, e)
                    self.fault_history.append(
                        f"degrading inc_backend={self.inc_backend!r} "
                        "-> 'xla'")
                    self._build("xla")
                step = self._rollback()
                self.fault_history.append(f"rolled back to t={step}")
                if attempt < wd.max_retries:
                    time.sleep(delay)
                    delay *= wd.backoff_factor
            else:
                self._chunks_since_ckpt += 1
                if self._chunks_since_ckpt >= wd.ckpt_every_chunks:
                    self.checkpoint()
                    self._chunks_since_ckpt = 0
                return kpis
        raise TwinServerDown(
            f"{wd.max_retries + 1} consecutive chunk attempts failed at "
            f"t={self.t}; stopping gracefully", history=self.fault_history)

    def _rollback(self) -> int:
        """Restore the newest *valid* checkpoint (skipping corrupt steps).

        Only the current tree's structure is read, never its leaf values,
        so rolling back over buffers invalidated by a failed donated
        chunk is safe -- restore rebuilds fresh device arrays from the
        host snapshot.
        """
        tree, _, step = ckpt.restore_latest_valid(
            self.ckpt_dir, self._tree())
        self._gen += 1
        self.state, self.power = tree["state"], tree["power"]
        self.fairness = tree["fairness"]
        self._chunks_since_ckpt = 0
        return step

    def serve(self, n_chunks: int):
        """Generator: stream ``n_chunks`` KPI summaries, one per chunk."""
        for _ in range(n_chunks):
            yield self.step_chunk()

    # ------------------------------------------------------- live controls
    def set_power(self, P) -> None:
        """Swap the per-cell/(subband) tx power grid; next chunk uses it.

        Accepts the engine's resolved (n_cells, n_freq) grid.  A pure
        array swap: the chunk program traced ``power`` as an argument, so
        no recompilation happens.
        """
        self.power = jnp.asarray(P, jnp.float32)

    def set_fairness(self, p) -> None:
        """Swap the PF fairness exponent ``p``; next chunk uses it."""
        self.fairness = jnp.float32(p)

    # -------------------------------------------------- checkpoint/restore
    def _tree(self):
        # the full serving tuple: state (incl. PRNG key + TTI counter +
        # active mask + carried fading) and the live controls
        return {"state": self.state, "power": self.power,
                "fairness": self.fairness}

    def checkpoint(self, block: bool = True):
        """Snapshot the serving state at the current TTI (atomic, keep-k).

        ``block=False`` uses ``train.checkpoint.save_async``: leaves are
        snapshotted to host synchronously (so later donated-buffer reuse
        cannot corrupt the write) and the directory write happens on a
        daemon thread, returned for joining.
        """
        if self.ckpt_dir is None:
            raise ValueError("TwinServer built without ckpt_dir")
        step = self.t
        extra = {"chunk_tti": self.chunk_tti}
        if block:
            ckpt.save(self.ckpt_dir, step, self._tree(),
                      keep_last=self.keep_last, extra=extra)
            return step
        return ckpt.save_async(self.ckpt_dir, step, self._tree(),
                               keep_last=self.keep_last, extra=extra)

    def restore(self, step=None) -> int:
        """Rewind to a checkpointed TTI (default: the newest valid one).

        Restores state *and* controls, so the resumed trajectory is
        bitwise the uninterrupted one -- including any control updates
        that were live at checkpoint time.  Only the current tree's
        *structure* is read (never its leaf values), so restoring over
        donated buffers is safe.  With ``step=None`` a corrupt or
        truncated latest step falls back to the previous valid one
        (``train.checkpoint.restore_latest_valid``); an explicit ``step``
        raises ``CheckpointCorrupt`` if that step fails validation.
        """
        if self.ckpt_dir is None:
            raise ValueError("TwinServer built without ckpt_dir")
        if step is None:
            tree, _, step = ckpt.restore_latest_valid(
                self.ckpt_dir, self._tree())
        else:
            tree, _ = ckpt.restore(self.ckpt_dir, step, self._tree())
        self._gen += 1
        self.state, self.power = tree["state"], tree["power"]
        self.fairness = tree["fairness"]
        self._chunks_since_ckpt = 0
        return step


def _smoke(tmpdir: str, n_ues: int = 96, n_cells: int = 7,
           chunk: int = 25) -> None:
    """CI smoke: arrivals happen, one kill/restore cycle resumes bitwise."""
    import numpy as np

    from repro.core.crrm import CRRM
    from repro.core.params import CRRM_parameters

    sim = CRRM(CRRM_parameters(
        n_ues=n_ues, n_cells=n_cells, n_sectors=1, seed=7,
        pathloss_model_name="UMa", power_W=10.0, traffic_model="poisson",
        scheduler_policy="pf",
        traffic_params=dict(arrival_rate_hz=300.0,
                            packet_size_bits=12_000.0)))
    churn = ChurnConfig(arrival_rate_hz=400.0, mean_lifetime_s=0.15,
                        max_arrivals_per_tti=8)
    srv = TwinServer(sim, churn, chunk_tti=chunk, ckpt_dir=tmpdir)

    k1 = srv.step_chunk()
    srv.set_power(np.asarray(srv.power) * 1.1)   # live control update
    srv.checkpoint()
    k2 = srv.step_chunk()
    tail = np.asarray(srv.last_tput)
    final = jax.tree_util.tree_map(np.asarray, srv.state)

    srv.restore()                                # "kill" + resume
    k2b = srv.step_chunk()
    tail_b = np.asarray(srv.last_tput)
    final_b = jax.tree_util.tree_map(np.asarray, srv.state)

    assert k1["mean_active_ues"] < n_ues, "no departures ever happened"
    assert k1["served_mbits"] > 0.0
    np.testing.assert_array_equal(tail, tail_b)
    for a, b in zip(jax.tree_util.tree_leaves(final),
                    jax.tree_util.tree_leaves(final_b)):
        np.testing.assert_array_equal(a, b)
    assert k2 == k2b, "restored KPI summary diverged"
    print("twin smoke OK: t=%d active=%d served=%.3f Mbit" %
          (int(final.t), int(final.active.sum()), k2["served_mbits"]))


def main(argv=None) -> None:
    """CLI: run a twin server and stream KPI lines (or the CI smoke)."""
    import argparse
    import tempfile

    from repro.compile_cache import enable_compile_cache
    from repro.obs.telemetry import format_summary

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: tiny scenario, one restore cycle, "
                         "bitwise resume assertion")
    ap.add_argument("--ues", type=int, default=1000)
    ap.add_argument("--cells", type=int, default=19)
    ap.add_argument("--chunk", type=int, default=100)
    ap.add_argument("--chunks", type=int, default=10)
    ap.add_argument("--arrival-hz", type=float, default=2000.0)
    ap.add_argument("--lifetime-s", type=float, default=0.4)
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.smoke:
        with tempfile.TemporaryDirectory() as td:
            _smoke(td)
        return

    from repro.core.crrm import CRRM
    from repro.core.params import CRRM_parameters

    sim = CRRM(CRRM_parameters(
        n_ues=args.ues, n_cells=args.cells, n_sectors=1, seed=0,
        pathloss_model_name="UMa", power_W=10.0, traffic_model="poisson",
        scheduler_policy="pf",
        traffic_params=dict(arrival_rate_hz=300.0,
                            packet_size_bits=12_000.0)))
    churn = ChurnConfig(
        arrival_rate_hz=args.arrival_hz, mean_lifetime_s=args.lifetime_s,
        max_arrivals_per_tti=max(
            4, int(4 * args.arrival_hz * sim.params.tti_s)))
    srv = TwinServer(sim, churn, chunk_tti=args.chunk,
                     ckpt_dir=args.ckpt_dir)
    for i, kpis in enumerate(srv.serve(args.chunks)):
        print(f"chunk {i} (t={int(kpis.pop('t'))}):")
        print(format_summary(kpis))


if __name__ == "__main__":
    main()
