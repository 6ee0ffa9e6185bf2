"""Entry path: a live twin, ``TwinServer.step_chunk`` in a closed loop.

The client asks for the next chunk when it holds the last KPI summary.
The server runs under its watchdog (guard readback every chunk, an
automatic checkpoint every ``ckpt_every_chunks``) with its checkpoints in
a temporary directory that the run removes.  Set-up warms the chunk
program, the guard and the checkpoint path over ``warm_chunks`` chunks.
"""
from __future__ import annotations

import shutil
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import check, reference
from bench.lib.harness import bench_key, param_seed


def _copy(tree):
    return jax.tree_util.tree_map(
        lambda x: None if x is None else jnp.copy(x), tree)


class Twin:
    span = "step_chunk"

    def __init__(self, ctx):
        from repro.core.crrm import CRRM
        from repro.core.params import CRRM_parameters
        from repro.robust.watchdog import WatchdogConfig
        from repro.sim.mobility import ChurnConfig
        from repro.twin import TwinServer
        a = ctx.args
        self.params, self.churn = dict(ctx.params), dict(a["churn"])
        self.n_tti = int(a["chunk_tti"])
        self.per_tti_fading = bool(a.get("per_tti_fading", False))
        self.ckpt_dir = tempfile.mkdtemp(prefix="bench-twin-")
        sim = CRRM(CRRM_parameters(**ctx.params, seed=param_seed(ctx.seed)))
        self.srv = TwinServer(
            sim, ChurnConfig(**self.churn), chunk_tti=self.n_tti,
            ckpt_dir=self.ckpt_dir, per_tti_fading=self.per_tti_fading,
            key=bench_key(ctx.seed),
            watchdog=WatchdogConfig(**a.get("watchdog", {})))
        self.U0 = np.asarray(self.srv.state.U)
        self.drop_keys = jax.random.PRNGKey(param_seed(ctx.seed))
        for _ in range(int(a["warm_chunks"])):
            self.srv.step_chunk()
        _copy(self.srv.state)        # the sampled chunk's state copies
        self.rng, self.calls, self.kept = ctx.rng, 0, None
        self.work = {"tti_per_call": self.n_tti}

    @property
    def program(self):
        return self.srv._chunk

    @program.setter
    def program(self, fn):
        self.srv._chunk = fn

    @property
    def failed(self) -> int:
        return len(self.srv.fault_history)

    def call(self) -> int:
        self.calls += 1
        keep = self.rng.random() * self.calls < 1.0
        s_in = _copy(self.srv.state) if keep else None
        kpis = self.srv.step_chunk()
        if keep:
            self.kept = (s_in, _copy(self.srv.state), self.srv.last_tput,
                         kpis)
        return self.n_tti

    def finish(self) -> None:
        shutil.rmtree(self.ckpt_dir, ignore_errors=True)

    def sample(self) -> check.Sample:
        s_in, s_out, tput, kpis = self.kept
        rc = reference.ref_cfg(self.params,
                               per_tti_fading=self.per_tti_fading,
                               churn=self.churn)
        return check.Sample(rc=rc, s0=check.as_ref_state(s_in),
                            n_tti=self.n_tti, batched=False,
                            prog=check.outputs(s_out, tput, kpis),
                            U0=self.U0, drop_keys=self.drop_keys,
                            h_ut_m=self.params["h_ut_m"])


def make(ctx):
    return Twin(ctx)
