"""Entry path: many independent drops of one deployment in one program.

``jax.vmap`` of the simulator's own ``episode_fns(...).rollout`` over
``n_drops`` drops.  Each drop has its own UE field, fading and episode
key, made on the device from the seed by the topology-resampling reset
of ``repro.env.CrrmEnv`` (one compiled program for all drops).  Each
timed call rolls every drop ``chunk_tti`` TTIs from the previous call's
state; each drop's TTI counts as one simulated TTI.
"""
from __future__ import annotations

import jax
import numpy as np

from bench.lib import check, reference
from bench.lib.harness import bench_key, param_seed


class Drops:
    span = "drops"

    def __init__(self, ctx):
        from repro.core.params import CRRM_parameters
        from repro.env import CrrmEnv
        a = ctx.args
        self.n_tti, self.n_drops = int(a["chunk_tti"]), int(a["n_drops"])
        fns_kw = dict(a.get("episode_fns", {}))
        self.per_tti_fading = bool(fns_kw.get("per_tti_fading", False))
        self.params = dict(ctx.params)
        env = CrrmEnv(CRRM_parameters(**ctx.params,
                                      seed=param_seed(ctx.seed)),
                      resample_topology=True, **fns_kw)
        keys = jax.random.split(bench_key(ctx.seed), self.n_drops)
        drops, _ = env.reset_batch(keys)
        self.static, self.state = drops.static, drops.ep
        self.U0, self.drop_keys = np.asarray(self.state.U), keys
        fns = env.sim.episode_fns(**fns_kw)
        n = self.n_tti
        self.program = jax.jit(jax.vmap(
            lambda static, state: fns.rollout(static, state, n)))
        self.state, tput = self.program(self.static, self.state)
        jax.block_until_ready(tput)
        self.rng, self.calls, self.kept = ctx.rng, 0, None
        self.work = {"tti_per_call": self.n_tti * self.n_drops}
        self.failed = 0

    def call(self) -> int:
        s_in = self.state
        self.state, tput = self.program(self.static, s_in)
        jax.block_until_ready((self.state, tput))
        self.calls += 1
        if self.rng.random() * self.calls < 1.0:
            self.kept = (s_in, self.state, tput)
        return self.n_tti * self.n_drops

    def finish(self) -> None:
        self.static = self.state = self.program = None

    def sample(self) -> check.Sample:
        s_in, s_out, tput = self.kept
        rc = reference.ref_cfg(self.params,
                               per_tti_fading=self.per_tti_fading,
                               churn=None)
        return check.Sample(rc=rc, s0=check.as_ref_state(s_in),
                            n_tti=self.n_tti, batched=True,
                            prog=check.outputs(s_out, tput), U0=self.U0,
                            drop_keys=self.drop_keys,
                            h_ut_m=self.params["h_ut_m"])


def make(ctx):
    return Drops(ctx)
