"""Entry path: ``CRRM(params).episode_fns(...).rollout``, state threaded.

Set-up builds the simulator (the program's graph build over every link),
takes the episode's static inputs and starting state, drops the graph,
compiles the rollout of ``chunk_tti`` TTIs and runs it once.  Each timed
call is one rollout of ``chunk_tti`` TTIs from the previous call's state
(closed loop).  ``expect_kernel`` names a Pallas kernel that the compiled
program must contain on the chip.
"""
from __future__ import annotations

import jax
import numpy as np

from bench.lib import check, reference
from bench.lib.harness import bench_key, param_seed


class Rollout:
    span = "rollout"

    def __init__(self, ctx):
        from repro.core.crrm import CRRM
        from repro.core.params import CRRM_parameters
        a = ctx.args
        self.n_tti = int(a["chunk_tti"])
        self.params = dict(ctx.params)
        sim = CRRM(CRRM_parameters(**ctx.params, seed=param_seed(ctx.seed)))
        fns = sim.episode_fns(**a.get("episode_fns", {}))
        self.static = sim.episode_static()
        self.state = sim.init_episode_state(bench_key(ctx.seed))
        self.U0 = np.asarray(self.state.U)
        self.drop_keys = jax.random.PRNGKey(param_seed(ctx.seed))
        n_ues, n_cells = sim.n_ues, sim.n_cells
        del sim                      # the graph's link matrices
        self.program = fns.rollout.lower(self.static, self.state,
                                         self.n_tti).compile()
        m = self.program.memory_analysis()
        if m is not None:
            ctx.log(f"rollout memory_analysis: argument "
                    f"{m.argument_size_in_bytes / 2**20:.1f} MiB, output "
                    f"{m.output_size_in_bytes / 2**20:.1f} MiB, temp "
                    f"{m.temp_size_in_bytes / 2**20:.1f} MiB")
        kernel = a.get("expect_kernel")
        if kernel and jax.default_backend() == "tpu" and (
                "tpu_custom_call" not in self.program.as_text()):
            raise SystemExit(f"the compiled rollout holds no Pallas kernel "
                             f"({kernel} expected)")
        self.state, tput = self.program(self.static, self.state)
        jax.block_until_ready(tput)
        self.rng, self.calls, self.kept = ctx.rng, 0, None
        frac = self.params.get("mobility_move_frac") or 1.0
        self.work = {"tti_per_call": self.n_tti, "kernel": kernel,
                     "rows": max(1, int(round(frac * n_ues))),
                     "cells": n_cells,
                     "chunks": (self.params.get("n_subbands", 1)
                                * self.params.get("n_rb_subbands", 1)),
                     "sectors": self.params.get("n_sectors", 1)}
        self.failed = 0

    def call(self) -> int:
        s_in = self.state
        self.state, tput = self.program(self.static, s_in)
        jax.block_until_ready((self.state, tput))
        self.calls += 1
        if self.rng.random() * self.calls < 1.0:
            self.kept = (s_in, self.state, tput)
        return self.n_tti

    def finish(self) -> None:
        self.static = self.state = self.program = None

    def sample(self) -> check.Sample:
        s_in, s_out, tput = self.kept
        rc = reference.ref_cfg(self.params, per_tti_fading=False, churn=None)
        return check.Sample(rc=rc, s0=check.as_ref_state(s_in),
                            n_tti=self.n_tti, batched=False,
                            prog=check.outputs(s_out, tput), U0=self.U0,
                            drop_keys=self.drop_keys,
                            h_ut_m=self.params["h_ut_m"])


def make(ctx):
    return Rollout(ctx)
