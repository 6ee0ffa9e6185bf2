"""Entry path: ``CRRM(params, mesh=mesh).episode_fns().rollout``.

The UE-sharded deployment: set-up builds a ``("ue",)`` mesh of the cell's
chips (of the devices there are, where there are fewer), builds the field
straight onto it (each chip draws and keeps its own rows; no device holds
an ``(n_ues, n_cells)`` array), compiles the sharded rollout of
``chunk_tti`` TTIs on the simulator's mesh and runs it once.  Each timed
call is one rollout from the previous call's state (closed loop).
``expect_kernel`` names a Pallas kernel that the compiled program must
contain on the chip.  ``work`` carries the engine's dirty-row budget per
shard and the shard count, as the program counted them while tracing,
and the names of the compiled program's collective instructions.

The call that the check draws keeps only its starting state, by
reference: from the second call on the device holds one state more than
the running one whichever call the seed draws, so ``peak_hbm_mib`` does
not depend on the seed.  ``finish()`` runs the compiled program once
more on that state for the outputs the check compares (the same program
on the same input), and ``sample()`` hands the reference single-device
copies of the state: a mesh must give one device's semantics.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh

from bench.drivers.rollout import Rollout
from bench.lib import check, reference
from bench.lib.harness import bench_key, param_seed
from bench.metrics.mesh_collective_ms_per_tti import collective_ops


class MeshRollout(Rollout):
    """``Rollout``'s timed call on the mesh; its own set-up and sample."""

    def __init__(self, ctx):
        from repro.core.crrm import CRRM
        from repro.core.params import CRRM_parameters
        from repro.mac import engine
        a = ctx.args
        self.n_tti = int(a["chunk_tti"])
        self.params = dict(ctx.params)
        devices = jax.devices()[:ctx.workload["chips"]]
        mesh = Mesh(np.asarray(devices), ("ue",))
        ctx.log(f"mesh {dict(mesh.shape)} over {len(devices)} "
                f"{devices[0].platform} device(s)")
        sim = CRRM(CRRM_parameters(**ctx.params, seed=param_seed(ctx.seed)),
                   mesh=mesh)
        fns = sim.episode_fns(**a.get("episode_fns", {}))
        self.static = sim.episode_static()
        self.state = sim.init_episode_state(bench_key(ctx.seed))
        self.U0 = np.asarray(self.state.U)
        self.drop_keys = jax.random.PRNGKey(param_seed(ctx.seed))
        n_ues, n_cells = sim.n_ues, sim.n_cells
        del sim
        traced = len(engine.row_budgets())
        self.program = fns.rollout.lower(self.static, self.state,
                                         self.n_tti).compile()
        budgets = engine.row_budgets()[traced:]
        if not budgets:
            raise SystemExit("the compiled rollout patches no mover rows")
        shards, row_budget = budgets[-1]
        m = self.program.memory_analysis()
        if m is not None:
            ctx.log(f"rollout memory_analysis per chip: argument "
                    f"{m.argument_size_in_bytes / 2**20:.1f} MiB, output "
                    f"{m.output_size_in_bytes / 2**20:.1f} MiB, temp "
                    f"{m.temp_size_in_bytes / 2**20:.1f} MiB")
        text = self.program.as_text()
        kernel = a.get("expect_kernel")
        if kernel and jax.default_backend() == "tpu" and (
                "tpu_custom_call" not in text):
            raise SystemExit(f"the compiled rollout holds no Pallas kernel "
                             f"({kernel} expected)")
        self.state, _ = self.program(self.static, self.state)
        jax.block_until_ready(self.state)
        self.rng, self.calls, self.kept = ctx.rng, 0, None
        frac = self.params.get("mobility_move_frac") or 1.0
        self.work = {"tti_per_call": self.n_tti, "kernel": kernel,
                     "rows": max(1, int(round(frac * n_ues))),
                     "cells": n_cells, "shards": shards,
                     "row_budget": row_budget,
                     "collectives": collective_ops(text),
                     "chunks": (self.params.get("n_subbands", 1)
                                * self.params.get("n_rb_subbands", 1)),
                     "sectors": self.params.get("n_sectors", 1)}
        ctx.log(f"{shards} shard(s), {row_budget} dirty rows per shard "
                f"per TTI for {self.work['rows']} movers")
        self.failed = 0

    def call(self) -> int:
        s_in = self.state
        self.state, tput = self.program(self.static, s_in)
        jax.block_until_ready((self.state, tput))
        self.calls += 1
        if self.rng.random() * self.calls < 1.0:
            self.kept = s_in
        return self.n_tti

    def finish(self) -> None:
        s_out, tput = self.program(self.static, self.kept)
        self.prog = check.outputs(s_out, tput)
        super().finish()

    def sample(self) -> check.Sample:
        s_in = self.kept
        one = jax.devices()[0]
        s0 = jax.tree_util.tree_map(lambda x: jax.device_put(x, one),
                                    check.as_ref_state(s_in))
        rc = reference.ref_cfg(self.params, per_tti_fading=False, churn=None)
        return check.Sample(rc=rc, s0=s0, n_tti=self.n_tti, batched=False,
                            prog=self.prog, U0=self.U0,
                            drop_keys=self.drop_keys,
                            h_ut_m=self.params["h_ut_m"])


def make(ctx):
    return MeshRollout(ctx)
