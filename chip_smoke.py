#!/usr/bin/env python3
"""Chip smoke test: the simulator's main path on a TPU, with its checks.

One process drives each user-facing path once through its normal entry
points (``CRRM`` -> ``episode_fns().rollout``, ``TwinServer``) at the sizes
of the repository's benchmark recipes, and checks the results against the
repository's own equivalence contracts:

* batch study -- 100k UEs x 127 cells, 10% movers per TTI, full-buffer
  PF, 20 TTIs: the incremental radio mode's trajectory within 1e-5 of the
  dense recompute;
* million-UE fused path -- 1M UEs x 127 cells, incremental, 5 TTIs: the
  compiled Pallas dirty-row kernel (``inc_backend="pallas"``, a
  ``tpu_custom_call`` in the program) against the XLA rows, attachment
  equal and throughput within 1e-4; ``inc_backend="auto"`` must pick the
  kernel;
* twin -- 20k UEs x 57 cells under birth-death churn, chunks of 50 TTIs:
  a restored checkpoint replays the following chunks bitwise.

``--chips 4`` runs only the UE x cell mesh: 1M UEs x 128 cells on a 2x2
("ue", "cell") mesh against the single-chip rollout of the same episode,
serving cells bitwise and the network KPIs within 1e-5.

The ``#`` lines report wall and compile seconds per phase: smoke figures,
not benchmark results.  The last line of stdout is one JSON object naming
the device, printed only when every phase passed on a TPU; without a TPU
the script exits non-zero before any phase.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the 2x2 mesh phase only
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

#: the smart_update_scan / million_episode recipe of
#: benchmarks/paper_benches.py: full-buffer PF, 10% window movers
RECIPE = dict(n_sectors=1, seed=3, pathloss_model_name="UMa", power_W=10.0,
              scheduler_policy="pf", fairness_p=0.5, mobility_step_m=20.0,
              mobility_move_frac=0.10)


def log(msg: str) -> None:
    print(f"# {msg}", flush=True)


@contextlib.contextmanager
def phase(name: str):
    """Log a phase's wall and backend-compile seconds when it succeeds."""
    from repro.obs.profile import CompileCounter
    t0 = time.perf_counter()
    with CompileCounter() as cc:
        yield
    log(f"{name}: wall {time.perf_counter() - t0:.3f} s, compile "
        f"{cc.seconds:.3f} s ({cc.count} programs) [smoke figures, not "
        f"benchmark results]")


def check(ok: bool, msg: str) -> None:
    """A failed check ends the run (unlike ``assert``, also under -O)."""
    if not ok:
        raise SystemExit(f"chip_smoke: check failed: {msg}")


def rel_err(x, ref) -> float:
    """Max abs deviation over the reference's scale (the benches' metric)."""
    return float(np.abs(x - ref).max() / max(np.abs(ref).max(), 1.0))


def memory_line(compiled) -> str:
    m = compiled.memory_analysis()
    return (f"argument {m.argument_size_in_bytes / 2**20:.1f} MiB, output "
            f"{m.output_size_in_bytes / 2**20:.1f} MiB, temp "
            f"{m.temp_size_in_bytes / 2**20:.1f} MiB, code "
            f"{m.generated_code_size_in_bytes / 2**20:.1f} MiB")


def peak_line(devices) -> str:
    return ", ".join(
        f"{d.id}: {d.memory_stats()['peak_bytes_in_use'] / 2**20:.1f} MiB"
        for d in devices)


def batch_study(n_ues=100_000, n_cells=127, n_tti=20):
    """Dense vs incremental rollouts of one batch-study episode."""
    from repro.core.crrm import CRRM
    from repro.core.params import CRRM_parameters

    key = jax.random.PRNGKey(0)
    traj = {}
    for mode in ("dense", "incremental"):
        sim = CRRM(CRRM_parameters(n_ues=n_ues, n_cells=n_cells,
                                   radio_mode=mode, **RECIPE))
        fns = sim.episode_fns()
        static, state = sim.episode_static(), sim.init_episode_state(key)
        with phase(f"batch {mode}, {n_ues} UEs x {n_cells} cells x {n_tti} "
                   f"TTIs, first call"):
            _, tput = jax.block_until_ready(fns.rollout(static, state, n_tti))
        with phase(f"batch {mode}, second call"):
            jax.block_until_ready(fns.rollout(static, state, n_tti))
        traj[mode] = np.asarray(tput)
    rel = rel_err(traj["incremental"], traj["dense"])
    log(f"batch: incremental vs dense max rel err {rel:.3e} (bound 1e-5)")
    check(rel < 1e-5, f"incremental deviates from dense: {rel:.3e}")


def million_fused(n_ues=1_000_000, n_cells=127, n_tti=5):
    """The fused Pallas dirty-row kernel against the XLA rows at 1M UEs."""
    from repro.core.crrm import CRRM
    from repro.core.params import CRRM_parameters

    sim = CRRM(CRRM_parameters(n_ues=n_ues, n_cells=n_cells,
                               radio_mode="incremental", **RECIPE))
    static = sim.episode_static()
    key = jax.random.PRNGKey(0)
    out, text = {}, {}
    for backend in ("pallas", "xla"):
        fns = sim.episode_fns(inc_backend=backend)
        state = sim.init_episode_state(key)
        with phase(f"million {backend}, {n_ues} UEs x {n_cells} cells, "
                   f"compile"):
            compiled = fns.rollout.lower(static, state, n_tti).compile()
        text[backend] = compiled.as_text()
        log(f"million {backend}: memory_analysis {memory_line(compiled)}")
        with phase(f"million {backend}, {n_tti} TTIs"):
            s, tput = jax.block_until_ready(compiled(static, state))
        out[backend] = (np.asarray(s.serving), np.asarray(tput))
        del s, tput
    log(f"million: peak_bytes_in_use {peak_line(jax.devices()[:1])}")
    auto = sim.episode_fns(inc_backend="auto").rollout.lower(
        static, sim.init_episode_state(key), n_tti).as_text()

    (a_p, t_p), (a_x, t_x) = out["pallas"], out["xla"]
    n_diff = int((a_p != a_x).sum())
    rel = rel_err(t_p, t_x)
    log(f"million: pallas vs xla serving cells differing {n_diff}, "
        f"throughput max rel err {rel:.3e} (bound 1e-4)")
    check(n_diff == 0, f"{n_diff} UEs attach differently under the kernel")
    check(rel < 1e-4, f"fused throughput deviates from XLA: {rel:.3e}")
    check("tpu_custom_call" in text["pallas"],
          "inc_backend='pallas' compiled without the Pallas kernel")
    check("tpu_custom_call" not in text["xla"],
          "inc_backend='xla' compiled a Pallas kernel")
    check("tpu_custom_call" in auto,
          "inc_backend='auto' did not pick the kernel on the TPU")


def twin(n_ues=20_000, n_cells=57, chunk_tti=50):
    """Chunked twin serving under churn; checkpoint/restore is bitwise."""
    from repro.core.crrm import CRRM
    from repro.core.params import CRRM_parameters
    from repro.sim.mobility import ChurnConfig
    from repro.twin import TwinServer

    params = CRRM_parameters(
        n_ues=n_ues, n_cells=n_cells, radio_mode="dense",
        traffic_model="poisson",
        traffic_params=dict(arrival_rate_hz=300.0, packet_size_bits=12_000.0),
        **RECIPE)
    # stationary occupancy = rate x lifetime = 0.7 x capacity
    churn = ChurnConfig(arrival_rate_hz=0.35 * n_ues, mean_lifetime_s=2.0,
                        max_arrivals_per_tti=max(8, n_ues // 512))

    def snapshot(srv, kpis):
        return kpis, np.asarray(srv.last_tput), [
            np.asarray(x) for x in jax.tree_util.tree_leaves(srv.state)]

    with tempfile.TemporaryDirectory() as td:
        srv = TwinServer(CRRM(params), churn, chunk_tti=chunk_tti,
                         ckpt_dir=td)
        with phase(f"twin {n_ues} UEs x {n_cells} cells, chunk 1"):
            srv.step_chunk()
        with phase(f"twin chunk 2 ({chunk_tti} TTIs)"):
            srv.step_chunk()
        srv.checkpoint()
        ahead = [snapshot(srv, srv.step_chunk()) for _ in range(2)]
        srv.restore()
        again = [snapshot(srv, srv.step_chunk()) for _ in range(2)]
        check(not srv.fault_history, f"twin faults: {srv.fault_history}")
    for (k1, t1, l1), (k2, t2, l2) in zip(ahead, again):
        check(k1 == k2, "restored KPI summary diverged")
        np.testing.assert_array_equal(t1, t2)
        for x, y in zip(l1, l2):
            np.testing.assert_array_equal(x, y)
    kpis = ahead[-1][0]
    check(0.0 < kpis["active_ues"] < n_ues,
          f"churn never engaged: {kpis['active_ues']} of {n_ues} active")
    log(f"twin: {len(ahead) + len(again) + 2} chunks, restored chunks "
        f"bitwise equal, {kpis['active_ues']:.0f} active UEs, "
        f"{kpis['served_mbits']:.3f} Mbit served in the last chunk")


def mesh_2x2(n_ues=1_000_000, n_cells=128, n_tti=5):
    """The UE x cell mesh on four chips against one chip.

    Serving cells must agree bitwise and the network KPIs (telemetry:
    served bits per TTI, Jain fairness) within 1e-5.  Per-UE throughput
    is reported, not bounded: the mesh reorders float sums (interference
    over cell shards, each cell's PF share denominator over UE shards,
    some 8k UEs per cell here), which moves per-UE rates by more than
    1e-5 at this scale, and a rare SINR crosses a CQI threshold, which
    moves that UE's rate by a CQI step.
    """
    from jax.sharding import Mesh

    from repro.core.crrm import CRRM
    from repro.core.params import CRRM_parameters

    devices = jax.devices()
    check(len(devices) >= 4, f"--chips 4 needs 4 devices, have {devices}")
    mesh = Mesh(np.array(devices[:4]).reshape(2, 2), ("ue", "cell"))
    params = CRRM_parameters(n_ues=n_ues, n_cells=n_cells,
                             radio_mode="incremental", **RECIPE)
    key = jax.random.PRNGKey(0)
    out = {}
    for name, kw in (("mesh", dict(mesh=mesh, cell_axis=("cell",))),
                     ("single", {})):
        sim = CRRM(params)
        fns = sim.episode_fns(telemetry=True, **kw)
        static, state = sim.episode_static(), sim.init_episode_state(key)
        with phase(f"{name} {n_ues} UEs x {n_cells} cells, compile"):
            compiled = fns.rollout.lower(static, state, n_tti).compile()
        log(f"{name}: memory_analysis per device {memory_line(compiled)}")
        with phase(f"{name}, {n_tti} TTIs"):
            s, tput, tel = jax.block_until_ready(compiled(static, state))
        shards = [(x.device.id, x.data.shape)
                  for x in s.U.addressable_shards]
        log(f"{name}: state.U shards {shards}; peak_bytes_in_use "
            f"{peak_line(devices[:4])}")
        out[name] = (np.asarray(s.serving), np.asarray(tput),
                     np.asarray(tel.served_bits), np.asarray(tel.jain))
        del s, tput, tel
    (a_m, t_m, b_m, j_m), (a_1, t_1, b_1, j_1) = out["mesh"], out["single"]
    check(bool((a_m == a_1).all()),
          f"{int((a_m != a_1).sum())} serving cells differ on the mesh")
    net = rel_err(b_m.sum(axis=1), b_1.sum(axis=1))
    jain = rel_err(j_m, j_1)
    log(f"mesh: serving cells bitwise equal; network served bits per TTI "
        f"max rel err {net:.3e}, Jain {jain:.3e}, per-cell served bits "
        f"{rel_err(b_m, b_1):.3e} (bound 1e-5 on the network KPIs)")
    check(net < 1e-5 and jain < 1e-5,
          f"mesh KPIs deviate: served bits {net:.3e}, Jain {jain:.3e}")
    dev = np.abs(t_m - t_1) / np.maximum(np.abs(t_1), 1.0)
    for t in range(dev.shape[0]):
        big, small = dev[t] > 1e-2, (dev[t] > 1e-5) & (dev[t] <= 1e-2)
        log(f"mesh: TTI {t}: {int(big.sum())} UEs off by > 1e-2 in cells "
            f"{sorted(set(a_1[big].tolist()))[:8]}, {int(small.sum())} by "
            f"1e-5..1e-2 in {len(set(a_1[small].tolist()))} cells")
    log(f"mesh: per-UE throughput max rel err {rel_err(t_m, t_1):.3e}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 2x2 UE x cell mesh phase")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; the default device is "
              f"{dev.platform!r}", file=sys.stderr)
        return 1
    from repro.compile_cache import enable_compile_cache
    log(f"device {dev.device_kind} x {len(jax.devices())}, jax "
        f"{jax.__version__}, compile cache {enable_compile_cache()}")

    if args.chips == 4:
        mesh_2x2()
    else:
        batch_study()
        million_fused()
        twin()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
