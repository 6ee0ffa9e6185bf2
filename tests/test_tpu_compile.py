"""The fused Pallas kernel compiles for a TPU v5e (``interpret=False``),
and so do the PF scheduler's dense per-cell maximum, the incremental
rollout of ``uma_mmtc`` on one described v5e and the UE-sharded one of
``uma_mmtc_b`` on a described v5e 2x2.

No chip is needed: the TPU compiler compiles for a described, unattached
v5e, and refuses there what the chip would refuse -- layouts Mosaic cannot
lower, gathers, VMEM overflow.  Each case is one kernel of the main path at
its real widths (about two seconds of compile).  The topology is described
inside a fixture only, so collecting this file never loads the TPU library.
"""
import importlib.util
import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as PSpec

from repro.core.crrm import CRRM
from repro.core.params import CRRM_parameters
from repro.kernels import fused_sinr, ops
from repro.mac import engine, traffic
from repro.mac import scheduler as mac_sched
from repro.mac import segments
from repro.sim import radio
from repro.sim.pathloss import make_pathloss

ROOT = Path(__file__).resolve().parents[1]
#: one v5e chip's HBM
HBM_BYTES = 16 * 2**30


def _bench_metric(name):
    spec = importlib.util.spec_from_file_location(
        name, ROOT / "bench" / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                 # noqa: BLE001 - no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(sharding, *, n, m, k, bn, bm, fading=None, n_sectors=1,
             attach_on_mean=False):
    """Compile ``fused_sinr_accumulate`` for the described chip, in the
    kernel layout ``kernels/ops.py`` produces."""
    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)

    fad = {None: None, "wide": sds(n, m), "rb": sds(k, n, m)}[fading]
    pathgain = make_pathloss("UMa").get_pathgain

    def run(U, C_rows, P_rows, bore, fad):
        return fused_sinr.fused_sinr_accumulate(
            U, C_rows, P_rows, bore, fad, pathgain_fn=pathgain,
            n_sectors=n_sectors, bn=bn, bm=bm, interpret=False,
            attach_on_mean=attach_on_mean)

    compiled = jax.jit(run).lower(sds(n, 3), sds(3, 1, m), sds(k, 1, m),
                                  sds(1, m), fad).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_million_ue_dirty_slab_compiles(one_chip):
    """The 1M-UE recipe's dirty slab: 100k rows x 127 cells, K=1."""
    _compile(one_chip, n=102_400, m=127, k=1, bn=256, bm=127)


def test_per_rb_fading_sectored_compiles(one_chip):
    """Per-RB fading, K=4 frequency chunks, 3-sector pattern."""
    _compile(one_chip, n=1024, m=512, k=4, bn=256, bm=512, fading="rb",
             n_sectors=3)


def test_wideband_fading_attach_on_mean_compiles(one_chip):
    """Wideband fading with attachment on the unfaded mean."""
    _compile(one_chip, n=1024, m=512, k=2, bn=256, bm=512, fading="wide",
             attach_on_mean=True)


@pytest.mark.parametrize("batch,n,k", [(0, 6_250_000, 1), (128, 570, 4)],
                         ids=["movers20", "drops128"])
def test_pf_dense_max_compiles(one_chip, monkeypatch, batch, n, k):
    """``allocate_pf`` at the benchmark cells' shapes (57 cells), as the
    chip compiles it: one scatter left, the sum's, and temporaries no
    larger than with the max's scatter (no (n, 57) buffer)."""
    n_cells = 57
    lead = (batch,) if batch else ()

    def pf(active, log_w, a):
        return mac_sched.allocate_pf(active, log_w, a, n_cells, 12)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(lead + shape, dtype, sharding=one_chip)

    def compile_pf(dense):
        # the described chip is not the default backend: choose for it
        monkeypatch.setattr(segments, "_scatter_serialises", lambda: dense)
        return jax.jit(jax.vmap(pf) if batch else pf).lower(
            sds((n, k), jnp.bool_), sds((n, k), jnp.float32),
            sds((n,), jnp.int32)).compile()

    compiled, scattered = compile_pf(True), compile_pf(False)
    text = compiled.as_text()
    scatters = re.findall(r"= \S+ scatter\(.*to_apply=(%[\w.-]+)", text)
    assert len(scatters) == 1, scatters
    combiner = text[text.index(scatters[0] + " ("):]
    assert " add(" in combiner[:combiner.index("\n}")]
    assert (compiled.memory_analysis().temp_size_in_bytes
            <= scattered.memory_analysis().temp_size_in_bytes)


#: the engine's stages that move and patch the mover window
_WINDOW_SCOPES = re.compile(r"/(mobility|radio/gather|radio/scatter)/")


def _window_gathers(text):
    """Gather and scatter instructions under the window's stages."""
    return [m.group(0)[:160] for m in re.finditer(
        r'= (?:\([^)]*\)|\S+) (?:gather|scatter)\([^\n]*?op_name="([^"]*)"',
        text) if _WINDOW_SCOPES.search(m.group(1))]


def _compile_rollout(topo, monkeypatch, workload, mesh=None):
    """The compiled incremental rollout of a benchmark cell at its full
    size on the described chips (shapes only), as the chip compiles it:
    a ``("ue",)`` mesh shards every per-UE leaf, else one chip holds the
    field and the graph-built static's fading root."""
    cell = json.loads((ROOT / "bench" / "workloads" / f"{workload}.json")
                      .read_text())
    cfg = json.loads((ROOT / "bench" / "configs" / f"{cell['config']}.json")
                     .read_text())["CRRM_parameters"]
    cfg.update(cell["params"])
    n, m = cfg["n_ues"], cfg["n_cells"]
    p = CRRM_parameters(**cfg)
    # the described chip is not the default backend: choose for it
    monkeypatch.setattr(radio, "pallas_available", lambda: True)
    monkeypatch.setattr(segments, "_scatter_serialises", lambda: True)
    monkeypatch.setattr(ops, "_off_tpu", lambda: False)
    cfg_small = CRRM(CRRM_parameters(**dict(cfg, n_ues=8))).radio_config()
    fns = engine.make_episode_fns(
        p, n, m, cfg_small,
        traffic.make_traffic(p.traffic_model, n, p.tti_s)[1],
        mobility_step_m=p.mobility_step_m,
        mobility_move_frac=p.mobility_move_frac, mesh=mesh,
        **cell["driver_args"]["episode_fns"])
    one = SingleDeviceSharding(topo.devices[0])

    def rows(*shape, dt=jnp.float32):
        if mesh is None:
            return jax.ShapeDtypeStruct(shape, dt, sharding=one)
        spec = PSpec("ue", *([None] * (len(shape) - 1)))
        return jax.ShapeDtypeStruct(shape, dt,
                                    sharding=NamedSharding(mesh, spec))

    def rep(*shape, dt=jnp.float32):
        if mesh is None:
            return jax.ShapeDtypeStruct(shape, dt, sharding=one)
        return jax.ShapeDtypeStruct(shape, dt,
                                    sharding=NamedSharding(mesh, PSpec()))

    i32 = jnp.int32
    static = engine.EpisodeStatic(
        se=rows(n, 1), cqi=rows(n, 1, dt=i32), a=rows(n, dt=i32),
        C=rep(m, 3), P=rep(m, 1), bore=rep(m),
        fad=None if mesh is not None else rows(n, m))
    state = engine.EpisodeState(
        U=rows(n, 3), backlog=rows(n), pf_avg=rows(n), rr_cursor=rep(dt=i32),
        key=rep(2, dt=jnp.uint32), harq_bits=rows(n),
        harq_retx=rows(n, dt=i32), serving=rows(n, dt=i32),
        ttt=rows(n, dt=i32), t=rep(dt=i32))
    return fns.rollout.lower(
        static, state, cell["driver_args"]["chunk_tti"]).compile()


def _per_chip_bytes(compiled):
    mem = compiled.memory_analysis()
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)


def test_incremental_rollout_compiles_for_one_chip(topo, monkeypatch):
    """``uma_mmtc.movers20``'s program: the incremental rollout of 10 TTIs
    at 6.25M UEs x 57 cells on one described chip, as the chip compiles
    it: the fused kernel, the mover window moved and patched with slices
    (no gather or scatter under ``mobility``, ``radio/gather`` or
    ``radio/scatter``; PF's sum scatter under ``sched`` stays), and the
    arguments, outputs and temporaries within the chip's 16 GiB."""
    before = radio.window_lowerings()
    compiled = _compile_rollout(topo, monkeypatch, "uma_mmtc.movers20")
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert radio.window_lowerings()["slices"] == before["slices"] + 1
    assert _window_gathers(text) == []
    assert re.search(r'scatter\([^\n]*op_name="[^"]*/sched/', text)
    assert _per_chip_bytes(compiled) <= HBM_BYTES, _per_chip_bytes(compiled)


def test_ue_sharded_incremental_rollout_compiles_for_2x2(topo, monkeypatch):
    """``uma_mmtc_b.mesh4``'s program: the incremental rollout of 10 TTIs
    at 25M UEs x 57 cells on a ``("ue",)`` mesh of the four described
    chips (6.25M rows each), as the chip compiles it: the fused kernel
    inside ``shard_map``, the mover window moved and patched with slices,
    and each chip's arguments, outputs and temporaries within its 16
    GiB."""
    mesh = Mesh(np.asarray(topo.devices), ("ue",))
    assert mesh.size == 4
    before = len(engine.row_budgets())
    compiled = _compile_rollout(topo, monkeypatch, "uma_mmtc_b.mesh4", mesh)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert engine.row_budgets()[before:] == [(4, 5_000_000)]
    assert _window_gathers(text) == []
    # PF's per-TTI maximum and sum cross the mesh as all-reduces named
    # after their primitives; the benchmark's collective metric finds
    # them by opcode, under the names the trace shows (none is fused)
    pf = {m.group(1): m.group(2) for m in re.finditer(
        r'%([\w.\-]+) = [^\n]*? all-reduce\([^\n]*op_name="[^"]*'
        r'/while/body/[^"]*/sched/(pmax|psum)"', text)}
    assert sorted(pf.values()) == ["pmax", "psum"], pf
    found = _bench_metric("mesh_collective_ms_per_tti").collective_ops(text)
    assert set(pf) <= set(found), (pf, found)
    assert not [n for n in found if n.startswith("fusion")], found
    assert _per_chip_bytes(compiled) <= HBM_BYTES, _per_chip_bytes(compiled)
