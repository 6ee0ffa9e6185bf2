"""The fused Pallas kernel compiles for a TPU v5e (``interpret=False``),
and so does the PF scheduler's dense per-cell maximum.

No chip is needed: the TPU compiler compiles for a described, unattached
v5e, and refuses there what the chip would refuse -- layouts Mosaic cannot
lower, gathers, VMEM overflow.  Each case is one kernel of the main path at
its real widths (about two seconds of compile).  The topology is described
inside a fixture only, so collecting this file never loads the TPU library.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import fused_sinr
from repro.mac import scheduler as mac_sched
from repro.mac import segments
from repro.sim.pathloss import make_pathloss


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                 # noqa: BLE001 - no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
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
