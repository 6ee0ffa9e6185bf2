"""Per-cell segment reductions (``repro.mac.segments``): the dense lowering
of ``segment_max`` is bitwise the scatter it replaces, batched or not, and
the PF scheduler's compiled program keeps only the sum's scatter.

The dense lowering is taken on the TPU only; the ``tpu_choice`` fixture
makes these CPU tests take the TPU's choice (tests/test_tpu_compile.py
compiles it for a described v5e)."""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.mac import scheduler as mac_sched
from repro.mac import segments

ABOVE = segments.DENSE_MAX_SEGMENTS + 1


@pytest.fixture
def tpu_choice(monkeypatch):
    monkeypatch.setattr(segments, "_scatter_serialises", lambda: True)


def _scatter_max(data, seg, n_seg, fill):
    return jnp.full((n_seg,) + data.shape[1:], fill,
                    data.dtype).at[seg].max(data)


def _case(kind, n, n_seg, k, batch, rng):
    """(data, seg, fill) of shape (batch, n[, k]) / (batch, n)."""
    shape = (batch, n) + ((k,) if k else ())
    data = rng.normal(size=shape).astype(np.float32)
    seg = rng.integers(0, n_seg, (batch, n))
    fill = -np.inf
    if kind == "empty":          # most bins see no row: the fill comes back
        seg = rng.integers(0, max(1, n_seg // 3), (batch, n))
        fill = -1e30
    elif kind == "idle":         # PF's idle sentinel, and a bin of idle rows
        data[rng.random(shape) < 0.4] = -np.inf
        data[seg == 0] = -np.inf
    elif kind == "ties":         # equal values within a bin
        data = np.round(data).astype(np.float32)
    elif kind == "ids":          # .at[] ids: -1 counts from the end, n_seg drops
        seg[:, ::7] = -1
        seg[:, 3::11] = n_seg
    return jnp.asarray(data), jnp.asarray(seg, dtype=jnp.int32), fill


CASES = [
    # kind, n, n_seg, K (0: 1-D data), batch
    ("random", 37, 9, 2, 5),
    ("random", 37, 9, 0, 3),
    ("random", 20_000, 57, 1, 2),         # movers20's shape, fewer UEs
    ("random", 570, 57, 4, 8),            # drops128's, fewer drops
    ("random", 1140, 57, 4, 2),           # the twin's
    ("random", 0, 57, 4, 2),              # no rows at all
    ("empty", 200, 57, 4, 3),
    ("idle", 1140, 57, 4, 3),
    ("ties", 500, 7, 3, 3),
    ("ids", 300, 11, 2, 3),
    ("random", 3000, ABOVE, 1, 2),        # above the threshold: the scatter
    ("idle", 3000, ABOVE, 2, 2),
]


@pytest.mark.parametrize("kind,n,n_seg,k,batch", CASES,
                         ids=[f"{c[0]}-{c[1]}x{c[2]}x{c[3]}b{c[4]}"
                              for c in CASES])
def test_segment_max_is_the_scatter_bitwise(tpu_choice, kind, n, n_seg, k,
                                            batch):
    rng = np.random.default_rng(CASES.index((kind, n, n_seg, k, batch)))
    data, seg, fill = _case(kind, n, n_seg, k, batch, rng)
    before = segments.max_lowerings()

    def seg_max(d, s):
        return segments.segment_max(d, s, n_seg, fill=fill)

    got = jax.jit(jax.vmap(seg_max))(data, seg)
    for b in range(batch):
        want = np.asarray(_scatter_max(data[b], seg[b], n_seg, fill))
        one = np.asarray(seg_max(data[b], seg[b]))
        np.testing.assert_array_equal(one.view(np.int32),
                                      want.view(np.int32))
        np.testing.assert_array_equal(np.asarray(got[b]).view(np.int32),
                                      want.view(np.int32))
    lowering = "dense" if n_seg <= segments.DENSE_MAX_SEGMENTS else "scatter"
    after = segments.max_lowerings()
    assert after[lowering] - before[lowering] == 1 + batch
    if kind == "empty":
        assert (np.asarray(got)[:, n_seg // 3 + 1:] == fill).all()


def _pf_program(batch, differentiable):
    """Compiled ``allocate_pf`` at the cells' 57 bins (the twin's 1140 UEs,
    4 subbands), and the ``segment_max`` lowerings its trace took."""
    n, n_cells, k = 1140, 57, 4

    def pf(active, log_w, a):
        return mac_sched.allocate_pf(active, log_w, a, n_cells, 12,
                                     differentiable=differentiable)

    lead = (batch,) if batch else ()
    args = (jax.ShapeDtypeStruct(lead + (n, k), jnp.bool_),
            jax.ShapeDtypeStruct(lead + (n, k), jnp.float32),
            jax.ShapeDtypeStruct(lead + (n,), jnp.int32))
    before = segments.max_lowerings()
    text = jax.jit(jax.vmap(pf) if batch else pf).lower(
        *args).compile().as_text()
    after = segments.max_lowerings()
    return text, {k: after[k] - before[k] for k in after}


@pytest.mark.parametrize("batch", [0, 3], ids=["unbatched", "vmapped"])
def test_pf_program_keeps_only_the_sum_scatter(tpu_choice, batch):
    text, lowered = _pf_program(batch, differentiable=False)
    scatters = re.findall(r"= \S+ scatter\(.*to_apply=(%[\w.-]+)", text)
    assert len(scatters) == 1, scatters
    combiner = text[text.index(scatters[0] + " ("):]
    combiner = combiner[:combiner.index("\n}")]
    assert " add(" in combiner and " maximum(" not in combiner
    assert lowered == {"dense": 1, "scatter": 0}


def test_pf_differentiable_path_keeps_the_max_scatter(tpu_choice):
    text, lowered = _pf_program(0, differentiable=True)
    assert len(re.findall(r"= \S+ scatter\(", text)) == 2
    assert lowered == {"dense": 0, "scatter": 1}


def test_off_the_tpu_the_max_stays_the_scatter():
    """Off the TPU the scatter is the cheap lowering, and the engine's
    program (and with it every KPI) is the scatter's."""
    text, lowered = _pf_program(0, differentiable=False)
    assert jax.default_backend() != "tpu"
    assert len(re.findall(r"= \S+ scatter\(", text)) == 2
    assert lowered == {"dense": 0, "scatter": 1}
