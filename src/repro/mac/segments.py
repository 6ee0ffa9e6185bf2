"""Segment reductions that lower well on the TPU and under ``vmap``.

The schedulers and the telemetry reducers aggregate per-UE rows into
per-cell bins: a sum (``zeros.at[a].add(w)``) and a maximum
(``full.at[a].max(log_w)``).  As XLA scatters both cost the same on a
TPU v5e, about 9 ns per row whatever the number of bins: the chip
serialises colliding updates, and with tens of cells nearly every
update collides (55.2 ms for 6.25M rows into 57 bins, either op).

**Maximum.**  A maximum is exact in any order, so on the TPU
:func:`segment_max` with few bins (``n_seg <= DENSE_MAX_SEGMENTS``)
lowers as a dense masked reduction instead:

    out[j, k] = max(fill, max_i where(seg[i] == j, data[i, k], fill))

one reduction per trailing column with the rows on the lane axis; XLA
fuses the compare, the select and the max into it, so no
``(n, n_seg)`` buffer is written.  It is bitwise the scatter, and
``vmap`` batches it as it is: each batch element reduces over its own
``n_seg`` bins.  Elsewhere it stays the scatter: with more bins; on the
``differentiable`` path, whose gradient at ties belongs to the
scatter; and off the TPU, where XLA's scatter is a plain loop far
cheaper than the dense pass (29 us against 1.7 ms for 6,250 rows into
57 bins on an 8-core x86 host), and where the CPU compiler, fusing the
reduction with its neighbours, rounds *their* arithmetic differently in
the last bit.  :func:`max_lowerings` counts, at trace time, which
lowering each call took.

**Sum.**  A float sum depends on its order, and a dense sum differs
from the scatter in the last bits; the engine's KPIs are bitwise
claims, so :func:`segment_sum` stays the scatter.

Both scatters keep the *exact* unbatched op as the primal (the
engine's bit-exactness claims ride on it -- the sharded 1e-5 gate, the
telemetry structural no-op).  Under ``vmap`` with a *batched* index
vector (every episode of a batch owns its own attachment ``a``), the
batching rule would turn them into a rank-2 scatter over (batch,
segment) coordinate tuples, which lowers ~10x slower than the
unbatched op.  So they carry a ``jax.custom_batching.custom_vmap`` rule
that flattens the batch axis into the segment ids:

    ids[b, i] = seg[b, i] + n_seg * b

one flat 1-D scatter over ``batch * n_seg`` bins instead of a rank-2
scatter -- the same lowering the unbatched op gets.  Within one batch
element the updates keep their row order, so per-element results match
the unbatched scatter bitwise (asserted in tests/test_twin.py).

``n_seg`` (and the ``fill`` value for :func:`segment_max`) are
trace-time constants; the decorated callables are cached per value so
repeated traces reuse one ``custom_vmap`` object.
"""
from __future__ import annotations

import math
from functools import lru_cache

import jax
import jax.numpy as jnp
from jax.custom_batching import custom_vmap


def _broadcast_unbatched(axis_size, in_batched, *args):
    """Give every argument a leading batch axis of ``axis_size``."""
    out = []
    for batched, x in zip(in_batched, args):
        out.append(x if batched
                   else jnp.broadcast_to(x[None], (axis_size,) + x.shape))
    return out


def _flat_ids(seg, n_seg):
    """Fold the batch coordinate into the segment ids: one 1-D id space."""
    b = jnp.arange(seg.shape[0], dtype=seg.dtype)[:, None]
    return (seg + n_seg * b).reshape(-1)


@lru_cache(maxsize=None)
def _segment_sum_fn(n_seg: int):
    @custom_vmap
    def seg_sum(data, seg):
        # THE primal: exactly the scatter-add the callers used to inline.
        shape = (n_seg,) + data.shape[1:]
        return jnp.zeros(shape, data.dtype).at[seg].add(data)

    @seg_sum.def_vmap
    def seg_sum_vmap(axis_size, in_batched, data, seg):
        data, seg = _broadcast_unbatched(axis_size, in_batched, data, seg)
        b, n = data.shape[:2]
        flat = data.reshape((b * n,) + data.shape[2:])
        out = jnp.zeros((b * n_seg,) + flat.shape[1:], flat.dtype)
        out = out.at[_flat_ids(seg, n_seg)].add(flat)
        return out.reshape((b, n_seg) + flat.shape[1:]), True

    return seg_sum


@lru_cache(maxsize=None)
def _segment_max_fn(n_seg: int, fill: float):
    @custom_vmap
    def seg_max(data, seg):
        shape = (n_seg,) + data.shape[1:]
        return jnp.full(shape, fill, data.dtype).at[seg].max(data)

    @seg_max.def_vmap
    def seg_max_vmap(axis_size, in_batched, data, seg):
        data, seg = _broadcast_unbatched(axis_size, in_batched, data, seg)
        b, n = data.shape[:2]
        flat = data.reshape((b * n,) + data.shape[2:])
        out = jnp.full((b * n_seg,) + flat.shape[1:], fill, flat.dtype)
        out = out.at[_flat_ids(seg, n_seg)].max(flat)
        return out.reshape((b, n_seg) + flat.shape[1:]), True

    return seg_max


def segment_sum(data, seg, n_seg: int, *, differentiable: bool = False):
    """``out[j] = sum_{i: seg[i] == j} data[i]`` over ``data``'s axis 0.

    ``data`` is (n, ...), ``seg`` (n,) int; returns (n_seg, ...).
    Unbatched this IS ``zeros.at[seg].add(data)`` (bit-exact); under
    ``vmap`` the custom rule scatters into a flattened (batch * n_seg)
    id space instead of a rank-2 scatter.

    ``differentiable=True`` skips the ``custom_vmap`` wrapper and issues
    the plain scatter directly: ``custom_vmap`` carries no JVP/transpose
    rule, so any autodiff trace through the wrapped op fails to
    linearize.  The primal is the identical scatter either way (bitwise
    equal results); only the vmap lowering differs -- callers on the
    differentiable-CRRM path (``RelaxConfig``) trade the batched-scatter
    optimisation for a gradient.
    """
    if differentiable:
        shape = (int(n_seg),) + data.shape[1:]
        return jnp.zeros(shape, data.dtype).at[seg].add(data)
    return _segment_sum_fn(int(n_seg))(data, seg)


def _dense_max_1d(col, seg, n_seg: int, fill):
    """``max(fill, max_{i: seg[i] == j} col[i])`` for each ``j``: rows
    on the lane axis, the mask fused into the reduction."""
    hit = seg[None, :] == jnp.arange(n_seg, dtype=seg.dtype)[:, None]
    return jnp.where(hit, col[None, :], fill).max(axis=-1, initial=fill)


def _dense_max(data, seg, n_seg: int, fill):
    """The dense lowering of :func:`segment_max`: one masked reduction per
    trailing column (one fused pass over the rows each; a single
    reduction over a (n_seg, K, n) mask would make XLA write the mask)."""
    fill = jnp.asarray(fill, data.dtype)
    # .at[seg] semantics: negative ids count from the end, others drop
    seg = jnp.where(seg < 0, seg + n_seg, seg)
    cols = data.reshape(data.shape[0], math.prod(data.shape[1:]))
    out = jnp.stack([_dense_max_1d(cols[:, k], seg, n_seg, fill)
                     for k in range(cols.shape[1])], axis=-1)
    return out.reshape((n_seg,) + data.shape[1:])


#: the most bins :func:`segment_max` reduces densely.  On a TPU v5e the
#: scatter costs about 9 ns per row and the dense pass about 1-3 ps per
#: row and bin (0.39 ms for 6.25M rows x 57 bins, 42 us for 262k x 57),
#: so they break even at 3,000-8,000 bins; 1,024 keeps the dense pass
#: three times or more below the scatter at any row count, and leaves
#: fields of 4,096 cells or more on the scatter.
DENSE_MAX_SEGMENTS = 1024


def _scatter_serialises() -> bool:
    """Whether the backend serialises colliding scatter updates (the
    TPU): the one place where the dense maximum pays."""
    return jax.default_backend() == "tpu"


#: trace-time tally of the lowering each :func:`segment_max` call took
_MAX_LOWERINGS = {"dense": 0, "scatter": 0}


def max_lowerings() -> dict:
    """``{"dense": n, "scatter": m}``: how many :func:`segment_max` calls
    traced so far in this process took each lowering (read deltas)."""
    return dict(_MAX_LOWERINGS)


def segment_max(data, seg, n_seg: int, fill=-jnp.inf, *,
                differentiable: bool = False):
    """``out[j] = max(fill, max_{i: seg[i] == j} data[i])`` over axis 0.

    ``data`` is (n, ...), ``seg`` (n,) int; returns (n_seg, ...); ``fill``
    seeds empty segments (trace-time constant).  On the TPU with at most
    ``DENSE_MAX_SEGMENTS`` bins it is the dense masked reduction, bitwise
    ``full(fill).at[seg].max(data)`` (a maximum is exact in any order),
    batched by plain ``vmap``.  Otherwise it is that scatter, with
    :func:`segment_sum`'s flattened ``custom_vmap`` rule.
    ``differentiable=True`` issues the plain scatter (scatter-max has an
    autodiff rule, with its own gradient at ties; the ``custom_vmap``
    wrapper has none).
    """
    n_seg = int(n_seg)
    if differentiable:
        _MAX_LOWERINGS["scatter"] += 1
        shape = (n_seg,) + data.shape[1:]
        return jnp.full(shape, float(fill), data.dtype).at[seg].max(data)
    if n_seg <= DENSE_MAX_SEGMENTS and _scatter_serialises():
        _MAX_LOWERINGS["dense"] += 1
        return _dense_max(data, seg, n_seg, float(fill))
    _MAX_LOWERINGS["scatter"] += 1
    return _segment_max_fn(n_seg, float(fill))(data, seg)
