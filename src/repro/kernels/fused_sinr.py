"""Pallas TPU kernel: the fused CRRM pipeline D -> G -> RSRP -> w/u (+argmax).

The paper materialises every Figure-1 matrix in HBM; on TPU that makes the
whole pipeline HBM-bandwidth bound (arithmetic intensity < 1 FLOP/byte for
the elementwise blocks).  This kernel streams cell tiles through VMEM and
accumulates, flash-attention style, the only O(N) state the downstream blocks
need:

  * total[i, k]   -- sum_j p_jk g_ij      (interference + wanted)
  * best_val[i]   -- running max_j of wideband RSRP
  * best_idx[i]   -- its argmax (the attachment vector a)
  * w_best[i, k]  -- RSRP row of the current best server

so the (N, M) distance/gain/RSRP matrices never touch HBM.  Tie-break matches
``jnp.argmax`` (lowest cell index wins).

Per-link fading streams through the same tile pipeline: a ``fading`` tile --
``(bn, bm)`` wideband or ``(K, bn, bm)`` per-RB -- multiplies the gain tile
exactly as ``radio.apply_fading`` does, and ``attach_on_mean`` reproduces the
``attach_ignores_fading`` regime by ranking servers on the *unfaded* RSRP row
sum while still reporting the faded serving row.

Layout.  Every value the kernel computes is a 2-D ``(bn, bm)`` tile (UEs on
sublanes, cells on lanes) or a ``(bn, 1)`` / ``(1, bm)`` edge of one, which
is what the TPU's vector layout holds natively.  The frequency axis K (small
and static) is an unrolled Python loop, never an array axis: per-cell inputs
arrive as ``(3 | K, 1, M)`` row stacks and per-RB fading as ``(K, N, M)``,
so each ``k`` is a leading-axis index (``kernels/ops.py`` does the
transposes).  Coordinate columns, the argmax's serving row and the ``(bn, K)``
output blocks are built with one-hot selects instead of gathers or lane
slices, which are exact (one non-zero term per sum).

Grid: (UE tiles, cell tiles); the cell dimension is `arbitrary` (sequential)
because every step read-modify-writes the same output block.  The pathloss
strategy is traced *into* the kernel as pure jnp (any 38.901 model works).

VMEM per step, as the TPU compiler allocates it (``tests/test_tpu_compile.py``
pins the kernels' compiles for a v5e; DESIGN.md §Million-UE-scaling has the
figures): the double-buffered input blocks, the ``(bn, bm)`` temporaries of
the gain chain, and the lane-padded ``(bn, K)`` output blocks.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -3.4e38  # python float: jnp constants would be captured consts


def _column(x, i):
    """Column ``i`` of a ``(rows, w)`` tile as ``(rows, 1)``: a one-hot
    select and lane sum (exact: one non-zero term)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.sum(jnp.where(lane == i, x, 0.0), axis=1, keepdims=True)


def _atan2(y, x):
    """float32 ``arctan2`` from the ops Mosaic lowers (it has no atan).

    Octant reduction to ``t = min/max`` in [0, 1], a second reduction
    about tan(pi/8), then the Cephes ``atanf`` minimax polynomial (about
    2e-7 relative error, i.e. an ulp or two of ``jnp.arctan2``).
    ``atan2(0, 0) = 0`` as in ``jnp.arctan2``.
    """
    ax, ay = jnp.abs(x), jnp.abs(y)
    hi, lo = jnp.maximum(ax, ay), jnp.minimum(ax, ay)
    t = lo / jnp.where(hi == 0.0, 1.0, hi)
    mid = t > 0.41421356237309503                  # tan(pi/8)
    z = jnp.where(mid, (t - 1.0) / (t + 1.0), t)
    z2 = z * z
    p = ((((8.05374449538e-2 * z2 - 1.38776856032e-1) * z2
           + 1.99777106478e-1) * z2 - 3.33329491539e-1) * z2 * z + z)
    a = jnp.where(mid, 0.7853981633974483 + p, p)
    a = jnp.where(ay > ax, 1.5707963267948966 - a, a)
    a = jnp.where(x < 0.0, 3.141592653589793 - a, a)
    return jnp.where(y < 0.0, -a, a)


def _make_kernel(pathgain_fn, n_sectors: int, bm: int, k: int,
                 fading: str | None = None, attach_on_mean: bool = False):
    def kernel(*refs):
        if fading is None:
            (u_ref, c_ref, p_ref, bore_ref,
             total_ref, bval_ref, barg_ref, wbest_ref) = refs
            fad_ref = None
        else:
            (u_ref, c_ref, p_ref, bore_ref, fad_ref,
             total_ref, bval_ref, barg_ref, wbest_ref) = refs
        j = pl.program_id(1)

        @pl.when(j == 0)
        def _init():
            total_ref[...] = jnp.zeros_like(total_ref)
            bval_ref[...] = jnp.full_like(bval_ref, _NEG)
            barg_ref[...] = jnp.zeros_like(barg_ref)
            wbest_ref[...] = jnp.zeros_like(wbest_ref)

        u = u_ref[...]                               # (bn, 3)
        ux, uy, uz = (_column(u, i) for i in range(3))   # (bn, 1) each
        cx, cy, cz = c_ref[0], c_ref[1], c_ref[2]    # (1, bm) each

        # broadcast difference mirroring radio.compute_distances operation
        # for operation (d3d built FROM d2d, not from the raw squared sum)
        dx = ux - cx
        dy = uy - cy
        dz = uz - cz
        d2d = jnp.sqrt(dx * dx + dy * dy)
        d3d = jnp.sqrt(d2d * d2d + dz * dz)

        # -- G: pluggable pathloss strategy (traced jnp) -------------------
        g = pathgain_fn(d2d, d3d, cz, uz)
        if n_sectors > 1:
            # 3GPP horizontal pattern, inlined for fusion; the offset from
            # boresight wraps to [-pi, pi] by whole turns
            off = _atan2(dy, dx) - bore_ref[...]
            off = off - 6.283185307179586 * jnp.round(
                off * 0.15915494309189535)
            phi3 = 1.1344640137963142  # 65 deg in radians
            att = jnp.minimum(12.0 * (off / phi3) ** 2, 30.0)
            g = g * jnp.power(10.0, -0.1 * att)

        # -- RSRP per frequency chunk (K unrolled, each a 2-D tile) --------
        if fading == "wide":
            gf = g * fad_ref[...]                    # apply_fading, 2-D
            r = [gf * p_ref[kk] for kk in range(k)]
        elif fading == "rb":
            r = [(g * fad_ref[kk]) * p_ref[kk] for kk in range(k)]
        else:
            r = [g * p_ref[kk] for kk in range(k)]
        if attach_on_mean:
            rank = [g * p_ref[kk] for kk in range(k)]
        else:
            rank = r
        meas = rank[0]
        for rk in rank[1:]:
            meas = meas + rk

        # -- online reductions: tile argmax by max + lowest matching lane --
        lane = jax.lax.broadcasted_iota(jnp.int32, meas.shape, 1)
        t_max = jnp.max(meas, axis=1, keepdims=True)             # (bn, 1)
        t_arg = jnp.min(jnp.where(meas == t_max, lane, bm), axis=1,
                        keepdims=True)                           # (bn, 1)
        onehot = lane == t_arg
        kcol = jax.lax.broadcasted_iota(jnp.int32, total_ref.shape, 1)
        tot = jnp.zeros(total_ref.shape, jnp.float32)
        t_w = jnp.zeros(wbest_ref.shape, jnp.float32)
        for kk in range(k):
            put = kcol == kk
            tot = jnp.where(put, jnp.sum(r[kk], axis=1, keepdims=True), tot)
            t_w = jnp.where(put, jnp.sum(jnp.where(onehot, r[kk], 0.0),
                                         axis=1, keepdims=True), t_w)
        total_ref[...] += tot
        prev = bval_ref[...]
        better = t_max > prev                                    # (bn, 1)
        bval_ref[...] = jnp.where(better, t_max, prev)
        barg_ref[...] = jnp.where(better, t_arg + j * bm, barg_ref[...])
        wbest_ref[...] = jnp.where(better, t_w, wbest_ref[...])

    return kernel


@partial(jax.jit,
         static_argnames=("pathgain_fn", "n_sectors", "bn", "bm", "interpret",
                          "attach_on_mean"))
def fused_sinr_accumulate(U, C_rows, P_rows, bore_row, fad=None, *,
                          pathgain_fn, n_sectors: int = 1, bn: int = 256,
                          bm: int = 512, interpret: bool = False,
                          attach_on_mean: bool = False):
    """Run the fused accumulator.  Returns (total, best_val, best_idx, w_best).

    Kernel layout (``kernels/ops.py`` converts): U (N, 3), C_rows (3, 1, M)
    cell coordinates, P_rows (K, 1, M) per-chunk powers, bore_row (1, M),
    fad None / (N, M) wideband / (K, N, M) per-RB.  Outputs are (N, K),
    (N, 1), (N, 1) int32, (N, K).  N % bn == 0 and M % bm == 0 (ops.py
    pads; padded cells need power 0 and padded fading 0).
    ``attach_on_mean`` ranks servers on the unfaded RSRP row sum
    (``attach_ignores_fading``); it requires ``fad``.
    """
    n, m, k = U.shape[0], C_rows.shape[2], P_rows.shape[0]
    assert n % bn == 0 and m % bm == 0, (n, m, bn, bm)
    if fad is None:
        fading = None
        assert not attach_on_mean, "attach_on_mean requires a fading tensor"
    elif fad.ndim == 2:
        fading = "wide"
        assert fad.shape == (n, m), (fad.shape, n, m)
    else:
        fading = "rb"
        assert fad.shape == (k, n, m), (fad.shape, k, n, m)
    grid = (n // bn, m // bm)
    kernel = _make_kernel(pathgain_fn, n_sectors, bm, k, fading,
                          attach_on_mean)
    in_specs = [
        pl.BlockSpec((bn, 3), lambda i, j: (i, 0)),
        pl.BlockSpec((3, 1, bm), lambda i, j: (0, 0, j)),
        pl.BlockSpec((k, 1, bm), lambda i, j: (0, 0, j)),
        pl.BlockSpec((1, bm), lambda i, j: (0, j)),
    ]
    operands = [U, C_rows, P_rows, bore_row]
    if fading == "wide":
        in_specs.append(pl.BlockSpec((bn, bm), lambda i, j: (i, j)))
        operands.append(fad)
    elif fading == "rb":
        in_specs.append(pl.BlockSpec((k, bn, bm), lambda i, j: (0, i, j)))
        operands.append(fad)
    out_shape = [
        jax.ShapeDtypeStruct((n, k), jnp.float32),   # total
        jax.ShapeDtypeStruct((n, 1), jnp.float32),   # best_val
        jax.ShapeDtypeStruct((n, 1), jnp.int32),     # best_idx
        jax.ShapeDtypeStruct((n, k), jnp.float32),   # w_best
    ]
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bn, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, k), lambda i, j: (i, 0)),
        ],
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="fused_sinr",
    )(*operands)
