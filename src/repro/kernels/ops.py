"""Public jit'd wrappers for the Pallas kernels.

These handle padding to tile multiples, the kernels' data layout, interpret
mode off the TPU (``interpret=True`` executes the kernel body with jnp ops
for correctness validation on any backend), and the final cheap SINR math on
the kernel-accumulated O(N) state.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import fused_sinr as _fused


def _off_tpu() -> bool:
    """Interpret mode is the default exactly where there is no TPU; on a
    TPU the kernels always compile (a refusal raises to the caller)."""
    return jax.default_backend() != "tpu"


def _pad_axis(x, mult, axis=0, fill=0.0):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    shape = list(x.shape)
    shape[axis] = pad
    return jnp.concatenate(
        [x, jnp.full(tuple(shape), fill, x.dtype)], axis=axis)


def _pad_rows(x, mult, fill=0.0):
    return _pad_axis(x, mult, axis=0, fill=fill)


def fused_sinr(U, C, Pw, *, pathgain_fn, noise_w: float, boresight=None,
               fad=None, attach_on_mean: bool = False,
               n_sectors: int = 1, bn: int = 256, bm: int = 512,
               interpret=None):
    """Fused D->G->RSRP->w/u->SINR pipeline.

    Returns (gamma, a, w, u) exactly like ``ref.fused_sinr_ref`` but with
    O(N) HBM traffic.  Padded cells get zero power and a far position, so
    they can never win the attachment argmax or contribute interference.

    ``fad`` streams per-link fading through the tile pipeline -- ``(N, M)``
    wideband or ``(N, M, K)`` per-RB, multiplied onto the gain tile exactly
    as ``radio.apply_fading``.  ``attach_on_mean`` attaches on the unfaded
    RSRP row sum (the ``attach_ignores_fading`` regime).  The same entry
    point serves the dirty-row incremental backend: callers gather the
    dirty UE slab (rows of U and fad) and scatter the returned rows back
    (``radio.radio_update_rows_fused``).

    A tile dimension larger than the problem shrinks to it (a block equal
    to the whole axis satisfies the TPU's (8, 128) tiling at any size);
    otherwise ``bn`` must be a multiple of 8 and ``bm`` of 128 on the chip.
    This wrapper converts to the kernel's layout (cells and frequency
    chunks as leading-axis row stacks, per-RB fading as (K, N, M)).
    """
    if interpret is None:
        interpret = _off_tpu()
    n, m = U.shape[0], C.shape[0]
    bn = min(bn, n)
    bm = min(bm, m)
    Up = _pad_rows(U, bn)
    Cp = _pad_rows(C, bm, fill=1e9)
    Pp = _pad_rows(Pw, bm, fill=0.0)
    if boresight is None:
        bore = jnp.zeros((1, Cp.shape[0]), jnp.float32)
    else:
        bore = _pad_rows(boresight.reshape(-1), bm).reshape(1, -1)
    if fad is not None:
        fad = _pad_axis(_pad_axis(fad, bn, axis=0), bm, axis=1)
        if fad.ndim == 3:
            fad = jnp.moveaxis(fad, 2, 0)          # (K, N, M)
    total, bval, barg, wbest = _fused.fused_sinr_accumulate(
        Up, Cp.T[:, None, :], Pp.T[:, None, :], bore, fad,
        pathgain_fn=pathgain_fn, n_sectors=n_sectors, bn=bn, bm=bm,
        interpret=interpret, attach_on_mean=attach_on_mean)
    total, barg, wbest = total[:n], barg[:n, 0], wbest[:n]
    u = total - wbest
    gamma = wbest / (noise_w + u)
    return gamma, barg, wbest, u
