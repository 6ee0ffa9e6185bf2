"""The numbers that decide ``correct``: the timed path against the reference.

Every number is a disagreement, 0 when the two agree, and is held to a
limit of its own from the cell's workload file:

* ``tput_mismatch`` -- the share of served UE-TTIs (non-zero throughput
  on either side) whose throughput differs from the reference's by more
  than 1% (plus 1 bit/s).  One UE whose SINR sits on a CQI threshold can
  round to the other side in a different summation order; that moves its
  own rate by a CQI step and, through the PF split and the EWMA state,
  its cell's rates for the rest of the call, so this number is a share
  and never a single worst UE;
* ``cell_mismatch`` -- the share of cells (of every drop) in which more
  than 1% of the served UE-TTIs of the call differ as ``tput_mismatch``
  counts them, each UE-TTI placed in the cell that serves it in the
  reference.  A UE on the edge of CQI 1 whose PF average is nought takes
  its whole cell under the alpha-fair split when it rounds onto the
  served side, so rounding can move a cell or two of a large field; a
  fault moves every cell;
* ``net_bits_err`` -- the largest relative error, over the TTIs of the
  call, of the network's served throughput (the sum over UEs and drops);
* ``state_mismatch`` -- the share of UEs whose carried state at the end
  of the call (PF average, finite backlog) differs by more than 1%
  (plus 1 unit), or 1.0 if the TTI counter differs;
* ``pos_err_m`` -- the largest position error after the call, in metres
  (cells whose UEs move or are born);
* ``drop_err_m`` -- the largest error, in metres, of the UE field the
  program holds after set-up against the one the reference draws from
  the seed: the starting drop is checked too, not only taken over;
* ``kpi_err`` / ``active_err`` -- the twin's chunk summary: the largest
  relative error of its served, load, HARQ, buffer and fairness KPIs,
  and the absolute error of its mean live-UE count (an integer process:
  exact).
"""
from __future__ import annotations

import numpy as np

#: the chunk summary's network aggregates (a handful of failed attempts
#: is too small a count for a relative error)
KPI_KEYS = ("served_mbits", "mean_cell_load_rb", "harq_acks",
            "mean_buffer_mbits", "mean_jain", "busiest_cell_mbps")


def _np(x):
    return np.asarray(x, np.float64)


def _mismatched(p, r):
    served = (p != 0) | (r != 0)
    return served, (np.abs(p - r) > 1e-2 * np.abs(r) + 1.0) & served


def tput_mismatch(prog, ref) -> float:
    served, bad = _mismatched(_np(prog), _np(ref))
    return float(bad.sum() / max(int(served.sum()), 1))


def cell_mismatch(prog, ref, attach, n_cells: int, batched: bool) -> float:
    """Share of cells with more than 1% of their served UE-TTIs off."""
    served, bad = _mismatched(_np(prog), _np(ref))
    cell = np.asarray(attach, np.int64)
    if batched:                 # (drops, tti, n): one id space over drops
        cell = cell + n_cells * np.arange(cell.shape[0])[:, None, None]
        n_cells *= cell.shape[0]
    n_served = np.bincount(cell[served], minlength=n_cells)
    n_bad = np.bincount(cell[bad], minlength=n_cells)
    has = n_served > 0
    return float(((n_bad > 1e-2 * n_served) & has).sum()
                 / max(int(has.sum()), 1))


def net_bits_err(prog, ref, tti_axis: int = 0) -> float:
    """Largest relative error of the per-TTI network total."""
    p, r = _np(prog), _np(ref)
    axes = tuple(i for i in range(p.ndim) if i != tti_axis)
    ps, rs = p.sum(axis=axes), r.sum(axis=axes)
    return float((np.abs(ps - rs) / np.maximum(np.abs(rs), 1.0)).max())


def state_mismatch(prog: dict, ref: dict) -> float:
    if not np.array_equal(np.asarray(prog["t"]), np.asarray(ref["t"])):
        return 1.0
    bad = np.zeros(np.shape(prog["pf_avg"]), bool)
    for k in ("pf_avg", "backlog"):
        p, r = _np(prog[k]), _np(ref[k])
        fin = np.isfinite(r) & np.isfinite(p)
        p, r = np.where(fin, p, 0.0), np.where(fin, r, 0.0)
        bad |= np.where(fin, np.abs(p - r) > 1e-2 * np.abs(r) + 1.0,
                        _np(prog[k]) != _np(ref[k]))
    return float(bad.mean())


def pos_err_m(prog_U, ref_U) -> float:
    return float(np.abs(_np(prog_U) - _np(ref_U)).max())


def kpi_err(prog: dict, ref: dict) -> float:
    return float(max(abs(prog[k] - ref[k]) / max(abs(ref[k]), 1e-9)
                     for k in KPI_KEYS))


def judge(numbers: dict, limits: dict) -> bool:
    """All numbers within their limits; a number without a limit, or a
    limit without a number, is a fault of the cell's files."""
    if set(numbers) != set(limits):
        raise ValueError(f"numbers {sorted(numbers)} vs limits "
                         f"{sorted(limits)}")
    return all(np.isfinite(numbers[k]) and numbers[k] <= limits[k]
               for k in numbers)
