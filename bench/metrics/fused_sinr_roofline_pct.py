"""The fused kernel's share of its roofline, counted on the cell's work.

The work is that of the cell, not of the implementation: per TTI the
dirty rows are recomputed against every cell on every frequency chunk.
Operations: a fixed count per link and chunk (below).  Bytes: the least
the work must move -- each dirty row's position read once, the cell
arrays read once, each row's outputs (interference total and serving
power per chunk, best value and index) written once.  The least time is
the larger of operations over the peak rate and bytes over the HBM
bandwidth (``bench/lib/peaks.py``); the share is that over the kernel's
device time, so it cannot pass 100% unless the kernel ran faster than
the chip allows.  The published peak is the matrix unit's bf16 rate;
this float32 vector kernel is therefore bound by bytes.
"""
from bench.lib.peaks import peaks
from bench.lib.trace import is_op

#: per link: distances (3 sub, 4 mul, 2 add, 2 sqrt) 11; UMa pathloss
#: (log10, the LOS and NLOS affine forms, breakpoint select, max, dB to
#: linear) 13
OPS_LINK = 24
#: per link, three-sector pattern: bearing, offset wrap (sin, cos,
#: atan2), quadratic attenuation, clamp, dB to linear, product
OPS_SECTOR = 12
#: per link and chunk: power product, interference accumulation, the
#: attachment compare and select
OPS_CHUNK = 4
F32 = 4
KERNEL = "fused_sinr"


def work(rows: int, cells: int, chunks: int, sectors: int):
    """(operations, bytes) of one TTI's dirty-row recompute."""
    links = rows * cells
    ops = links * (OPS_LINK + (OPS_SECTOR if sectors > 1 else 0)
                   + OPS_CHUNK * chunks)
    read_b = F32 * (rows * 3 + cells * (3 + chunks + 1))
    write_b = F32 * rows * (2 * chunks + 2)
    return ops, read_b + write_b


def least_s(ops: float, nbytes: float, device_kind: str) -> float:
    pk = peaks(device_kind)
    return max(ops / pk.flops, nbytes / pk.hbm_bw)


def share_pct(ops, nbytes, seconds: float, device_kind: str) -> float:
    return 100.0 * least_s(ops, nbytes, device_kind) / seconds


def read(run):
    if run.red is None:
        return None
    ns = sum(v for name, v in run.red.op_ns.items() if is_op(name, KERNEL))
    if not ns:
        return None
    w = run.work
    ops, nbytes = work(w["rows"], w["cells"], w["chunks"], w["sectors"])
    ttis = sum(n for _, _, n in run.spans)
    return share_pct(ops * ttis, nbytes * ttis, ns / 1e9, run.device_kind)
