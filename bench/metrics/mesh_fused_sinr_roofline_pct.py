"""The fused kernel's share of its roofline on one chip of the mesh,
counted on the cell's work per chip: the rows that moved on that chip
(the movers over the shards) against every cell on every chunk, over the
kernel's device time on a chip (mean over the chips).  The work and the
least time are those of ``fused_sinr_roofline_pct``; a kernel that
recomputes padded rows reads a lower share."""
from bench.lib.trace import is_op
from bench.metrics.fused_sinr_roofline_pct import KERNEL, share_pct, work


def read(run):
    if run.red is None or "shards" not in run.work:
        return None
    ns = sum(v for name, v in run.red.op_ns.items() if is_op(name, KERNEL))
    if not ns:
        return None
    w = run.work
    ops, nbytes = work(w["rows"] // w["shards"], w["cells"], w["chunks"],
                       w["sectors"])
    ttis = sum(n for _, _, n in run.spans)
    return share_pct(ops * ttis, nbytes * ttis, ns / 1e9, run.device_kind)
