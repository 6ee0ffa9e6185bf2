"""Summed device time of the fused Pallas kernel's operations (named
``fused_sinr`` or ``fused_sinr.<n>``) per simulated TTI, in ms."""
from bench.lib.trace import is_op

KERNEL = "fused_sinr"


def kernel_ns(red):
    return sum(ns for name, ns in red.op_ns.items() if is_op(name, KERNEL))


def read(run):
    if run.red is None or not kernel_ns(run.red):
        return None
    return kernel_ns(run.red) / 1e6 / sum(n for _, _, n in run.spans)
