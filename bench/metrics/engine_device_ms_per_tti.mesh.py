"""``engine_device_ms_per_tti`` on the UE-sharded cell: device busy time
inside the harness's spans around each rollout call, per simulated TTI,
mean over the chips of the mesh, in ms."""
from bench.lib import trace


def read(run):
    if run.red is None or not run.red.busy:
        return None
    busy = [sum(trace.busy_in_spans(run.red, c)) for c in run.red.busy]
    return sum(busy) / len(busy) / 1e6 / sum(n for _, _, n in run.spans)
