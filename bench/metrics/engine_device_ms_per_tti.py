"""Device busy time inside the harness's spans around each rollout call,
per simulated TTI (counted as ``sim_ttis_per_s`` counts them), in ms."""
from bench.lib import trace


def read(run):
    if run.red is None:
        return None
    busy = sum(trace.busy_in_spans(run.red))
    return busy / 1e6 / sum(n for _, _, n in run.spans)
