"""Host time per chunk: the mean over chunks of the harness span around
``step_chunk`` (wall) minus the device busy time inside it, in ms --
guard readback, KPI summary, checkpoint and dispatch."""
from bench.lib import trace


def read(run):
    if run.red is None or not run.red.spans:
        return None
    busy = trace.busy_in_spans(run.red)
    host = [(s.end - s.start - b) for s, b in zip(run.red.spans, busy)]
    return sum(host) / len(host) / 1e6
