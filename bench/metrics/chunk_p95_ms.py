"""95th percentile of the client-side chunk latency over every chunk of
the window: from calling ``step_chunk()`` to holding its KPI summary."""
import numpy as np


def read(run):
    return float(np.percentile([(e - s) * 1e3 for s, e, _ in run.spans], 95))
