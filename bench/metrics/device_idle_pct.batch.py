"""Share of the traced window in which no operation ran on the device:
100 (1 - busy / window), busy being the union of the device-op
intervals."""


def read(run):
    if run.red is None:
        return None
    a, b = run.red.window
    return 100.0 * (1.0 - run.red.busy_ns / (b - a))
