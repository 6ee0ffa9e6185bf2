"""The device allocator's ``peak_bytes_in_use`` after the window, in MiB.

It includes set-up (the simulator's graph build), not the rollout alone.
"""


def read(run):
    return run.peak_bytes / 2**20
