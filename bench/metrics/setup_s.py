"""Process start to the first timed call: imports, device, the cell built
on the device from the seed, compile or cache load, warm-up."""


def read(run):
    return run.setup_s
