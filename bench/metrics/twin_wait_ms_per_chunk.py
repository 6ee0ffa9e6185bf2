"""Mean time per chunk of the program's ``crrm:twin.wait`` span (waiting
for the chunk's outputs on the device), over the chunks of the window,
in ms."""
from bench.lib.stages import window_chunks

SPAN = "twin.wait"


def read(run):
    found = window_chunks(run)
    if found is None:
        return None
    return sum(c.parts.get(SPAN, 0) for c in found) / len(found) / 1e6
