"""Mean own time of the ``crrm:twin.chunk`` span: its duration less what
the spans inside it cover, over the chunks of the window, in ms."""
from bench.lib.stages import window_chunks


def read(run):
    found = window_chunks(run)
    if found is None:
        return None
    return sum(c.own_ns for c in found) / len(found) / 1e6
