"""Device-to-host transfers per chunk: the ``readbacks`` arguments of the
spans inside each ``crrm:twin.chunk`` (``twin.summary``, ``twin.guard``),
summed over the chunks of the window, over their number."""
from bench.lib.stages import window_chunks

ARG = "readbacks"


def read(run):
    found = window_chunks(run)
    if found is None:
        return None
    return sum(c.args.get(ARG, 0) for c in found) / len(found)
