"""Mean duration of one ``crrm:twin.checkpoint`` span (an automatic
checkpoint of the guarded twin) inside the window, in ms."""
from bench.lib.stages import program_spans

SPAN = "twin.checkpoint"


def read(run):
    ckpt = [s.end - s.start for s in program_spans(run) or ()
            if s.name == SPAN]
    return sum(ckpt) / len(ckpt) / 1e6 if ckpt else None
