"""Simulated TTIs completed in the window over the window's wall seconds.

The window runs from the first timed call's dispatch to the completion of
the last call that began before ``--seconds`` ran out; a drop's TTI
counts as one TTI.
"""


def read(run):
    ttis = sum(n for _, _, n in run.spans)
    return ttis / (run.spans[-1][1] - run.spans[0][0])
