"""The comparison that decides ``correct`` rejects its control.

At a tiny size on the CPU, for every cell: the program's sampled call
agrees with the float32 reference within the cell's limits, and the same
reference computed in bfloat16 (the control, in the program's place)
fails at least one of them.
"""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(Path(__file__).parent)]

from bench.lib import check  # noqa: E402
from bench.lib.compare import judge  # noqa: E402
from bench.lib.harness import BENCH, context, load_module  # noqa: E402
from cells import cells, shrink  # noqa: E402


@pytest.mark.parametrize("cell", cells())
def test_program_passes_and_control_fails(cell):
    _, ctx = context(cell, 2**34 + 3, shrink(cell))
    driver = load_module(BENCH / "drivers" / f"{ctx.workload['driver']}.py"
                         ).make(ctx)
    for _ in range(3):
        driver.call()
    driver.finish()
    sample = driver.sample()
    limits = ctx.workload["limits"]
    prog = check.numbers(sample, sorted(limits))
    ctrl = check.numbers(sample, sorted(limits), control=True)
    assert judge(prog, limits), prog
    assert not judge(ctrl, limits), ctrl
