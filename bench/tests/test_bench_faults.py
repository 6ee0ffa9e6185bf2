"""A run whose timed path is broken underneath comes out not correct.

The harness runs each cell on the CPU at a tiny size (its look for a chip
skipped) with one fault planted in the program the driver times:

* ``unchanged`` -- the call returns the state it was given;
* ``half_batch`` -- half of the batch (of drops, else of UEs) left out
  and given the mean of the rest;
* ``altered`` -- one TTI's answer (every UE's served throughput in the
  call's first TTI) altered by 5% where it is produced.

The exchange between chips cannot be left out: every cell runs on one
chip.
"""
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(Path(__file__).parent)]

from bench.lib.harness import run  # noqa: E402
from cells import cells, shrink  # noqa: E402


def _copy(tree):
    return jax.tree_util.tree_map(jnp.copy, tree)


def unchanged(out, state, batched):
    return (state,) + tuple(out[1:])


def half_batch(out, state, batched):
    tput = out[1]
    axis = 0 if batched else tput.ndim - 1
    half = tput.shape[axis] // 2
    rest = jax.lax.slice_in_dim(tput, half, tput.shape[axis], axis=axis)
    mean = jnp.broadcast_to(rest.mean(axis=axis, keepdims=True),
                            jax.lax.slice_in_dim(tput, 0, half,
                                                 axis=axis).shape)
    tput = jnp.concatenate([mean, rest], axis=axis)
    return (out[0], tput) + tuple(out[2:])


def altered(out, state, batched):
    tput = out[1]
    tput = (tput.at[:, 0].multiply(1.05) if batched
            else tput.at[0].multiply(1.05))
    return (out[0], tput) + tuple(out[2:])


def planting(fault):
    def wrap(driver):
        program, batched = driver.program, driver.span == "drops"

        def broken(static, state, *args):
            saved = _copy(state)
            return fault(program(static, state, *args), saved, batched)

        driver.program = broken
        driver.call()           # compile the broken path before the window
    return wrap


@pytest.mark.parametrize("fault", [unchanged, half_batch, altered],
                         ids=lambda f: f.__name__)
@pytest.mark.parametrize("cell", cells())
def test_fault_makes_the_run_not_correct(cell, fault, capsys):
    rc = run(cell, 2**35 + 9, 0.2, False, t_start=time.perf_counter(),
             require_chip=False, shrink=shrink(cell), wrap=planting(fault))
    out, err = capsys.readouterr()
    assert rc == 0, err
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is False, line["check"]
