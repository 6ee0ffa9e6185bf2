#!/usr/bin/env python3
"""Readings that the limits of a cell's check are set from.

    python3 bench/control.py --workload uma_mmtc.movers20 \\
        --seeds 101 102 103 --seconds 3

For each seed, in one process: build the cell at its own size, run its
timed calls for ``--seconds``, then compare the sampled call with the
float32 reference twice -- once as the program produced it (the lower
reading of each number) and once with the reference computed in
bfloat16 in the program's place (the control; its smallest reading over
the seeds is the upper one).  One JSON line per seed.  The benchmark's
own runs do not run this.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("bench/control.py: needs a TPU", file=sys.stderr)
        return 2
    from bench.lib import check
    from bench.lib.cache import enable_compile_cache
    from bench.lib.harness import BENCH, context, load_module
    enable_compile_cache()
    for seed in args.seeds:
        _, ctx = context(args.workload, seed)
        driver = load_module(BENCH / "drivers" /
                             f"{ctx.workload['driver']}.py").make(ctx)
        t_end, calls = time.perf_counter() + args.seconds, 0
        while calls == 0 or time.perf_counter() < t_end:
            driver.call()
            calls += 1
        driver.finish()
        sample = driver.sample()
        names = sorted(ctx.workload["limits"])
        del driver
        print(json.dumps({
            "workload": args.workload, "seed": seed, "calls": calls,
            "program": check.numbers(sample, names),
            "control": check.numbers(sample, names, control=True)}),
            flush=True)
        del sample
    return 0


if __name__ == "__main__":
    sys.exit(main())
