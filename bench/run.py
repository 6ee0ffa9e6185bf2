#!/usr/bin/env python3
"""The benchmark: one run of one cell of ``BENCHMARK.json`` on the chip.

    python3 bench/run.py --workload uma_mmtc.movers20 --seed 7 \\
        --seconds 20 --trace 0

Set-up (import, device, the cell's simulator built on the device from the
seed, compile or cache load, warm-up) is timed from process start to the
first timed call.  Then the cell's driver runs timed calls for
``--seconds``; ``--trace 1`` profiles that window and reports the cell's
per-layer metrics instead of its end-to-end ones.  Afterwards one call of
the window, drawn from the seed, is checked against the plain reference
(``bench/lib/reference.py``).  The last line of stdout is the result as
JSON; ``#`` lines before it are notes.  Without a TPU (or with fewer chips
than the cell asks for) it exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from bench.lib.harness import run
    return run(args.workload, args.seed, args.seconds, bool(args.trace),
               t_start=T_START)


if __name__ == "__main__":
    sys.exit(main())
