#!/usr/bin/env python3
"""Where a cell's time goes, by the program's own names, from one trace.

    python3 bench/stage_table.py --workload uma_mmtc.movers20 --seed 7 \\
        --calls 5 --out stages_movers20.json

Builds and warms the cell's driver as ``bench/run.py`` does, then
profiles ``--calls`` timed calls (Python tracer off) and prints one JSON
object: the own device time of each engine stage (``jax.named_scope``,
``bench/lib/stages.py``) per simulated TTI beside the device busy time
per TTI that ``engine_device_ms_per_tti`` reads, and, where the program
wrote ``crrm:`` host spans, the parts of each span ``twin.chunk``.  Each
operation's ``op_name`` comes from the compiled program's text.  No
check, no result line: a reading aid for ``PERF.md``, not a run of the
benchmark.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def compiled_text(driver) -> str:
    """The text of the program the driver's timed call runs."""
    prog = driver.program
    if hasattr(prog, "as_text"):
        return prog.as_text()
    if driver.span == "step_chunk":
        srv = driver.srv
        args = (srv.static, srv.state, srv.power, srv.fairness)
    else:
        args = (driver.static, driver.state)
    return prog.lower(*args).compile().as_text()


def table(ns: dict, ttis: int) -> dict:
    tot = sum(ns.values())
    return {k: {"ms_per_tti": v / 1e6 / ttis, "pct": 100.0 * v / tot}
            for k, v in sorted(ns.items(), key=lambda kv: -kv[1])}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    from bench.lib import harness, stages, trace
    _, ctx = harness.context(args.workload, args.seed)
    if jax.default_backend() == "tpu":
        from bench.lib.cache import enable_compile_cache
        enable_compile_cache()
    driver = harness.load_module(
        harness.BENCH / "drivers" / f"{ctx.workload['driver']}.py").make(ctx)
    setup_s = time.perf_counter() - T_START
    tmp = Path(tempfile.mkdtemp(prefix="stage-table-"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    ttis, t0 = 0, time.perf_counter()
    try:
        jax.profiler.start_trace(str(tmp), profiler_options=opts)
        for _ in range(args.calls):
            with jax.profiler.TraceAnnotation("bench:" + driver.span):
                ttis += driver.call()
        jax.profiler.stop_trace()
        wall = time.perf_counter() - t0
        scoped = stages.load_xplane(sorted(tmp.rglob("*.xplane.pb"))[-1])
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    own = stages.stage_ns(scoped, stages.hlo_op_names(compiled_text(driver)))
    busy = sum(trace.busy_in_spans(trace.reduce_trace(
        trace.Trace(scoped.device, scoped.spans, []))))
    out = {
        "cell": args.workload, "seed": args.seed,
        "device": jax.devices()[0].device_kind, "setup_s": setup_s,
        "calls": args.calls, "ttis": ttis, "wall_s": wall,
        "busy_ms_per_tti": busy / 1e6 / ttis,
        "stage_sum_ms_per_tti": sum(own.values()) / 1e6 / ttis,
        "stages": table(own, ttis),
    }
    found = stages.chunks(scoped.program, "twin.chunk")
    if found:
        n = len(found)
        parts = {k for c in found for k in c.parts}
        ckpt = [s.end - s.start for s in scoped.program
                if s.name == "twin.checkpoint"]
        out["twin"] = {
            "chunks": n,
            "chunk_ms": sum(c.ns for c in found) / n / 1e6,
            "parts_ms_per_chunk": {k: sum(c.parts.get(k, 0)
                                          for c in found) / n / 1e6
                                   for k in sorted(parts)},
            "unspanned_ms_per_chunk": sum(c.own_ns for c in found) / n / 1e6,
            "args_per_chunk": {k: sum(c.args.get(k, 0) for c in found) / n
                               for k in sorted({a for c in found
                                                for a in c.args})},
            "ckpt_ms": sum(ckpt) / len(ckpt) / 1e6 if ckpt else None,
        }
    text = json.dumps(out, indent=1)
    print(text, flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(text + "\n")
    driver.finish()
    return 0


if __name__ == "__main__":
    sys.exit(main())
