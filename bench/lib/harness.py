"""One run of one cell: set-up, the measured window, the check, the line.

Everything that belongs to one configuration, cell, entry path or metric
is found by name from ``BENCHMARK.json``:

* ``bench/configs/<config>.json`` -- the deployment (``CRRM_parameters``);
* ``bench/workloads/<cell>.json`` -- the traffic mix: parameter overrides,
  the driver and its arguments, and the limits of the check;
* ``bench/drivers/<driver>.py`` -- an entry path: ``make(ctx)`` builds and
  warms the program and returns an object with ``span``, ``call()``
  (one timed unit, blocking, returns the TTIs it simulated),
  ``finish()``, ``sample()``, ``work`` and ``failed``;
* ``bench/metrics/<metric>.py`` -- ``read(run)`` returns the metric's
  value from a :class:`RunRecord`, or None when it finds nothing to read.
"""
from __future__ import annotations

import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Callable, List, NamedTuple, Optional, Tuple

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def param_seed(seed: int) -> int:
    """A 31-bit seed for the program's own ``PRNGKey(params.seed)``."""
    return seed % 2147483647


def bench_key(seed: int):
    """The run's JAX key from a seed of any size (``PRNGKey`` keeps only
    32 bits of its argument, so the high part is folded in)."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


class Context(NamedTuple):
    workload: dict
    params: dict          # CRRM_parameters keyword arguments
    args: dict            # the driver's arguments
    seed: int
    rng: np.random.Generator
    log: Callable[[str], None]


class RunRecord(NamedTuple):
    spans: List[Tuple[float, float, int]]   # host clock: start, end, TTIs
    setup_s: float
    peak_bytes: int
    device_kind: str
    work: dict
    red: Any              # trace.Reduction of a traced run, else None


def log(msg: str) -> None:
    print(f"# {msg}", flush=True)


class CompileCount:
    """XLA backend compilations inside a ``with`` region."""

    _count = [0]
    _registered = [False]

    def __enter__(self):
        import jax.monitoring
        if not self._registered[0]:
            def listen(name, secs, **kw):
                if name.endswith("backend_compile_duration"):
                    CompileCount._count[0] += 1
            jax.monitoring.register_event_duration_secs_listener(listen)
            self._registered[0] = True
        self._base = self._count[0]
        return self

    def __exit__(self, *exc):
        self.count = self._count[0] - self._base


def context(cell: str, seed: int,
            shrink: Optional[dict] = None) -> Tuple[dict, Context]:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next((w for w in bench["workloads"] if w["name"] == cell), None)
    if entry is None:
        raise SystemExit(f"no cell {cell!r} in BENCHMARK.json")
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == entry["config"])
    config = json.loads((ROOT / cfg_entry["file"]).read_text())
    workload = json.loads((BENCH / "workloads" / f"{cell}.json").read_text())
    params = dict(config["CRRM_parameters"])
    params.update(workload.get("params", {}))
    args = dict(workload.get("driver_args", {}))
    if shrink:
        params.update(shrink.get("params", {}))
        args.update(shrink.get("driver_args", {}))
    ctx = Context(workload=workload, params=params, args=args, seed=seed,
                  rng=np.random.default_rng(seed), log=log)
    return bench, ctx


def metrics_for(bench: dict, cell: str, trace: bool) -> List[dict]:
    """The metrics this cell reports in this kind of run."""
    if not trace:
        return [m for m in bench["end_to_end"]
                if cell in m.get("workloads", [cell])]
    e2e = {m["name"] for m in metrics_for(bench, cell, False)}
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", [cell] if m["moves"] in e2e
                             else [])]


def run(cell: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_chip: bool = True, shrink=None,
        wrap: Optional[Callable] = None) -> int:
    """One run; prints the result line and returns the exit code."""
    bench, ctx = context(cell, seed, shrink)
    import jax
    devices = jax.devices()
    chips = ctx.workload["chips"]
    if require_chip:
        if devices[0].platform != "tpu" or len(devices) < chips:
            print(f"bench: cell {cell} needs {chips} TPU chip(s); JAX "
                  f"reports {len(devices)} {devices[0].platform} "
                  f"device(s)", file=sys.stderr)
            return 2
        from bench.lib.cache import enable_compile_cache
        log(f"compile cache {enable_compile_cache()}")
    dev = devices[0]
    log(f"cell {cell} seed {seed} on {dev.device_kind} x {len(devices)}, "
        f"jax {jax.__version__}")
    driver = load_module(BENCH / "drivers" / f"{ctx.workload['driver']}.py"
                         ).make(ctx)
    if wrap is not None:
        wrap(driver)

    spans: List[Tuple[float, float, int]] = []
    trace_dir = Path(tempfile.mkdtemp(prefix="bench-trace-")) if trace \
        else None
    try:
        with CompileCount() as cc:
            if trace:
                jax.profiler.start_trace(str(trace_dir))
            t_first = time.perf_counter()
            t_end = t_first + seconds
            while True:
                t0 = time.perf_counter()
                if spans and t0 >= t_end:
                    break
                with jax.profiler.TraceAnnotation("bench:" + driver.span):
                    n = driver.call()
                spans.append((t0, time.perf_counter(), n))
            if trace:
                jax.profiler.stop_trace()
        if cc.count:
            print(f"bench: {cc.count} program(s) compiled inside the "
                  f"measured window", file=sys.stderr)
            return 3
        peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devices[:chips])
        red = None
        if trace:
            from bench.lib import trace as tr
            pb = sorted(trace_dir.rglob("*.xplane.pb"))[-1]
            red = tr.reduce_trace(tr.load_xplane(pb))
            kernel = driver.work.get("kernel")
            if kernel:
                named = {n: ns for n, ns in red.op_ns.items()
                         if tr.is_op(n, kernel)}
                log(f"trace: device ops named {kernel!r}: "
                    f"{sorted(named.items())[:8]}")
                if not named:
                    print(f"bench: the compiled program holds {kernel!r} "
                          f"but the trace shows no device operation of "
                          f"that name", file=sys.stderr)
                    return 4
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    record = RunRecord(spans=spans, setup_s=t_first - t_start,
                       peak_bytes=peak, device_kind=dev.device_kind,
                       work=driver.work, red=red)
    log(f"window: {len(spans)} calls, {sum(s[2] for s in spans)} TTIs in "
        f"{spans[-1][1] - spans[0][0]:.3f} s; set-up {record.setup_s:.3f} s; "
        f"peak {peak / 2**20:.1f} MiB")

    driver.finish()
    from bench.lib import check
    limits = ctx.workload["limits"]
    numbers = check.numbers(driver.sample(), sorted(limits))
    from bench.lib.compare import judge
    correct = judge(numbers, limits)

    metrics = {}
    for m in metrics_for(bench, cell, trace):
        v = load_module(BENCH / "metrics" / f"{m['name']}.py").read(record)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    line = {"correct": bool(correct), "attempted": len(spans),
            "failed": int(driver.failed), "metrics": metrics,
            "device": device}
    if red is not None:
        from bench.lib import trace as tr
        device["busy_s"] = red.busy_ns / 1e9
        device["window_s"] = (red.window[1] - red.window[0]) / 1e9
        line["breakdown"] = tr.breakdown(red)
    line["check"] = {k: {"value": numbers[k], "limit": limits[k]}
                     for k in sorted(numbers)}
    print(json.dumps(line), flush=True)
    for k in sorted(numbers):
        print(f"check {k} {numbers[k]!r} limit {limits[k]!r}",
              file=sys.stderr, flush=True)
    return 0
