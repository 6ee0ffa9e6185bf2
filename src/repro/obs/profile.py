"""Profiling hooks: traces, annotations, compile counters, stage timers.

Four small tools, all safe on any backend (every one degrades to a no-op
when the underlying jax facility is missing):

* :func:`trace` -- context manager around ``jax.profiler.trace``: dumps a
  TensorBoard/perfetto trace of everything launched inside it;
* :func:`annotate` -- named ``TraceAnnotation`` scope so engine phases
  (prepare / rollout / sync) are legible inside that trace;
* :class:`CompileCounter` -- counts *XLA backend compilations* process-wide
  via the ``jax.monitoring`` event stream.  Wrapping a steady-state loop in
  one is the retrace detector: a loop that re-enters XLA per iteration is
  the classic silent 100x (shape-polymorphic arguments, python-hashed
  statics, fresh closures);
* :class:`RetraceWatch` -- per-executable jit-cache-size snapshots for the
  engine/env functions (``EpisodeFns.step``/``rollout``,
  ``CrrmEnv._vmapped``): asserts that *these* callables did not pick up new
  specialisations across a region, which is sharper than the global count;
* :class:`StageTimer` -- the per-stage wall-time breakdown used by
  ``benchmarks/paper_benches.py``: blocks on stage outputs and renders an
  aligned table of stage -> (calls, total ms, share).
"""
from __future__ import annotations

import contextlib
import time
from typing import Any, Callable, Dict, Optional

import jax

#: process-wide XLA backend-compile count and seconds, fed by the
#: jax.monitoring duration event '/jax/core/compile/backend_compile_duration'
#: (one per compilation; a persistent-cache hit reports its short load).
#: Registered lazily, once; CompileCounter reads deltas.
_COMPILE_EVENTS = {"count": 0, "seconds": 0.0}
_LISTENER_STATE = {"registered": False, "available": None}


def _on_duration(name: str, secs: float, **kw) -> None:
    if name.endswith("backend_compile_duration"):
        _COMPILE_EVENTS["count"] += 1
        _COMPILE_EVENTS["seconds"] += secs


def _ensure_listener() -> bool:
    """Register the compile-event listener once; False if unsupported."""
    if not _LISTENER_STATE["registered"]:
        try:
            import jax.monitoring
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            _LISTENER_STATE["available"] = True
        except Exception:           # pragma: no cover - jax without events
            _LISTENER_STATE["available"] = False
        _LISTENER_STATE["registered"] = True
    return bool(_LISTENER_STATE["available"])


@contextlib.contextmanager
def trace(log_dir: str, create_perfetto_trace: bool = False):
    """``jax.profiler.trace`` as a guarded context manager.

    Collects a device/host trace of everything dispatched inside the
    block into ``log_dir`` (TensorBoard's profile plugin reads it).  A
    backend without profiler support degrades to a no-op rather than
    failing the caller's run.
    """
    try:
        cm = jax.profiler.trace(log_dir,
                                create_perfetto_trace=create_perfetto_trace)
    except Exception:               # pragma: no cover - no profiler backend
        yield
        return
    with cm:
        yield


def annotate(name: str):
    """A named ``TraceAnnotation`` scope (no-op without profiler support).

    Wrap engine phases so a :func:`trace` dump shows them as labelled
    spans:  ``with annotate("rollout"): fns.rollout(...)``.
    """
    try:
        return jax.profiler.TraceAnnotation(name)
    except Exception:               # pragma: no cover - no profiler backend
        return contextlib.nullcontext()


class CompileCounter:
    """Counts XLA backend compilations inside a ``with`` region.

    >>> with CompileCounter() as c:
    ...     fns.rollout(static, state, 50)   # steady state: compiles == 0
    >>> assert c.count == 0, f"unexpected retrace: {c.count} compiles"

    The canonical failure it catches is the *shape-polymorphic call*: a
    caller feeding varying shapes (or fresh static arguments) into a
    jitted function recompiles per call, silently trading the one-program
    scan for per-call tracing.  ``supported`` is False on jax builds
    without the monitoring event stream -- the count then stays 0 and
    callers should skip the assertion (tests do).  ``seconds`` is the
    backend compile time spent in the region.
    """

    def __init__(self):
        self.supported = _ensure_listener()
        self._base = self._base_s = 0
        self.count = 0
        self.seconds = 0.0

    def __enter__(self) -> "CompileCounter":
        self._base = _COMPILE_EVENTS["count"]
        self._base_s = _COMPILE_EVENTS["seconds"]
        self.count = 0
        self.seconds = 0.0
        return self

    def __exit__(self, *exc) -> None:
        self.count = _COMPILE_EVENTS["count"] - self._base
        self.seconds = _COMPILE_EVENTS["seconds"] - self._base_s


def executable_cache_size(fn) -> Optional[int]:
    """Number of compiled specialisations a ``jax.jit`` callable holds.

    None when the callable does not expose a jit cache (non-jit
    functions, older jax).  Growth across two calls with "the same"
    arguments is a retrace -- the thing :class:`RetraceWatch` asserts
    never happens to the engine executables.
    """
    probe = getattr(fn, "_cache_size", None)
    if probe is None:
        return None
    try:
        return int(probe())
    except Exception:               # pragma: no cover - version dependent
        return None


class RetraceWatch:
    """Snapshot named executables' jit caches; report growth as retraces.

    >>> watch = RetraceWatch(step=fns.step, rollout=fns.rollout)
    >>> run_many_episodes()
    >>> watch.retraces()            # {} -- or {'rollout': 2} on a bug
    >>> watch.assert_stable()       # raises listing the offenders

    The engine bakes its trace-time switches into ``make_episode_fns``,
    so in steady state every ``step``/``rollout`` call must hit an
    existing specialisation; any growth here means a caller is feeding
    shape- or static-polymorphic arguments (new ``n_tti`` values are the
    one *expected* specialisation axis -- snapshot after warm-up).
    """

    def __init__(self, **executables):
        self._fns: Dict[str, Any] = dict(executables)
        self._base = {name: executable_cache_size(f) or 0
                      for name, f in self._fns.items()}

    def retraces(self) -> Dict[str, int]:
        """name -> number of new specialisations since construction."""
        out = {}
        for name, f in self._fns.items():
            now = executable_cache_size(f)
            if now is not None and now > self._base[name]:
                out[name] = now - self._base[name]
        return out

    def assert_stable(self) -> None:
        grew = self.retraces()
        assert not grew, (
            f"unintended recompilation: {grew} (an executable picked up "
            f"new jit specialisations in a region expected to be steady "
            f"state -- check for shape-polymorphic or fresh-static "
            f"arguments)")


class StageTimer:
    """Accumulating per-stage wall-clock breakdown (host-side, blocking).

    ``time(stage, fn, *args)`` runs ``fn`` and blocks on its output (so
    async dispatch cannot leak one stage's device time into the next);
    ``stage(name)`` is the context-manager spelling for arbitrary blocks.
    ``report()`` renders stage -> (calls, total ms, share) aligned rows --
    the breakdown ``benchmarks/paper_benches.py`` prints as ``# profile:``
    comment lines.
    """

    def __init__(self):
        self._total: Dict[str, float] = {}
        self._calls: Dict[str, int] = {}

    @contextlib.contextmanager
    def stage(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            self._total[name] = self._total.get(name, 0.0) + dt
            self._calls[name] = self._calls.get(name, 0) + 1

    def time(self, name: str, fn: Callable, *args, **kw):
        """Run ``fn`` under ``stage(name)``, blocking on its output."""
        with self.stage(name):
            out = fn(*args, **kw)
            jax.block_until_ready(out)
        return out

    def total_s(self, name: str) -> float:
        return self._total.get(name, 0.0)

    def report(self, prefix: str = "") -> str:
        if not self._total:
            return f"{prefix}(no stages timed)"
        grand = sum(self._total.values())
        width = max(len(n) for n in self._total)
        rows = []
        for name, tot in sorted(self._total.items(), key=lambda kv: -kv[1]):
            share = tot / grand if grand else 0.0
            rows.append(f"{prefix}{name:<{width}}  x{self._calls[name]:<4d} "
                        f"{tot * 1e3:9.1f} ms  {share:6.1%}")
        return "\n".join(rows)
