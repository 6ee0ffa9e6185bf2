"""Observability: in-scan KPI telemetry, profiling hooks, compiled reports.

Three orthogonal windows into an otherwise-opaque compiled episode
(DESIGN.md §Observability):

* :mod:`repro.obs.telemetry` -- the :class:`~repro.obs.telemetry.Telemetry`
  pytree accumulated as a ``lax.scan`` *output* inside the TTI engine:
  per-TTI/per-cell served bits, granted RBs, HARQ ACK/NACK/retx/drop
  counters, A3 handover events, buffer occupancy, Jain fairness and (in
  the incremental radio mode) dirty-row counts.  A trace-time switch: off
  (the default) compiles the exact legacy program.
* :mod:`repro.obs.profile` -- ``jax.profiler`` trace context manager,
  the program's ``crrm:`` host spans (``annotate``, also kept in memory:
  ``recent_spans``), a compile/retrace counter that catches unintended
  recompilation of engine and env executables, and the per-stage
  wall-time breakdown helper the benchmark harness uses.
* :mod:`repro.obs.report` -- AOT cost analysis of the compiled TTI step:
  HLO FLOPs/bytes, collective wire bytes (``analysis/hlo.py``) and the
  roofline table (``analysis/roofline.py``), written as JSON + markdown
  artifacts.
"""
from repro.obs.telemetry import Telemetry, summarize, format_summary  # noqa: F401
from repro.obs.profile import (  # noqa: F401
    CompileCounter, RetraceWatch, StageTimer, annotate, recent_spans, trace)
