"""What the program names inside itself: engine stages and host spans.

Two kinds of names, both a contract of the program (DESIGN.md
§Observability):

* the episode engine's stages, ``jax.named_scope`` names that end up in
  each device operation's ``op_name`` metadata
  (``jit(rollout)/while/body/closed_call/sched/reduce_max``): the first
  scope of the path that names a stage is the operation's stage, with
  ``radio/gather``, ``radio/kernel`` and ``radio/scatter`` kept whole.
  On the TPU an ``XLA Ops`` event carries no ``op_name``, so it is
  looked up by the operation's own name in the compiled program's text
  (:func:`hlo_op_names`);
* the program's host spans, ``crrm:<name>`` annotations with their
  arguments (``crrm:twin.summary`` with ``readbacks=12``), written into
  the profiler trace and kept in memory by ``repro.obs.profile``.

:func:`load_xplane` reads a profile, :func:`load_json` the neutral JSON
form of ``bench/tests/data/scoped_trace.json``; :func:`stage_ns` gives
each stage's own device time inside the harness's spans, :func:`chunks`
the parts of each twin chunk.
"""
from __future__ import annotations

import json
import re
from collections import defaultdict
from typing import Dict, List, NamedTuple, Optional, Tuple

from bench.lib import trace

#: the engine's stage scopes (mac/engine.py)
STAGES = ("call_setup", "churn", "faults", "mobility", "radio", "attach",
          "link", "sched", "harq", "traffic", "telemetry")
#: the sub-scopes of ``radio`` kept apart (sim/radio.py)
RADIO_PARTS = ("gather", "kernel", "scatter")
#: the stage of an operation under no stage scope
UNSCOPED = "unscoped"
#: prefix of the program's host spans (repro.obs.profile.SPAN_PREFIX)
PROGRAM_PREFIX = "crrm:"


def stage_of(op_name: Optional[str]) -> str:
    """The stage of an operation from its ``op_name`` path.

    The path's last component is the operation's own primitive
    (``.../radio/gather`` is a gather in ``radio``), so only the scopes
    before it count."""
    scopes = (op_name or "").split("/")[:-1]
    for i, part in enumerate(scopes):
        if part in STAGES:
            if (part == "radio" and i + 1 < len(scopes)
                    and scopes[i + 1] in RADIO_PARTS):
                return f"radio/{scopes[i + 1]}"
            return part
    return UNSCOPED


_HLO_LINE = re.compile(r'^\s*(?:ROOT\s+)?%?([^\s=]+) = .*?'
                       r'metadata=\{[^}]*op_name="([^"]*)"')


def hlo_op_names(hlo_text: str) -> Dict[str, str]:
    """Each instruction's ``op_name`` from a compiled program's text
    (``compiled.as_text()``), by the instruction's own name -- the name
    its operation has on the trace's ``XLA Ops`` line."""
    out = {}
    for line in hlo_text.splitlines():
        m = _HLO_LINE.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


class Span(NamedTuple):
    name: str        # without the prefix
    start: int       # ns
    end: int         # ns
    args: dict


class ScopedTrace(NamedTuple):
    device: Dict[int, List[trace.Event]]     # chip -> operations
    spans: List[trace.Event]                 # the harness's spans
    program: List[Span]                      # the program's spans


def load_json(path) -> Tuple[ScopedTrace, Dict[str, str]]:
    """``{"device": {chip: [[name, start, end], ...]}, "spans": [[name,
    start, end], ...], "program": [[name, start, end, {args}], ...],
    "op_names": {name: op_name}}``: the trace and the op_names that the
    compiled program's text would give."""
    with open(path) as f:
        d = json.load(f)
    ev = lambda rows: [trace.Event(str(n), int(s), int(e))
                       for n, s, e in rows]
    prog = [Span(str(n), int(s), int(e), dict(a))
            for n, s, e, a in d["program"]]
    return (ScopedTrace({int(k): ev(v) for k, v in d["device"].items()},
                        ev(d["spans"]), prog), dict(d["op_names"]))


def load_xplane(path) -> ScopedTrace:
    """Read a profiler ``.xplane.pb``: the device operations, the
    harness's spans and the program's ``crrm:`` spans with their
    arguments."""
    from jax.profiler import ProfileData
    tr = trace.load_xplane(path)
    prog: List[Span] = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PROGRAM_PREFIX):
                    prog.append(Span(
                        e.name[len(PROGRAM_PREFIX):], int(e.start_ns),
                        int(e.start_ns + e.duration_ns),
                        dict(e.stats)))
    prog.sort(key=lambda e: (e.start, -e.end))
    return ScopedTrace(tr.device, tr.spans, prog)


def stage_ns(tr: ScopedTrace, op_names: Dict[str, str]) -> Dict[str, float]:
    """Own device time per stage inside the harness's spans, in ns (mean
    over chips), as ``trace.reduce_trace`` counts an operation's own
    time; ``op_names`` maps an operation's own name to its ``op_name``
    (:func:`hlo_op_names`)."""
    if not tr.spans:
        raise ValueError("trace holds no harness spans")
    win = trace.union((s.start, s.end) for s in tr.spans)
    chips = sorted(tr.device) or [0]
    out: Dict[str, float] = defaultdict(float)
    for c in chips:
        evs = tr.device.get(c, [])
        for e, own in zip(evs, trace.self_times(evs)):
            inside = trace.covered(win, e.start, e.end)
            if inside > 0:
                out[stage_of(op_names.get(e.name))] += (
                    own * inside / (e.end - e.start) / len(chips))
    return dict(out)


class Chunk(NamedTuple):
    ns: int                       # the chunk span's duration
    parts: Dict[str, int]         # child span name -> summed duration
    args: Dict[str, int]          # child span argument -> summed value
    own_ns: int                   # duration less what the children cover


def chunks(spans: List[Span], parent: str) -> List[Chunk]:
    """The spans named ``parent`` with what the spans inside each one
    (on the same clock) took and counted."""
    spans = sorted(spans, key=lambda s: (s.start, -s.end))
    out = []
    for i, top in enumerate(spans):
        if top.name != parent:
            continue
        parts: Dict[str, int] = defaultdict(int)
        args: Dict[str, int] = defaultdict(int)
        inner: List[Tuple[int, int]] = []
        j = i + 1
        while j < len(spans) and spans[j].start < top.end:
            s, j = spans[j], j + 1
            if s.end <= top.end and s.name != parent:
                parts[s.name] += s.end - s.start
                for k, v in s.args.items():
                    if isinstance(v, (int, float)):
                        args[k] += v
                inner.append((s.start, s.end))
        covered = sum(e - s for s, e in trace.union(inner))
        out.append(Chunk(top.end - top.start, dict(parts), dict(args),
                         top.end - top.start - covered))
    return out


def program_spans(run) -> Optional[List[Span]]:
    """The program's in-memory spans that lie inside the run's measured
    window (``RunRecord.spans``, host clock), or None where the program
    keeps no such record."""
    from repro.obs import profile
    recent = getattr(profile, "recent_spans", None)
    if recent is None or not run.spans:
        return None
    a, b = int(run.spans[0][0] * 1e9), int(run.spans[-1][1] * 1e9)
    return [Span(s.name, s.start_ns, s.end_ns, dict(s.args))
            for s in recent() if s.start_ns >= a and s.end_ns <= b]


def window_chunks(run) -> Optional[List[Chunk]]:
    """The twin's chunks (``crrm:twin.chunk``) among the program's spans
    in the run's window, or None where there are none."""
    spans = program_spans(run)
    return (chunks(spans, "twin.chunk") if spans else None) or None
