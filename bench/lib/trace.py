"""From a profiler trace to the numbers the per-layer metrics read.

Two halves, kept apart so that the arithmetic can be checked on a small
recorded trace (``bench/tests/data/small_trace.json``):

* :func:`load_xplane` reads the ``.xplane.pb`` that ``jax.profiler``
  writes into a :class:`Trace`: the device operations of each chip (the
  ``XLA Ops`` line of every ``/device:TPU:<i>`` plane), the harness's own
  host spans (``TraceAnnotation`` names that start with ``bench:``), and
  the other host events, all on the profiler's one clock, in ns;
* :func:`reduce_trace` turns a :class:`Trace` into busy time (the union
  of device-op intervals), idle gaps inside the traced window, device
  time per operation name, and each gap labelled with what the host was
  doing in it: the harness span it falls in and the host event that
  overlaps it most.
"""
from __future__ import annotations

import bisect
import json
import re
from collections import defaultdict
from typing import Dict, List, NamedTuple, Tuple

import numpy as np

SPAN_PREFIX = "bench:"


class Event(NamedTuple):
    name: str
    start: int      # ns
    end: int        # ns


class Trace(NamedTuple):
    device: Dict[int, List[Event]]   # chip -> operations on it
    spans: List[Event]               # the harness's spans, prefix removed
    host: List[Event]                # other host events


def load_json(path) -> Trace:
    """A trace in the neutral JSON form: ``{"device": {chip: [[name,
    start, end], ...]}, "spans": [...], "host": [...]}``."""
    with open(path) as f:
        d = json.load(f)
    ev = lambda rows: [Event(str(n), int(s), int(e)) for n, s, e in rows]
    return Trace(device={int(k): ev(v) for k, v in d["device"].items()},
                 spans=ev(d["spans"]), host=ev(d["host"]))


#: the line of a device plane that holds one event per XLA operation
DEVICE_LINE = "XLA Ops"


def op_name(text: str) -> str:
    """An operation's own name from its event name on the ``XLA Ops``
    line, which on the TPU is the whole HLO instruction
    (``%fused_sinr.10 = (f32[...]) custom-call(...)``): ``fused_sinr.10``.
    The operands' names stay out, so an operation is never counted under
    the name of the kernel whose output it reads."""
    m = re.match(r"%?([^\s=]+) = ", text)
    return m.group(1) if m else text


def is_op(name: str, kernel: str) -> bool:
    """Whether operation ``name`` is an instance of ``kernel``
    (``fused_sinr``, ``fused_sinr.10``)."""
    return re.fullmatch(re.escape(kernel) + r"(\.\d+)*", name) is not None


def load_xplane(path) -> Trace:
    """Read a profiler ``.xplane.pb`` into a :class:`Trace`."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    device: Dict[int, List[Event]] = {}
    spans: List[Event] = []
    host: List[Event] = []
    for plane in pd.planes:
        tpu = re.match(r"/device:TPU:(\d+)", plane.name)
        if tpu:
            chip = int(tpu.group(1))
            for line in plane.lines:
                if line.name == DEVICE_LINE:
                    device.setdefault(chip, []).extend(
                        Event(op_name(e.name), int(e.start_ns),
                              int(e.start_ns + e.duration_ns))
                        for e in line.events)
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns <= 0:
                        continue
                    s, t = int(e.start_ns), int(e.start_ns + e.duration_ns)
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append(Event(e.name[len(SPAN_PREFIX):], s, t))
                    else:
                        host.append(Event(e.name, s, t))
    spans.sort(key=lambda e: e.start)
    return Trace(device=device, spans=spans, host=host)


def union(intervals) -> List[Tuple[int, int]]:
    """Merge (start, end) intervals into sorted disjoint ones."""
    out: List[List[int]] = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(merged, a: int, b: int) -> int:
    """ns of the sorted disjoint intervals ``merged`` inside [a, b]."""
    i = bisect.bisect_right(merged, (a, a))
    i = max(0, i - 1)
    tot = 0
    while i < len(merged) and merged[i][0] < b:
        s, e = merged[i]
        tot += max(0, min(e, b) - max(s, a))
        i += 1
    return tot


def gaps(merged, a: int, b: int) -> List[Tuple[int, int]]:
    """The idle intervals of [a, b] that ``merged`` leaves uncovered."""
    out, cur = [], a
    for s, e in merged:
        if e <= a or s >= b:
            continue
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < b:
        out.append((cur, b))
    return out


class Reduction(NamedTuple):
    window: Tuple[int, int]          # first span start .. last span end
    busy_ns: float                   # mean over chips of busy in window
    busy: Dict[int, List[Tuple[int, int]]]   # per chip, merged
    op_ns: Dict[str, float]          # own device time per op name, mean/chip
    gap_ns: Dict[str, Tuple[float, int]]     # label -> (total ns, count)
    spans: List[Event]


#: shorter idle gaps lie between the operations of one program: the
#: device's own scheduling, not the host
SHORT_GAP_NS = 20_000


def label_gaps(gap_list, spans: List[Event], host: List[Event]) -> List[str]:
    """Label each gap ``<harness span>/<host event overlapping it most>``.

    A gap outside every harness span is ``between calls``; a gap shorter
    than :data:`SHORT_GAP_NS` is ``<span>/between ops``.
    """
    long_ = [i for i, (g0, g1) in enumerate(gap_list)
             if g1 - g0 >= SHORT_GAP_NS]
    g0s = np.asarray([gap_list[i][0] for i in long_], np.int64)
    g1s = np.asarray([gap_list[i][1] for i in long_], np.int64)
    hs = np.asarray([h.start for h in host], np.int64)
    he = np.asarray([h.end for h in host], np.int64)
    # the long gaps each host event overlaps: [lo, hi) of ``long_``
    lo = np.searchsorted(g1s, hs, side="right")
    hi = np.searchsorted(g0s, he, side="left")
    best: Dict[int, Tuple[int, int, str]] = {}
    for k in np.flatnonzero(hi > lo):
        h = host[k]
        for i in range(lo[k], hi[k]):
            ov = int(min(h.end, g1s[i]) - max(h.start, g0s[i]))
            key = (ov, -(h.end - h.start))
            if ov > 0 and (i not in best or key > best[i][:2]):
                best[i] = (ov, -(h.end - h.start), h.name)
    best = {long_[i]: v for i, v in best.items()}
    span_starts = [s.start for s in spans]
    out = []
    for i, (g0, g1) in enumerate(gap_list):
        mid = (g0 + g1) // 2
        j = bisect.bisect_right(span_starts, mid) - 1
        span = (spans[j].name if j >= 0 and spans[j].end > mid
                else "between calls")
        if g1 - g0 < SHORT_GAP_NS:
            out.append(f"{span}/between ops")
        else:
            out.append(span if i not in best else f"{span}/{best[i][2]}")
    return out


def self_times(events: List[Event]) -> List[int]:
    """Each event's duration less that of the events nested in it.

    A while loop is an operation of its own on the ``XLA Ops`` line and
    spans the operations of its body; its own time is what its body's
    operations leave.  Events that merely overlap are not nested."""
    own = [e.end - e.start for e in events]
    order = sorted(range(len(events)),
                   key=lambda i: (events[i].start, -events[i].end))
    stack: List[int] = []
    for i in order:
        e = events[i]
        while stack and events[stack[-1]].end <= e.start:
            stack.pop()
        for j in reversed(stack):
            if events[j].end >= e.end:
                own[j] -= e.end - e.start
                break
        stack.append(i)
    return own


def reduce_trace(tr: Trace) -> Reduction:
    """Busy union, idle gaps, device time per name, labelled gaps."""
    if not tr.spans:
        raise ValueError("trace holds no harness spans")
    a, b = tr.spans[0].start, max(s.end for s in tr.spans)
    chips = sorted(tr.device) or [0]
    busy = {c: union((e.start, e.end) for e in tr.device.get(c, ()))
            for c in chips}
    busy_ns = sum(covered(busy[c], a, b) for c in chips) / len(chips)
    op_ns: Dict[str, float] = defaultdict(float)
    for c in chips:
        evs = tr.device.get(c, [])
        for e, own in zip(evs, self_times(evs)):
            ov = min(e.end, b) - max(e.start, a)
            if ov > 0:
                op_ns[e.name] += own * ov / (e.end - e.start) / len(chips)
    gap_list = gaps(busy[chips[0]], a, b)
    gap_ns: Dict[str, List[float]] = {}
    for g, lab in zip(gap_list, label_gaps(gap_list, tr.spans, tr.host)):
        tot = gap_ns.setdefault(lab, [0.0, 0])
        tot[0] += g[1] - g[0]
        tot[1] += 1
    return Reduction(window=(a, b), busy_ns=busy_ns, busy=busy,
                     op_ns=dict(op_ns),
                     gap_ns={k: (v[0], v[1]) for k, v in gap_ns.items()},
                     spans=list(tr.spans))


def busy_in_spans(red: Reduction, chip: int = 0) -> List[float]:
    """Device busy ns inside each harness span, in span order."""
    merged = red.busy.get(chip, [])
    return [covered(merged, s.start, s.end) for s in red.spans]


def breakdown(red: Reduction, top: int = 10) -> dict:
    """The ``breakdown`` of a traced result line, in seconds."""
    ops = sorted(red.op_ns.items(), key=lambda kv: -kv[1])[:top]
    gp = sorted(red.gap_ns.items(), key=lambda kv: -kv[1][0])[:top]
    return {"device_ops": [[n, ns / 1e9] for n, ns in ops],
            "idle_gaps": [[f"{n} ({c} gaps)", ns / 1e9]
                          for n, (ns, c) in gp]}
