"""Own device time of the collective operations per simulated TTI, mean
over the chips, in ms: PF's per-cell ``pmax`` and ``psum`` and whatever
else crosses the mesh.

XLA names a collective instruction after the JAX primitive that made it
(``pmax.30 = f32[57,1] all-reduce(...)``), so the operations are found by
opcode in the driver's compiled program (``collective_ops``, carried in
``work["collectives"]``) and read by those names in the trace.  The time
is each operation's own interval on the chip's ``XLA Ops`` line.  The
mesh program's collectives are synchronous ``all-reduce`` instructions,
so that is exposed time: the chip does nothing else meanwhile.  An async
pair (``-start``/``-done``) would count only its two markers, not the
transfer it overlaps.
"""
import re

#: an instruction whose opcode is a collective: ``%<name> = <shape>
#: <opcode>(``, the shape possibly a tuple
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = .*?\s"
                    r"(?:all-reduce|all-gather|reduce-scatter|"
                    r"collective-permute|all-to-all)(?:-start|-done)?\(",
                    re.M)
#: a computation's header line, and a fusion instruction's callee
_COMP = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+) .*\{\s*$")
_FUSION = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = .*?\sfusion\(.*?"
                     r"calls=%([\w.\-]+)")


def collective_ops(hlo_text: str) -> list:
    """The names the trace gives the collectives of a compiled program:
    each collective instruction's own name, or that of the fusion that
    holds it."""
    comp, found, callers = None, [], {}
    for line in hlo_text.splitlines():
        head = _COMP.match(line)
        if head and " = " not in line:
            comp = head.group(1)
            continue
        fusion = _FUSION.match(line)
        if fusion:
            callers[fusion.group(2)] = fusion.group(1)
        instr = _INSTR.match(line)
        if instr:
            found.append((comp, instr.group(1)))
    names = {callers.get(comp, name) for comp, name in found}
    return sorted(names)


def read(run):
    if run.red is None or "collectives" not in run.work:
        return None
    names = set(run.work["collectives"])
    ns = sum(v for name, v in run.red.op_ns.items() if name in names)
    return ns / 1e6 / sum(n for _, _, n in run.spans)
