"""Device milliseconds per round in the depthwise stage: the ops whose HLO
``op_name`` holds the program's ``cnn.depthwise`` scope (each block's
depthwise conv, its batch norm and its activation), forward
(``jvp(cnn.depthwise)``) and backward (``transpose(jvp(cnn.depthwise))``).

The ops run inside the round's accumulation scan, whose ``while``
``chipbench/scopes.py`` books to ``p2p.fanout`` as a whole; so per device
this sums the ops themselves, the union of their intervals. A round is
read only where the trace holds it whole: the profiler drops events once a
window holds too many (a 5 s window of this cell kept about half its
rounds), and the window's last round runs past its end. So the reader takes
the outermost ``while`` ops that hold a scoped op, keeps those that hold as
many ops as the fullest of them (the step runs the same ops every round),
and divides the scoped time inside them by their number. The map from
instruction name to scope comes from the cell's step compiled again
(``scopes.step_hlo``), once a run. None where the trace is missing, or the
program has no such scope or no loop around it."""

import re
import sys

from chipbench import scopes
from chipbench.scopes import _OP_NAME
from chipbench.trace import union_ns

SCOPE = "cnn.depthwise"
_IN_SCOPE = re.compile(r"(?:^|/)(?:[\w.]+\()*" + re.escape(SCOPE) + r"\)*(?:/|$)")


def scoped_instructions(hlo_text: str) -> set:
    """Names of the instructions whose op_name holds the scope, bare or
    inside JAX's ``jvp(...)`` / ``transpose(...)`` wrappers."""
    out = set()
    for line in hlo_text.splitlines():
        m = _OP_NAME.search(line)
        if m and " = " in line and _IN_SCOPE.search(m.group(1)):
            out.add(scopes.instr_name(line))
    return out


def whole_rounds(ops, names):
    """The rounds of one device's ops that the trace holds whole: for each,
    the ns of the scoped ops' union inside its ``while``. Also returns, for
    a log, every round's (start, end, ops held, scoped ns)."""
    ops = sorted(ops)
    loops = []
    for s, e, n in ops:
        if scopes.opcode(n) == "while" and not (loops and e <= loops[-1][1]):
            loops.append([s, e, 0, []])
    i = 0
    for s, e, n in ops:
        while i < len(loops) and s >= loops[i][1]:
            i += 1
        if i < len(loops) and s >= loops[i][0] and e <= loops[i][1]:
            loops[i][2] += 1
            if scopes.instr_name(n) in names:
                loops[i][3].append((s, e))
    seen = [(s, e, k, union_ns(iv)) for s, e, k, iv in loops if iv]
    full = max((k for _, _, k, _ in seen), default=0)
    return [ns for _, _, k, ns in seen if k == full], seen


def read(run):
    if run.trace is None or not run.trace.ops:
        return None
    try:
        names = scoped_instructions(scopes.step_hlo(run.cell))
    except Exception as e:  # noqa: BLE001 - a metric that cannot be read is None
        print(f"[chipbench] depthwise_ms: no HLO map: {e!r}", file=sys.stderr, flush=True)
        return None
    tr, per_device = run.trace, []
    for d in tr.devices:
        held, seen = whole_rounds(tr.ops[d], names)
        print(f"[chipbench] depthwise_ms: device {d}: {len(held)} of {len(seen)} rounds "
              f"held whole ({run.rounds} in the window)", file=sys.stderr, flush=True)
        if held:
            per_device.append(sum(held) / len(held))
    ms = sum(per_device) / len(per_device) * 1e-6 if per_device else 0.0
    return ms if ms > 0 else None
