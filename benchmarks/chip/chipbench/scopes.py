"""Device time by layer: the program's named scopes, read back through the
compiled step's optimized HLO.

The P2P step opens one ``jax.named_scope`` per layer (``p2p.fanout``,
``p2p.ef``, ``p2p.exchange``, ``p2p.encode``, ``p2p.gather``, ``p2p.decode``,
``p2p.optimizer``). A scope is compile-time metadata: it lands in the
``op_name`` of every HLO instruction it holds, and a TPU trace names each
op by that instruction's text without its metadata. So the map from
instruction name to scope comes from the compiled step's ``as_text()``,
and a trace's ops are summed by scope.

The map is built in a traced run only, after the windows, by compiling the
cell's step again: the same program compiles to the same instruction names
(on a TPU v5e every op of the traced windows was found in the map). A
program without the scopes maps nothing, and the readers of the scope
metrics then return None.
"""
from __future__ import annotations

import bisect
import functools
import json
import re
import sys
from collections import defaultdict

from chipbench.trace import union_ns

SCOPES = ("p2p.fanout", "p2p.ef", "p2p.exchange", "p2p.encode", "p2p.gather",
          "p2p.decode", "p2p.optimizer")
UNSCOPED = "unscoped"
COLLECTIVES = {"all-gather", "all-reduce", "reduce-scatter", "collective-permute", "all-to-all"}

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OPERAND = re.compile(r"%([\w.\-]+)")


def instr_name(text: str) -> str:
    """The instruction name of an HLO line or of a trace op's text: what
    comes before `` = ``, without ``ROOT`` and ``%``."""
    return text.split(" = ", 1)[0].strip().removeprefix("ROOT ").lstrip("%")


def _after_shape(rest: str) -> int:
    """Index in ``rest`` (the text after `` = ``) where the opcode starts."""
    if rest.startswith("("):  # a tuple shape: skip to its closing parenthesis
        depth = 0
        for i, c in enumerate(rest):
            depth += (c == "(") - (c == ")")
            if depth == 0:
                return i + 2
    return rest.find(" ") + 1


def _opcode_and_operands(rest: str):
    i = _after_shape(rest)
    j = rest.find("(", i)
    if i <= 0 or j < 0:
        return None, []
    depth, k = 0, j
    for k in range(j, len(rest)):
        depth += (rest[k] == "(") - (rest[k] == ")")
        if depth == 0:
            break
    return rest[i:j], _OPERAND.findall(rest[j:k])


@functools.lru_cache(maxsize=1 << 16)
def opcode(text: str):
    """The opcode of an HLO line or of a trace op's text
    (``%x.1 = f32[8]{0} all-gather-start(...)`` -> ``all-gather-start``)."""
    m = _INSTR.match(text)
    return _opcode_and_operands(m.group(2))[0] if m else None


def is_collective(text: str) -> bool:
    """A collective op, its ``-start`` and ``-done`` halves included."""
    op = opcode(text) or ""
    return re.sub(r"-(start|done)$", "", op) in COLLECTIVES


def innermost(op_name: str):
    """The innermost ``p2p.*`` scope of an op_name, or None."""
    found = [c for c in op_name.split("/") if c in SCOPES]
    return found[-1] if found else None


def hlo_scopes(text: str) -> dict:
    """{instruction name: scope} for every instruction of an HLO module's
    text. An instruction whose own op_name has no scope takes that of its
    first user that has one, else that of its first operand that has one
    (copies, bitcasts and async halves XLA inserted); what is left maps to
    ``unscoped``."""
    scope, operands, users, order = {}, {}, defaultdict(list), []
    for line in text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name, rest = m.groups()
        _, ops = _opcode_and_operands(rest)
        meta = _OP_NAME.search(rest)
        order.append(name)
        operands[name] = ops
        scope[name] = innermost(meta.group(1)) if meta else None
        for o in ops:
            users[o].append(name)

    def settle(rule):
        changed = True
        while changed:
            changed = False
            for n in order:
                if scope[n] is None:
                    s = next((scope[u] for u in rule(n) if scope.get(u)), None)
                    if s:
                        scope[n], changed = s, True

    settle(lambda n: users[n])
    settle(lambda n: users[n] + operands[n])
    return {n: s or UNSCOPED for n, s in scope.items()}


def scope_seconds(trace, hlo_map: dict) -> dict:
    """Device seconds of each scope in a trace: per device the union of
    the scope's op intervals, averaged over the devices. An op that lies
    wholly inside a ``while`` on its device counts for the ``while``'s
    scope, so a loop's body is not counted twice."""
    if not trace.ops:
        return {}
    tot = defaultdict(float)
    for d in trace.devices:
        ops = sorted(trace.ops[d])
        loops = []  # the outermost loops: a nested one lies inside the last
        for s, e, n in ops:
            if opcode(n) == "while" and not (loops and e <= loops[-1][1]):
                loops.append((s, e, hlo_map.get(instr_name(n), UNSCOPED)))
        starts = [s for s, _, _ in loops]
        spans = defaultdict(list)
        for s, e, n in ops:
            sc = hlo_map.get(instr_name(n), UNSCOPED)
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and e <= loops[i][1]:
                sc = loops[i][2]
            spans[sc].append((s, e))
        for sc, iv in spans.items():
            tot[sc] += union_ns(iv) * 1e-9 / len(trace.ops)
    return dict(tot)


_LAST: list = [None, None]  # the trace last reduced, and its seconds by scope


def step_hlo(cell) -> str:
    """The optimized HLO text of the cell's compiled step, compiled as the
    run compiles it."""
    import jax

    from chipbench import data
    from chipbench.system import System

    system = System(cell.config, cell.traffic, jax.devices())
    blocks = jax.eval_shape(lambda k: data.make_blocks(
        k, traffic=cell.traffic, mcfg=cell.config, sharding=system.data), jax.random.PRNGKey(0))
    return system.compile(blocks[0]).as_text()


def layer_seconds(run):
    """Seconds per scope in the run's traced window, ``unscoped``
    included, or None where the trace or the program's scopes are missing.
    Built once per run (every scope metric reads it) and logged."""
    if run.trace is None or not run.trace.ops:
        return None
    if _LAST[0] is not run.trace:
        try:
            hlo_map = hlo_scopes(step_hlo(run.cell))
        except Exception as e:  # noqa: BLE001 - a metric that cannot be read is None
            print(f"[chipbench] no scope map: {e!r}", file=sys.stderr, flush=True)
            hlo_map = {}
        secs = None
        if set(hlo_map.values()) - {UNSCOPED}:
            secs = scope_seconds(run.trace, hlo_map)
            names = {instr_name(n) for d in run.trace.devices for _, _, n in run.trace.ops[d]}
            print("[chipbench] device scopes " + json.dumps({
                "seconds": secs, "busy_s": run.trace.busy_s(), "rounds": run.rounds,
                "trace_ops_not_in_hlo": len(names - hlo_map.keys())}),
                file=sys.stderr, flush=True)
        _LAST[:] = [run.trace, secs]
    return _LAST[1]


def scope_ms(run, *names):
    """Device ms per round in the given scopes, or None."""
    secs = layer_seconds(run)
    if secs is None:
        return None
    return sum(secs.get(n, 0.0) for n in names) / run.rounds * 1e3
