"""Device milliseconds per round of collective ops, whatever their scope:
``all-gather``, ``all-reduce``, ``reduce-scatter``, ``collective-permute``,
``all-to-all`` and their ``-start`` / ``-done`` halves, read from each op's
HLO text. A TensorCore runs one op at a time, so this is the collective
time the step does not hide behind compute."""

from chipbench.scopes import is_collective


def read(run):
    if run.trace is None or not run.trace.ops:
        return None
    s = run.trace.op_s(is_collective)
    return s / run.rounds * 1e3 if s > 0 else None
