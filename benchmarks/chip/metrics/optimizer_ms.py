"""Device milliseconds per round in the optimizer: the ops of the
program's ``p2p.optimizer`` scope (the schedule, the momentum and the
parameters' update), by the compiled step's HLO (``chipbench/scopes.py``)."""

from chipbench.scopes import scope_ms


def read(run):
    return scope_ms(run, "p2p.optimizer")
