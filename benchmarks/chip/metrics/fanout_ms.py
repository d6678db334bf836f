"""Device milliseconds per round in the fan-out layer: the ops of the
program's ``p2p.fanout`` scope (the loss and its gradient, the m > 1
accumulation loop, clipping), by the compiled step's HLO
(``chipbench/scopes.py``)."""

from chipbench.scopes import scope_ms


def read(run):
    return scope_ms(run, "p2p.fanout")
