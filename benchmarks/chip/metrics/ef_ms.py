"""Device milliseconds per round in error feedback: the ops of the
program's ``p2p.ef`` scope (the residual added to the gradient, the new
residual, the EF bank's re-gather), by the compiled step's HLO
(``chipbench/scopes.py``)."""

from chipbench.scopes import scope_ms


def read(run):
    return scope_ms(run, "p2p.ef")
