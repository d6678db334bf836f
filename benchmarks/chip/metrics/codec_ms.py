"""Device milliseconds per round in the codec: the ops of the program's
``p2p.encode`` and ``p2p.decode`` scopes (uniform draws, bucketing and
padding, the Pallas kernels, reshapes back to leaves), by the compiled
step's HLO (``chipbench/scopes.py``). The payload's gather is not in it."""

from chipbench.scopes import scope_ms


def read(run):
    return scope_ms(run, "p2p.encode", "p2p.decode")
