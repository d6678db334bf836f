"""Seconds from the process's start to the window's: imports, JAX and chip
start-up, compilation or the compile cache's load, weights and data on the
device, and the first (checked) rounds, which warm the step up."""


def read(run):
    return run.setup_s
