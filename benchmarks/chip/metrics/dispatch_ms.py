"""Host milliseconds per round inside the compiled step's call (argument
checks, transfer of the call to the runtime), on the host clock."""


def read(run):
    return run.dispatch_s / run.dispatches * 1e3
