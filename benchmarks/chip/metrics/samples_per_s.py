"""Training samples of every round completed in the window, summed over
the peers, over the window's length (from its first dispatch to its last
completion)."""


def read(run):
    return run.samples / run.window_s
