"""token_ms (ms): the window's length over the units (tokens) it
completed."""


def read(run):
    return run.window_s / run.units * 1e3
