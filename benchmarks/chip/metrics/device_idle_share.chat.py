"""Device: 1 - busy union over the traced window, %."""

from benchmarks.chip import readers


def read(run):
    return readers.idle_share(run)
