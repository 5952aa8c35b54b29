"""Serve steps: mean wall time of the decode steps (serve.decode spans), ms."""

from benchmarks.chip import readers


def read(run):
    return readers.mean_ms(run, "serve.decode")
