"""Train step: forward and backward FLOPs per token times tokens/s over the bf16 peak, %."""

from benchmarks.chip import readers


def read(run):
    return readers.train_mfu(run)
