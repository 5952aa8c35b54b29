"""Model, decode step: share of the roofline (HBM bytes bound it at these sizes) over decode wall time, %."""

from benchmarks.chip import readers


def read(run):
    return readers.decode_roofline(run)
