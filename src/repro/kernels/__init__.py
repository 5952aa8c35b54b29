"""Pallas TPU kernels for the perf-critical hot spots (DESIGN.md §7).

``<name>.py``  — pl.pallas_call + BlockSpec VMEM tiling (TPU target)
``ops.py``     — jitted wrappers (layout, padding, GQA; ``interpret=True``
                 runs the Pallas interpreter, as the CPU tests do)
``ref.py``     — pure-jnp oracles the kernels are validated against
"""

from .ops import attention, rmsnorm_op, ssd, triad
from .ref import attention_ref, rmsnorm_ref, ssd_ref, triad_ref

__all__ = [
    "attention",
    "rmsnorm_op",
    "triad",
    "ssd",
    "attention_ref",
    "rmsnorm_ref",
    "triad_ref",
    "ssd_ref",
]
