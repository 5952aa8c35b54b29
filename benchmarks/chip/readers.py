"""Shared arithmetic of the per-layer readers in ``metrics/``.

Each reader takes a cell's ``Run`` and returns one number, or None
where the run holds nothing for it to read (no trace, no span, no step
of the kind it reads).
"""

from __future__ import annotations

from . import counts


def span_walls(run, name: str) -> list[float]:
    return [d for n, _, d in run.data.get("spans", []) if n == name]


def mean_ms(run, name: str):
    walls = span_walls(run, name)
    return sum(walls) / len(walls) * 1e3 if walls else None


def idle_share(run):
    t = run.trace
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def decode_roofline(run):
    """The decode steps' least possible time on the chip (the larger of
    FLOPs at peak and bytes at HBM bandwidth, step by step) over their
    wall time."""
    walls, w = span_walls(run, "serve.decode"), run.data.get("window")
    if not walls or not w or not run.data.get("peaks"):
        return None
    m, pk = run.data["config"], run.data["peaks"]
    bound = sum(counts.decode_step_bound_s(m, s["decode"], pk)[0]
                for s in w["steps"] if s["decode"])
    return 100.0 * bound / sum(walls)


def train_mfu(run):
    """Forward and backward FLOPs per token (recomputation not counted)
    times tokens per second over the chip's bf16 peak."""
    d = run.data
    if not d.get("peaks") or not d.get("tokens_per_s"):
        return None
    f = counts.train_flops_per_token(d["config"], d["seq"])
    return 100.0 * f * d["tokens_per_s"] / d["peaks"]["bf16_flops"]

