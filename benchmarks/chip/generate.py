"""The one traffic generator: every mix under ``traffic/`` is parameters
for it.

Open-loop serving (``serve_requests``): a window of ``seconds`` holds
exactly ``N = round(rate_per_s * seconds)`` arrivals, at N sorted
uniform times drawn from the seed: a Poisson stream conditioned on its
count, so the count does not vary from run to run while the bursts do.
The prompt and output lengths are the N quantiles of their clipped
lognormal distributions, in an order drawn from the seed, which also
draws the prompt ids: every seed offers the same work, at other times
and in another order.

Training (``train_batch``): rows of uniform token ids, a fresh batch for
every step, all rows different.
"""

from __future__ import annotations

from statistics import NormalDist

import numpy as np


def _quantile_lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the midpoint quantiles of a lognormal with the
    given median and sigma, rounded and clipped to [min, max]."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.rint(spec["median"] * np.exp(spec["sigma"] * z)).astype(np.int64)
    return np.clip(x, spec["min"], spec["max"])


def request_count(mix: dict, seconds: float) -> int:
    return int(round(mix["rate_per_s"] * seconds))


def serve_requests(mix: dict, seed: int, seconds: float, vocab: int) -> list[dict]:
    """The window's requests, in arrival order: ``due`` (seconds after
    the window opens), ``prompt`` (token ids) and ``max_new``."""
    n = request_count(mix, seconds)
    if n < 1:
        raise ValueError(f"rate {mix['rate_per_s']}/s gives no request in {seconds} s")
    rng = np.random.default_rng(seed)
    due = np.sort(rng.uniform(0.0, seconds, n))
    plens = rng.permutation(_quantile_lengths(mix["prompt"], n))
    outs = rng.permutation(_quantile_lengths(mix["output"], n))
    return [
        {"due": float(due[i]),
         "prompt": rng.integers(0, vocab, int(plens[i]), dtype=np.int32).tolist(),
         "max_new": int(outs[i])}
        for i in range(n)
    ]


def train_batch(mix: dict, seed: int, step: int, vocab: int) -> dict:
    """Step ``step``'s batch: ``tokens`` and next-token ``labels``,
    (batch, seq) int32, from one (seed, step) stream."""
    rng = np.random.default_rng((seed, step))
    rows = rng.integers(0, vocab, (mix["batch"], mix["seq"] + 1), dtype=np.int32)
    return {"tokens": rows[:, :-1], "labels": rows[:, 1:]}
