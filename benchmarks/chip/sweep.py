#!/usr/bin/env python3
"""Find a serving cell's knee, and the spread of its tail at a rate:
one engine, one window per (rate, seed).

    python3 benchmarks/chip/sweep.py --workload minicpm-2b.chat \
        --rates 0.12,0.2,0.3 --seeds 7 --seconds 40 \
        [--set engine.prefill_pad=512 --set prompt.max=512]

Prints one JSON line per window: offered and completed requests, the
drain time after the window (a backlog that grows through the window
drains long), and the TTFT and inter-token percentiles.  With more than
one seed, a line per rate gives each metric's spread over the seeds,
with and without the window farthest from the median.  ``--set`` changes
one key of the cell's traffic mix (a dotted path, a JSON value).  A
measurement tool for choosing a cell's fixed rate and shape; the
benchmark's runs do not call it.
"""

import argparse
import contextlib
import json
import statistics

from tools_common import setup


def override(mix: dict, assignment: str) -> dict:
    path, value = assignment.split("=", 1)
    *outer, last = path.split(".")
    mix = json.loads(json.dumps(mix))
    node = mix
    for k in outer:
        node = node[k]
    node[last] = json.loads(value)
    return mix


def spreads(values: list[float]) -> dict:
    from benchmarks.chip.stats import spread

    med = statistics.median(values)
    far = max(range(len(values)), key=lambda k: abs(values[k] - med))
    rest = values[:far] + values[far + 1:]
    return {"median": med, "spread": spread(values),
            "spread_without_farthest": spread(rest) if len(rest) >= 2 else None}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--set", action="append", default=[])
    args = ap.parse_args()
    root = setup()
    from benchmarks.chip import generate
    from benchmarks.chip.drivers import serve_open as so
    from benchmarks.chip.harness import load_cell

    _, cell, config, mix = load_cell(root, args.workload)
    for a in args.set:
        mix = override(mix, a)
    seeds = [int(x) for x in args.seeds.split(",")]
    server = so.Server(config, mix, seeds[0])
    for r in (float(x) for x in args.rates.split(",")):
        m = dict(mix, rate_per_s=r)
        runs = []
        for seed in seeds:
            reqs = generate.serve_requests(m, seed, args.seconds, config["vocab"])
            w = so.window(lambda n: contextlib.nullcontext(), server, reqs, args.seconds)
            s = so.summarize(w)
            done = s["attempted"] - s["failed"]
            runs.append(s)
            print(json.dumps({"sweep": cell["name"], "rate_per_s": r, "seed": seed,
                              "completed_per_s": done / args.seconds, **s}), flush=True)
        if len(runs) > 2:
            print(json.dumps({"spread": cell["name"], "rate_per_s": r, "seeds": seeds,
                              **{k: spreads([s[k] for s in runs]) for k in
                                 ("ttft_p90_ms", "ttft_p50_ms", "itl_p95_ms")}}), flush=True)


if __name__ == "__main__":
    main()
