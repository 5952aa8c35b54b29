#!/usr/bin/env python3
"""Readings that a serving cell's correctness limit is set from.

    python3 benchmarks/chip/calibrate.py --workload minicpm-2b.chat \
        --seeds 11,12,13 --seconds 15 [--control]

For a serving cell, for each seed, in one process: weights and engine from the seed, one
window of the cell's traffic at its rate, then the reference over the
same sample a run compares.  It prints the program's widest logit gap
and, with ``--control``, the gap of the tokens that a float8 copy of
the reference puts first at the same positions: the control that the
limit must fail.  For a training cell, the first steps' loss, gradient
and update norms against the reference, and with ``--control`` the same
numbers of the reference with every matrix product in bfloat16.  The
benchmark's runs do not call it.
"""

import argparse
import contextlib
import json
import time

from tools_common import setup


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args()
    root = setup()
    from benchmarks.chip import generate
    from benchmarks.chip.drivers import serve_open as so
    from benchmarks.chip.harness import load_cell

    _, cell, config, mix = load_cell(root, args.workload)
    if mix["driver"] == "train_steps":
        return train(cell, config, mix, args)
    L = mix["engine"]["max_seq"]
    for seed in (int(x) for x in args.seeds.split(",")):
        t0 = time.perf_counter()
        server = so.Server(config, mix, seed)
        reqs = generate.serve_requests(mix, seed, args.seconds, config["vocab"])
        w = so.window(lambda n: contextlib.nullcontext(), server, reqs, args.seconds)
        server.free()
        chosen = so.sample(w, seed, mix["check_tokens"], mix["check_requests"])
        t1 = time.perf_counter()
        gaps = so.compare(config, seed, chosen, L)
        t2 = time.perf_counter()
        out = {"calibrate": cell["name"], "seed": seed, "tokens": int(len(gaps)),
               "requests": len(chosen), "gap_max": float(gaps.max()),
               "gap_p99": float(sorted(gaps)[int(0.99 * (len(gaps) - 1))]),
               "budget_mismatch": so.budget_mismatches(w, config["vocab"]),
               "failed": so.summarize(w)["failed"], "reference_s": t2 - t1,
               "setup_and_window_s": t1 - t0}
        if args.control:
            c = so.compare(config, seed, chosen, L, control=True)
            out.update(control_gap_max=float(c.max()),
                       control_gap_median=float(sorted(c)[len(c) // 2]),
                       control_s=time.perf_counter() - t2)
        print(json.dumps(out), flush=True)


def train(cell, config, mix, args):
    """For a training cell: the program's first steps against the
    reference; with ``--control`` also the bfloat16 reference in its
    place, and the program fed half of each batch (its first rows twice),
    a fault the check must catch."""
    from benchmarks.chip.drivers import train_steps as ts

    for seed in (int(x) for x in args.seeds.split(",")):
        t0 = time.perf_counter()
        tr = ts.Trainer(config, mix, seed)
        tr.free()
        out = {"calibrate": cell["name"], "seed": seed, **ts.compare(tr),
               "setup_s": time.perf_counter() - t0}
        if args.control:
            c = ts.compare(tr, control=True)
            out.update({"control_" + k: c[k] for k in
                        ("loss_rel", "grad_norm_gap", "update_norm_gap", "grad_leaf",
                         "update_leaf")})
            half = ts.Trainer(config, mix, seed, half_batch=True)
            half.free()
            c = ts.compare(half)
            out.update({"half_batch_" + k: c[k] for k in
                        ("loss_rel", "grad_norm_gap", "update_norm_gap")})
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
