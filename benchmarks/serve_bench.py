"""Continuous-batching serve benchmark: Poisson arrivals, mixed lengths.

Drives the ``ContinuousBatchingEngine`` with a Poisson request trace
(exponential inter-arrival gaps, mixed prompt/output lengths) and
compares token throughput against the pre-continuous-batching baseline:
batch-at-a-time generation that right-pads a fixed batch, prefills
token-by-token through the decode step, and pulls logits to the host
every token — exactly what ``ServeEngine.generate`` did before the
rewrite.  The baseline is run back-to-back with no arrival gaps (every
request available immediately), which only flatters it.

Emits ``BENCH_serve.json`` (throughput, TTFT p50/p95, per-token latency,
padded-slot waste) through the shared bench-JSON helper.

    PYTHONPATH=src python benchmarks/serve_bench.py            # full trace
    PYTHONPATH=src python benchmarks/serve_bench.py --check    # >=3x bar
    PYTHONPATH=src python benchmarks/serve_bench.py --smoke    # CI-sized
"""

from __future__ import annotations

import argparse
import sys
import time
from collections import deque
from dataclasses import dataclass

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs import get_config, list_archs
from repro.models import init_decode_state, init_params
from repro.serve import (
    ContinuousBatchingEngine,
    QueueFull,
    make_serve_step,
    prefill_pad_for,
)


@dataclass
class TraceReq:
    arrival: float  # seconds after trace start
    prompt: list[int]
    max_new: int


def make_trace(cfg, n_requests: int, rate: float, prefill_pad: int,
               max_new_range: tuple[int, int], seed: int) -> list[TraceReq]:
    """Poisson arrivals (rate req/s) with mixed prompt/output lengths."""
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / rate, size=n_requests)
    t = np.cumsum(gaps)
    out = []
    for i in range(n_requests):
        plen = int(rng.integers(2, prefill_pad + 1))
        mn = int(rng.integers(max_new_range[0], max_new_range[1] + 1))
        prompt = rng.integers(0, cfg.vocab, size=plen).astype(int).tolist()
        out.append(TraceReq(float(t[i]), prompt, mn))
    return out


# ---------------------------------------------------------------------------
# Baseline: batch-at-a-time, token-by-token prefill (the old ServeEngine)
# ---------------------------------------------------------------------------


def baseline_run(cfg, params, trace: list[TraceReq], batch: int,
                 max_seq: int) -> tuple[float, int]:
    """Process the trace in fixed arrival-order batches of ``batch``.

    Right-aligns each batch to its longest prompt, prefills one token at
    a time through the jitted decode step, then decodes until the
    *longest* request in the batch finishes (stragglers pad the batch —
    the inefficiency continuous batching removes).  Returns
    (wall_seconds, useful_tokens)."""
    step = jax.jit(make_serve_step(cfg))
    # warm the compile cache for every batch size the trace produces, so
    # the comparison is steady-state serving, not XLA compile time
    for b in {min(batch, len(trace) - i) for i in range(0, len(trace), batch)}:
        st = init_decode_state(cfg, b, max_seq, dtype=jnp.float32)
        lg, _ = step(params, st, jnp.zeros((b, 1), jnp.int32), jnp.int32(0))
        jnp.argmax(lg, axis=-1).block_until_ready()
    useful = 0
    t0 = time.perf_counter()
    for i in range(0, len(trace), batch):
        chunk = trace[i : i + batch]
        b = len(chunk)
        plen = max(len(r.prompt) for r in chunk)
        toks = np.zeros((b, plen), dtype=np.int32)
        for j, r in enumerate(chunk):
            toks[j, plen - len(r.prompt):] = r.prompt  # right-align
        state = init_decode_state(cfg, b, max_seq, dtype=jnp.float32)
        logits = None
        for t in range(plen):
            logits, state = step(
                params, state, jnp.asarray(toks[:, t : t + 1]), jnp.int32(t)
            )
        for t in range(max(r.max_new for r in chunk)):
            cur = jnp.argmax(logits, axis=-1)
            for j, r in enumerate(chunk):  # per-request host pulls (old path)
                if t < r.max_new:
                    int(cur[j])
                    useful += 1
            logits, state = step(
                params, state, cur[:, None].astype(jnp.int32),
                jnp.int32(plen + t),
            )
    return time.perf_counter() - t0, useful


# ---------------------------------------------------------------------------
# Continuous-batching engine on the same trace
# ---------------------------------------------------------------------------


def engine_run(cfg, params, trace: list[TraceReq], slots: int, max_seq: int,
               prefill_pad: int, min_admit: int = 2) -> tuple[float, int, dict]:
    """Replay the trace against the engine in real time (requests become
    visible at their Poisson arrival instants).  Returns
    (wall_seconds, useful_tokens, serve_stats)."""
    eng = ContinuousBatchingEngine(
        cfg, params, slots=slots, max_seq=max_seq, prefill_pad=prefill_pad,
        min_admit=min_admit, state_dtype=jnp.float32,
    )
    # warm-up: one throwaway request compiles the admit + decode steps
    eng.submit([1], max_new=2)
    eng.run()
    eng.reset_stats()
    pending = deque(trace)
    t0 = time.perf_counter()
    while pending or not eng.sched.idle:
        now = time.perf_counter() - t0
        while pending and pending[0].arrival <= now:
            r = pending[0]
            try:
                eng.submit(r.prompt, max_new=r.max_new,
                           arrival_t=t0 + r.arrival)
            except QueueFull:
                break  # backpressure: decode a step, then retry
            pending.popleft()
        if eng.sched.idle:
            time.sleep(min(1e-3, max(0.0, pending[0].arrival - now)))
            continue
        eng.step()
    wall = time.perf_counter() - t0
    stats = eng.serve_stats()
    return wall, stats["tokens_generated"], stats


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--arch", choices=list_archs(), default="gemma-2b")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--rate", type=float, default=1000.0,
                    help="Poisson arrival rate (req/s); the default "
                         "exceeds engine capacity so throughput measures "
                         "capacity — lower it to explore the "
                         "latency-bound (arrival-limited) regime")
    ap.add_argument("--prefill-pad", type=int, default=64)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--max-new", type=int, nargs=2, default=(8, 24),
                    metavar=("LO", "HI"))
    ap.add_argument("--min-admit", type=int, default=2,
                    help="free slots required before an admission prefill "
                         "while the batch is decoding (amortizes prefills)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="BENCH_serve.json")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized trace; writes BENCH_serve_smoke.json")
    ap.add_argument("--check", action="store_true",
                    help="fail unless engine >= 3x baseline throughput")
    args = ap.parse_args()

    if args.smoke:
        args.requests = min(args.requests, 10)
        args.prefill_pad = min(args.prefill_pad, 12)
        args.max_new = (4, 8)
        args.rate = 64.0
        if args.out == "BENCH_serve.json":
            args.out = "BENCH_serve_smoke.json"

    cfg = get_config(args.arch).reduced()
    pad = prefill_pad_for(cfg, args.prefill_pad)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    trace = make_trace(cfg, args.requests, args.rate, pad, tuple(args.max_new),
                       args.seed)

    print(f"# {cfg.name}: {args.requests} requests, rate {args.rate}/s, "
          f"pad {pad}, slots {args.slots}", file=sys.stderr)

    e_wall, e_tokens, stats = engine_run(
        cfg, params, trace, args.slots, args.max_seq, pad,
        min_admit=args.min_admit,
    )
    e_tput = e_tokens / e_wall
    print(f"engine:   {e_tokens} tok in {e_wall:.2f}s = {e_tput:.1f} tok/s",
          flush=True)

    b_wall, b_tokens, = baseline_run(cfg, params, trace, args.slots,
                                     args.max_seq)
    b_tput = b_tokens / b_wall
    print(f"baseline: {b_tokens} tok in {b_wall:.2f}s = {b_tput:.1f} tok/s",
          flush=True)
    speedup = e_tput / b_tput
    print(f"speedup:  {speedup:.2f}x", flush=True)

    rows = [
        {"name": "engine_throughput", "tok_per_s": e_tput,
         "tokens": e_tokens, "wall_s": e_wall},
        {"name": "baseline_throughput", "tok_per_s": b_tput,
         "tokens": b_tokens, "wall_s": b_wall},
        {"name": "ttft", "p50_ms": stats.get("ttft_p50_ms"),
         "p95_ms": stats.get("ttft_p95_ms")},
        {"name": "itl", "p50_ms": stats.get("itl_p50_ms"),
         "p95_ms": stats.get("itl_p95_ms")},
        {"name": "slot_occupancy",
         "padded_slot_waste": stats["padded_slot_waste"],
         "prefill_steps": stats["prefill_steps"],
         "decode_steps": stats["decode_steps"]},
    ]
    try:
        from benchmarks.bench_json import bench_record, write_bench_json
    except ImportError:  # invoked as a script: benchmarks/ is sys.path[0]
        from bench_json import bench_record, write_bench_json

    write_bench_json(args.out, bench_record(
        "serve",
        rows,
        config={
            "arch": cfg.name, "slots": args.slots, "requests": args.requests,
            "rate_req_s": args.rate, "prefill_pad": pad,
            "max_seq": args.max_seq, "max_new": list(args.max_new),
            "seed": args.seed, "smoke": args.smoke,
        },
        speedup_vs_batch_at_a_time=speedup,
        throughput_tok_s=e_tput,
        baseline_tok_s=b_tput,
    ))

    if args.check and speedup < 3.0:
        print(f"CHECK FAILED: speedup {speedup:.2f}x < 3x", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
