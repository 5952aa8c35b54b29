"""Open-loop serving through ``ContinuousBatchingEngine``.

Set-up makes the weights from the seed (one jitted call), builds the
engine at the mix's geometry, and runs one request through it so that
its admit and decode steps are compiled (or loaded from the cache)
before the window.  The window then offers the mix's requests at their
due times; a request is submitted with its due time as its arrival, so
time to first token counts every wait.  After the window the engine
drains what is due, up to ``drain_cap_s``; a request still unfinished
then has failed.

Tokens are stamped on the host: a request's first token when its admit
step returns (``first_token_t``), every later one when the engine step
that made it returns.  The inter-token gaps are the differences.

Correctness: once the engine is freed, a sample of finished requests
drawn from the seed, the one with the most served tokens among them, is
run through the float32 reference with its served tokens appended, and
the widest gap by which a served token's reference logit lies below the
reference's best is held to the configuration's limit.  Every finished
request must also have returned exactly its budget of in-vocabulary ids.
"""

from __future__ import annotations

import dataclasses
import gc
import time

import numpy as np

from .. import generate, weights
from ..harness import Run, log
from ..stats import percentile


def model_config(config: dict):
    from repro.models.config import ModelConfig

    names = {f.name for f in dataclasses.fields(ModelConfig)}
    return ModelConfig(**{k: v for k, v in config.items() if k in names})


def reference(config: dict):
    import importlib

    return importlib.import_module(
        f"benchmarks.chip.references.{config['reference']}")


class Server:
    """The engine and its weights for one seed, ready to take a window."""

    def __init__(self, config: dict, mix: dict, seed: int):
        import jax
        import jax.numpy as jnp
        from repro.serve.engine import ContinuousBatchingEngine

        self.config, self.mix, self.seed = config, mix, seed
        geo = mix["engine"]
        params = weights.make_params(config, seed, jnp.bfloat16)
        jax.block_until_ready(params)
        self.eng = ContinuousBatchingEngine(
            model_config(config), params, slots=geo["slots"],
            max_seq=geo["max_seq"], prefill_pad=geo["prefill_pad"],
            max_queue=mix.get("max_queue", 4096))
        warm = self.eng.submit([1] * geo["prefill_pad"], max_new=2)
        self.eng.run()
        assert warm.done
        self.eng.reset_stats()

    def free(self) -> None:
        self.eng = None
        gc.collect()


def window(ctx_span, server: Server, reqs: list[dict], seconds: float,
           on_open=None, on_close=None) -> dict:
    """Offer ``reqs`` on schedule, drain, and return the records."""
    eng, clock = server.eng, time.perf_counter
    cap_s = server.mix["drain_cap_s"]
    recs = [dict(r, req=None, sub_t=None, tok_t=[]) for r in reqs]
    steps, inflight, queued = [], [], []
    i, n = 0, len(recs)
    if on_open:
        on_open()
    t0 = clock()
    end, cap = t0 + seconds, t0 + seconds + cap_s
    open_ = True
    while True:
        now = clock()
        while i < n and t0 + recs[i]["due"] <= now:
            r = recs[i]
            r["req"] = eng.submit(r["prompt"], max_new=r["max_new"],
                                  arrival_t=t0 + r["due"])
            r["sub_t"] = now
            queued.append(r)
            i += 1
        if open_ and now >= end:
            open_ = False
            if on_close:
                on_close()
        if eng.sched.idle:
            if i == n:
                break
            with ctx_span("bench.wait_arrival"):
                time.sleep(max(0.0, t0 + recs[i]["due"] - now))
            continue
        if now > cap:
            break
        s0 = clock()
        with ctx_span("bench.engine_step"):
            eng.step()
        s1 = clock()
        admitted = [r for r in queued if r["req"].admit_t >= 0]
        fresh = {id(r) for r in admitted}
        if admitted:
            queued = [r for r in queued if r["req"].admit_t < 0]
            inflight += admitted
        decoded = []
        for r in inflight:
            q = r["req"]
            new = len(q.tokens) - len(r["tok_t"])
            if id(r) in fresh:
                r["tok_t"].append(q.first_token_t)
                new -= 1
            r["tok_t"].extend([s1] * new)
            if new:
                decoded.append(len(r["prompt"]) + len(q.tokens) - 1)
        steps.append({"t0": s0, "t1": s1, "decode": decoded,
                      "admit": [len(r["prompt"]) for r in admitted],
                      "admit_t1": max((r["req"].first_token_t for r in admitted),
                                      default=None)})
        inflight = [r for r in inflight if not r["req"].done]
    if open_ and on_close:
        on_close()
    return {"t0": t0, "end": end, "cap": cap, "recs": recs, "steps": steps}


def summarize(w: dict) -> dict:
    """End-to-end values over every request due in the window."""
    ttft, gaps, span_admit, lag = [], [], 0, []
    admit_ends = sorted(s["admit_t1"] for s in w["steps"] if s["admit"])
    failed = 0
    for r in w["recs"]:
        q, due = r["req"], w["t0"] + r["due"]
        if r["sub_t"] is not None:
            lag.append(r["sub_t"] - due)
        if q is None or not q.done:
            failed += 1
            ttft.append((q.first_token_t if q is not None and q.first_token_t >= 0
                         else w["cap"]) - due)
            continue
        ttft.append(q.first_token_t - due)
        ts = r["tok_t"]
        for a, b in zip(ts, ts[1:]):
            gaps.append(b - a)
            j = np.searchsorted(admit_ends, a, side="right")
            span_admit += bool(j < len(admit_ends) and admit_ends[j] <= b)
    out = {"attempted": len(w["recs"]), "failed": failed,
           "ttft_p90_ms": percentile(ttft, 90) * 1e3,
           "ttft_p50_ms": percentile(ttft, 50) * 1e3,
           "itl_p95_ms": percentile(gaps, 95) * 1e3 if gaps else float("nan"),
           "itl_p50_ms": percentile(gaps, 50) * 1e3 if gaps else float("nan"),
           "gaps": len(gaps), "gap_admit_share": span_admit / max(1, len(gaps)),
           "lag_p50_ms": percentile(lag, 50) * 1e3, "lag_max_ms": max(lag) * 1e3,
           "admits": len(admit_ends), "steps": len(w["steps"]),
           "drain_s": (max(s["t1"] for s in w["steps"]) - w["end"]) if w["steps"] else 0.0}
    edges = [0.1, 0.25, 0.5, 0.75, 1.0, 1.5, 2.0, 3.0, 5.0, 10.0, float("inf")]
    hist = np.histogram(ttft, bins=[0.0] + edges)[0].tolist()
    out["ttft_hist_s"] = dict(zip([f"<{e}" for e in edges], hist))
    return out


def sample(w: dict, seed: int, tokens: int, most: int) -> list[dict]:
    """Finished requests for the reference: the one with the most served
    tokens, then others in an order drawn from the seed, until the
    sample holds ``tokens`` served tokens or ``most`` requests."""
    done = [r for r in w["recs"] if r["req"] is not None and r["req"].done]
    if not done:
        return []
    done.sort(key=lambda r: -len(r["req"].tokens))
    rest = [done[k] for k in np.random.default_rng(seed + 1).permutation(
        np.arange(1, len(done)))]
    out, total = [done[0]], len(done[0]["req"].tokens)
    for r in rest:
        if total >= tokens or len(out) >= most:
            break
        out.append(r)
        total += len(r["req"].tokens)
    return out


def compare(config: dict, seed: int, chosen: list[dict], length: int,
            control: bool = False) -> np.ndarray:
    seqs = [r["prompt"] + r["req"].tokens[:-1] for r in chosen]
    spans = [(len(r["prompt"]) - 1, len(r["prompt"]) - 1 + len(r["req"].tokens))
             for r in chosen]
    served = [t for r in chosen for t in r["req"].tokens]
    return reference(config).served_gaps(config, seed, seqs, spans, served, length,
                                         control=control)


def budget_mismatches(w: dict, vocab: int) -> int:
    bad = 0
    for r in w["recs"]:
        q = r["req"]
        if q is not None and q.done:
            bad += (len(q.tokens) != r["max_new"]
                    or any(not 0 <= t < vocab for t in q.tokens))
    return bad


def run(ctx) -> Run:
    from repro.obs import trace as obs_trace

    config, mix = ctx.config, ctx.mix
    reqs = generate.serve_requests(mix, ctx.seed, ctx.seconds, config["vocab"])
    server = Server(config, mix, ctx.seed)
    setup_s = time.perf_counter() - ctx.t_start
    if ctx.trace:
        obs_trace.enable_trace(capacity=1 << 20)
        obs_trace.reset_trace()
    w = window(ctx.span, server, reqs, ctx.seconds,
               on_open=ctx.start_trace, on_close=ctx.stop_trace)
    events = obs_trace.events() if ctx.trace else []
    obs_trace.disable_trace()
    summary = summarize(w)
    log(cell=ctx.cell["name"], setup_s=setup_s, **summary)

    mem = ctx.memory_peak()
    mismatches = budget_mismatches(w, config["vocab"])
    server.free()
    trace = ctx.reduce_trace()
    chosen = sample(w, ctx.seed, mix["check_tokens"], mix["check_requests"])
    gaps = compare(config, ctx.seed, chosen, mix["engine"]["max_seq"])
    gap = float(gaps.max()) if len(gaps) else float("inf")
    limit = config["check"]["logit_gap_max"]
    log(check_requests=len(chosen), check_tokens=int(len(gaps)),
        gap_p50=float(np.median(gaps)) if len(gaps) else None)
    checks = {"logit_gap_max": (gap, limit), "budget_mismatch": (mismatches, 0)}
    correct = gap <= limit and mismatches == 0
    spans = [(e[0], e[2], e[3]) for e in events if e[1] == "X"]
    e2e = {k: summary[k] for k in ("ttft_p90_ms", "itl_p95_ms")}
    e2e["setup_s"] = setup_s
    data = {"window": w, "summary": summary, "spans": spans, "config": config,
            "peaks": ctx.peaks}
    return Run(correct=correct, attempted=summary["attempted"], failed=summary["failed"],
               e2e=e2e, checks=checks, memory_peak_bytes=mem, data=data, trace=trace)
