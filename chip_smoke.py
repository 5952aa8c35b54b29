#!/usr/bin/env python3
"""Drive the serve and train paths once on a TPU, through the normal entry
points, with random weights from ``--seed``.

    python chip_smoke.py              # one chip: device, serve, train
    python chip_smoke.py --chips 4    # four chips: Dmap redistribution
                                      # and the data-parallel train step

Phases on one chip:

* device — the first JAX device must be a TPU; any other backend exits
  nonzero (there is no fallback).
* serve — minicpm-2b at its published widths (40 layers, d_model 2304,
  vocab 122753) in bf16, in ``ContinuousBatchingEngine(slots=4,
  max_seq=2048, prefill_pad=512)``: 8 requests (prompts 64–512 tokens,
  32–128 new tokens, one sampled at temperature 0.8), four of them
  submitted mid-decode.  Every request must return exactly its budget of
  in-vocabulary ids, and a greedy request rerun alone must give the same
  tokens as its scheduled run.
* train — minicpm-2b widths cut to 4 layers, batch 4 x 1024, three steps
  of ``launch/train.py``'s jitted, donated step: finite loss, every
  parameter leaf changed.

With ``--chips 4`` only the cross-chip path runs: the Dmap corner turn,
a block-cyclic map and the halo exchange on a 16384 x 16384 float32
field over 4 devices, each bitwise equal to NumPy and to the PythonMPI
local parts; then the data-parallel train step on 4 devices, whose
step-0 loss must match the one-device loss on the same batch.

Everything runs in this one process: a chip belongs to the process that
first touched JAX, so nothing here starts a child that uses JAX.  Every
line but the last is one JSON object labelled with the device kind
(compile seconds, wall seconds after ``block_until_ready``, memory
stats, serve stats).  The last line, printed only when every phase
passed, is ``{"ok": true, "device": {...}}``; any failure raises and
exits nonzero.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import gc
import json
import math
import statistics
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.dist.hints import mesh_context  # noqa: E402
from repro.dist.sharding import batch_shardings  # noqa: E402
from repro.launch import _jax_selftest as bridge  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_local_mesh  # noqa: E402
from repro.launch.train import build_train_step  # noqa: E402
from repro.models import init_params  # noqa: E402
from repro.obs import trace  # noqa: E402
from repro.serve.engine import ContinuousBatchingEngine  # noqa: E402
from repro.train.data import batch_iterator  # noqa: E402
from repro.train.train_step import init_opt_state  # noqa: E402

ARCH = "minicpm-2b"
SERVE = dict(slots=4, max_seq=2048, prefill_pad=512)
N_REQUESTS = 8
MID_DECODE_STEPS = 8   # the second half of the requests arrives after these
SAMPLED = 5            # the one request at temperature 0.8
RERUN = 6              # the greedy request rerun alone
TRAIN = dict(n_layers=4, batch=4, seq=1024, steps=3)
FIELD_N = 16384        # side of the float32 field the 4-chip bridge moves
# step-0 loss, 4-device data-parallel vs one device: the same math, with
# the batch mean and the matmul tiling split differently across chips
LOSS_RTOL = 1e-3

# repro.obs's spans of JAX's stages: tracing, lowering, compiling (or
# loading from the persistent cache)
_COMPILE_SPANS = ("jax.trace", "jax.lower", "jax.compile")


def check(cond, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


class Report:
    """JSON lines on stdout, each labelled with the device kind.

    ``listen()`` turns ``repro.obs`` tracing on; ``collect()`` folds the
    compile spans it recorded since the last call into the seconds spent
    tracing, lowering and compiling (or loading from the persistent
    cache) per jitted function name, and the persistent cache's hits and
    misses."""

    def __init__(self, kind: str):
        self.kind = kind
        self.compile_s: dict[str, float] = collections.defaultdict(float)
        self.cache_events: collections.Counter = collections.Counter()

    def listen(self) -> None:
        trace.enable_trace()

    def collect(self) -> None:
        for name, _ph, _ts, dur, attrs in trace.events():
            if name in _COMPILE_SPANS:  # tracing names "f", the rest "jit(f)"
                fun = attrs["fun_name"].removeprefix("jit(").removesuffix(")")
                self.compile_s[fun] += dur
                if "cache" in attrs:
                    self.cache_events[f"cache_{attrs['cache']}s"] += 1
        trace.reset_trace()

    def emit(self, **fields) -> None:
        print(json.dumps({"device_kind": self.kind, **fields}), flush=True)

    def step(self, phase: str, name: str, walls: list[float], **extra) -> None:
        """Compile seconds, the first call (compile included) and the
        steady calls after it, all wall seconds to a synchronized result."""
        self.collect()
        rest = walls[1:]
        self.emit(
            phase=phase, step=name, compile_s=self.compile_s.get(name, 0.0),
            calls=len(walls), first_call_wall_s=walls[0],
            steady_wall_s=dict(
                min=min(rest), median=statistics.median(rest), max=max(rest),
            ) if rest else None,
            **extra,
        )

    def memory(self, phase: str) -> None:
        stats = [d.memory_stats() or {} for d in jax.local_devices()]
        self.emit(
            phase=phase, what="memory",
            peak_bytes_in_use=[s.get("peak_bytes_in_use") for s in stats],
            bytes_in_use=[s.get("bytes_in_use") for s in stats],
        )


def serve_phase(rep: Report, cfg, seed: int, slots: int, max_seq: int,
                prefill_pad: int, new_range=(32, 128)) -> dict:
    params = init_params(cfg, jax.random.PRNGKey(seed), dtype=jnp.bfloat16)
    eng = ContinuousBatchingEngine(cfg, params, slots=slots, max_seq=max_seq,
                                   prefill_pad=prefill_pad)
    rng = np.random.default_rng(seed)
    lo, hi = prefill_pad // 8, prefill_pad
    plens = rng.integers(lo, hi + 1, N_REQUESTS)
    budgets = rng.integers(new_range[0], new_range[1] + 1, N_REQUESTS)
    plens[:2], budgets[:2] = (hi, lo), new_range[::-1]   # both extremes
    prompts = [rng.integers(0, cfg.vocab, int(n)).tolist() for n in plens]

    def submit(i):
        return eng.submit(prompts[i], max_new=int(budgets[i]),
                          temperature=0.8 if i == SAMPLED else 0.0,
                          seed=seed + i)

    traced = trace.enabled
    trace.enable_trace()
    first_event = len(trace.events())
    reqs = [submit(i) for i in range(N_REQUESTS // 2)]
    while eng.serve_stats()["decode_steps"] < MID_DECODE_STEPS:
        eng.step()
    reqs += [submit(i) for i in range(N_REQUESTS // 2, N_REQUESTS)]
    eng.run()
    stats = eng.serve_stats()
    for i, r in enumerate(reqs):
        check(len(r.tokens) == budgets[i],
              f"request {i}: {len(r.tokens)} tokens, budget {budgets[i]}")
        check(all(0 <= t < cfg.vocab for t in r.tokens),
              f"request {i}: id outside the vocabulary")
    alone = submit(RERUN)
    eng.run()
    check(alone.tokens == reqs[RERUN].tokens,
          f"request {RERUN} rerun alone differs from its scheduled run")
    events = trace.events()[first_event:]
    if not traced:
        trace.disable_trace()

    for span, name in (("serve.prefill", "admit"), ("serve.decode", "decode")):
        walls = [e[3] for e in events if e[0] == span]
        rep.step("serve", name, walls)
    rep.emit(phase="serve", what="serve_stats", **stats)
    return stats


def _leaf_samples(params) -> list[np.ndarray]:
    return [np.asarray(leaf.ravel()[:1024]) for leaf in jax.tree.leaves(params)]


def train_phase(rep: Report, cfg, seed: int, batch: int, seq: int,
                steps: int) -> list[float]:
    step_fn, ts, _, _ = build_train_step(cfg, steps=steps, batch=batch)
    params = init_params(cfg, jax.random.PRNGKey(seed), dtype=jnp.float32)
    opt_state = init_opt_state(cfg, params, ts)
    before = _leaf_samples(params)
    walls, losses = [], []
    for step, b in batch_iterator(cfg, batch, seq, seed=seed):
        if step >= steps:
            break
        t0 = time.perf_counter()
        params, opt_state, metrics = step_fn(params, opt_state, b)
        jax.block_until_ready((params, opt_state, metrics))
        walls.append(time.perf_counter() - t0)
        losses.append(float(metrics["loss"]))
        check(math.isfinite(losses[-1]), f"step {step}: loss {losses[-1]}")
    unchanged = [i for i, (a, z) in enumerate(zip(before, _leaf_samples(params)))
                 if np.array_equal(a, z)]
    check(not unchanged, f"parameter leaves {unchanged} did not change")
    rep.step("train", "train_step", walls, losses=losses,
             tokens_per_step=batch * seq)
    return losses


def bridge_phase(rep: Report, seed: int, world: int, n: int) -> None:
    field = np.random.default_rng(seed).standard_normal((n, n), np.float32)
    checks = (
        ("shards_match_pythonmpi_locals",
         lambda: bridge.check_shards_match_pythonmpi_locals(field, world)),
        ("corner_turn", lambda: bridge.check_corner_turn(field, world)),
        # 16 blocks of rows per rank
        ("block_cyclic",
         lambda: bridge.check_block_cyclic(field, world, n // (16 * world))),
        ("halo_exchange", lambda: bridge.check_halo_exchange(field, world, 2)),
    )
    for name, run in checks:
        t0 = time.perf_counter()
        run()
        rep.emit(phase="bridge", check=name, field=[n, n], devices=world,
                 wall_s=time.perf_counter() - t0)


def dp_train_phase(rep: Report, cfg, seed: int, batch: int, seq: int,
                   steps: int, world: int) -> tuple[float, float]:
    """Step 0 of the train step on one device, then on a ``world``-way
    data mesh (``launch/train.py``'s layout): the losses must agree."""
    _, b = next(batch_iterator(cfg, batch, seq, seed=seed))

    step_fn, ts, _, _ = build_train_step(cfg, steps=steps, batch=batch)
    params = init_params(cfg, jax.random.PRNGKey(seed), dtype=jnp.float32)
    t0 = time.perf_counter()
    out = step_fn(params, init_opt_state(cfg, params, ts), b)
    jax.block_until_ready(out)
    rep.step("dp_train", "train_step", [time.perf_counter() - t0], devices=1)
    loss_1 = float(out[2]["loss"])
    del params, out
    rep.compile_s.pop("train_step", None)

    mesh = make_local_mesh(data=world, model=1)
    step_fn, ts, p_sh, o_sh = build_train_step(cfg, steps=steps, batch=batch,
                                               mesh=mesh)
    b = jax.device_put(b, batch_shardings(cfg, mesh, "train", batch))
    params = jax.device_put(
        init_params(cfg, jax.random.PRNGKey(seed), dtype=jnp.float32), p_sh)
    opt_state = jax.device_put(init_opt_state(cfg, params, ts), o_sh)
    t0 = time.perf_counter()
    with mesh_context(mesh):
        params, opt_state, metrics = step_fn(params, opt_state, b)
        jax.block_until_ready((params, opt_state, metrics))
    wall = time.perf_counter() - t0
    for name, x in (("tokens", b["tokens"]),
                    ("params", jax.tree.leaves(params)[0])):
        devs = {s.device for s in x.addressable_shards}
        check(len(devs) == world, f"{name} on {len(devs)} devices, want {world}")
    loss_n = float(metrics["loss"])
    rel = abs(loss_n - loss_1) / abs(loss_1)
    rep.step("dp_train", "train_step", [wall], devices=world, loss=loss_n,
             loss_one_device=loss_1, loss_rel_diff=rel, loss_rtol=LOSS_RTOL)
    check(math.isfinite(loss_n) and rel <= LOSS_RTOL,
          f"{world}-device loss {loss_n} vs one-device {loss_1}")
    return loss_1, loss_n


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found {dev.platform}")
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but JAX found "
                         f"{len(devices)} device(s)")
    rep = Report(dev.device_kind)
    rep.emit(phase="device", platform=dev.platform, count=len(devices),
             compile_cache=use_compile_cache())
    rep.listen()

    cfg = get_config(ARCH)
    train_cfg = dataclasses.replace(cfg, n_layers=TRAIN["n_layers"])
    if args.chips == 1:
        serve_phase(rep, cfg, args.seed, **SERVE)
        gc.collect()  # the engine's params and KV cache leave the chip
        rep.memory("serve")
        train_phase(rep, train_cfg, args.seed, TRAIN["batch"], TRAIN["seq"],
                    TRAIN["steps"])
        rep.memory("train")
    else:
        bridge_phase(rep, args.seed, args.chips, FIELD_N)
        dp_train_phase(rep, train_cfg, args.seed, TRAIN["batch"], TRAIN["seq"],
                       TRAIN["steps"], args.chips)
        rep.memory("dp_train")
    rep.collect()
    rep.emit(phase="compile_cache", **rep.cache_events)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
