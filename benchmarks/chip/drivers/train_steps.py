"""Training steps through ``launch/train.py``'s jitted, donated step.

Set-up builds one object, the compiled step with its parameters and
AdamW state from the seed, and drives it through the mix's first
``check_steps`` steps with the window's own call and feed (a fresh batch
of distinct rows for every step).  From those steps it keeps what the
check compares: each step's loss, the per-leaf norms of the first
gradient as the optimizer took it (its first moment after one step over
``1 - b1``), and the per-leaf norms of the parameters' change after the
last of them.  The same object then runs the window: steps until
``seconds`` have passed, the next batch made on the host while the
device runs the step before.

Correctness: after the window, with the program's state freed, the
float32 reference runs the same steps from the same weights, and each
number is held to the configuration's limit.  Leaves whose reference
gradient is under a thousandth of the median leaf's are left out of the
two norm comparisons: Adam moves them by round-off alone.
"""

from __future__ import annotations

import gc
import statistics
import time

import numpy as np

from .. import generate, weights
from ..harness import Run, log
from ..stats import rate
from .serve_open import model_config, reference


def flat(tree: dict, prefix: str = "") -> dict:
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(flat(v, f"{prefix}{k}/"))
        else:
            out[prefix + k] = v
    return out


def _norms(tree) -> dict:
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda t: {k: jnp.linalg.norm(v.ravel()) for k, v in flat(t).items()})
    return {k: float(v) for k, v in f(tree).items()}


def _diff_norms(a, b) -> dict:
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x, y: {k: jnp.linalg.norm((x[k] - y[k]).ravel())
                              for k in x})
    return {k: float(v) for k, v in f(flat(a), flat(b)).items()}


def worst_leaf_gap(prog: dict, ref: dict, keep) -> tuple[float, str]:
    """The largest |norm_prog - norm_ref| over the reference norm of that
    leaf or of the median leaf, whichever is larger."""
    med = statistics.median(ref[k] for k in keep)
    gaps = {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in keep}
    k = max(gaps, key=gaps.get)
    return gaps[k], k


class Trainer:
    def __init__(self, config: dict, mix: dict, seed: int, half_batch: bool = False):
        import jax
        import jax.numpy as jnp
        from repro.launch.train import build_train_step
        from repro.train.train_step import init_opt_state

        self.config, self.mix, self.seed = config, mix, seed
        self.half_batch = half_batch  # a planted fault, for calibration only
        opt = mix["optimizer"]
        cfg = model_config(config)
        self.step_fn, ts, _, _ = build_train_step(
            cfg, steps=opt["total_steps"], batch=mix["batch"], lr=opt["lr"])
        self.params = weights.make_params(config, seed, jnp.float32)
        self.opt_state = init_opt_state(cfg, self.params, ts)
        self.k = 0
        self.losses = []
        for _ in range(mix["check_steps"]):
            loss = self.step()
            jax.block_until_ready(self.params)
            self.losses.append(float(loss))
            if self.k == 1:
                self.g1 = {k: v / (1.0 - opt["b1"])
                           for k, v in _norms(self.opt_state["m"]).items()}
        p0 = weights.make_params(config, seed, jnp.float32)
        self.dp = _diff_norms(self.params, p0)
        del p0

    def batch(self, k: int):
        import jax

        b = generate.train_batch(self.mix, self.seed, k, self.config["vocab"])
        if self.half_batch:
            n = len(b["tokens"]) // 2
            b = {key: np.concatenate([v[:n], v[:n]]) for key, v in b.items()}
        return jax.device_put(b)

    def step(self):
        b = self.batch(self.k)
        self.params, self.opt_state, met = self.step_fn(self.params, self.opt_state, b)
        self.k += 1
        return met["loss"]

    def free(self):
        self.params = self.opt_state = self.step_fn = None
        gc.collect()


def compare(trainer: Trainer, control: bool = False) -> dict:
    """The three compared numbers of a trainer's first steps against the
    reference (with ``control``, the reference with every matrix product
    in bfloat16 stands in for the program)."""
    config, mix, seed = trainer.config, trainer.mix, trainer.seed
    ref = reference(config)
    batches = [generate.train_batch(mix, seed, k, config["vocab"])
               for k in range(mix["check_steps"])]
    r_loss, r_g1, r_dp = ref.train_steps(config, seed, batches, mix["optimizer"])
    if control:
        p_loss, p_g1, p_dp = ref.train_steps(config, seed, batches, mix["optimizer"],
                                             control=True)
    else:
        p_loss, p_g1, p_dp = trainer.losses, trainer.g1, trainer.dp
    med = statistics.median(r_g1.values())
    keep = [k for k, v in r_g1.items() if v >= 1e-3 * med]
    g_gap, g_leaf = worst_leaf_gap(p_g1, r_g1, keep)
    d_gap, d_leaf = worst_leaf_gap(p_dp, r_dp, keep)
    return {"loss_rel": max(abs(a - b) / abs(b) for a, b in zip(p_loss, r_loss)),
            "grad_norm_gap": g_gap, "update_norm_gap": d_gap,
            "grad_leaf": g_leaf, "update_leaf": d_leaf,
            "left_out": sorted(set(r_g1) - set(keep)),
            "losses": p_loss, "ref_losses": r_loss}


def run(ctx) -> Run:
    import jax

    config, mix = ctx.config, ctx.mix
    tr = Trainer(config, mix, ctx.seed)
    setup_s = time.perf_counter() - ctx.t_start
    tokens = mix["batch"] * mix["seq"]
    walls, clock = [], time.perf_counter
    ctx.start_trace()
    t0 = clock()
    steps, prev = 0, None
    while clock() - t0 < ctx.seconds:
        s0 = clock()
        with ctx.span("bench.train_step"):
            loss = tr.step()
            if prev is not None:
                jax.block_until_ready(prev)
        prev = loss
        walls.append((s0, clock() - s0))
        steps += 1
    final = float(jax.block_until_ready(prev))
    window_s = clock() - t0
    ctx.stop_trace()
    mem = ctx.memory_peak()
    tr.free()
    trace = ctx.reduce_trace()
    c = compare(tr)
    lim = config["check"]
    log(cell=ctx.cell["name"], setup_s=setup_s, steps=steps, window_s=window_s,
        last_loss=final, **c)
    checks = {k: (c[k], lim[k]) for k in ("loss_rel", "grad_norm_gap", "update_norm_gap")}
    correct = all(v <= limit for v, limit in checks.values()) and np.isfinite(final)
    data = {"spans": [("bench.train_step", a, d) for a, d in walls], "peaks": ctx.peaks,
            "config": config, "tokens_per_s": rate(steps * tokens, window_s),
            "seq": mix["seq"]}
    return Run(correct=bool(correct), attempted=steps, failed=0,
               e2e={"train_tokens_per_s": rate(steps * tokens, window_s), "setup_s": setup_s},
               checks=checks, memory_peak_bytes=mem, data=data, trace=trace)
