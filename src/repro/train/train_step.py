"""train_step / serve-step factories with Dmap-derived shardings.

``make_train_step`` closes over (config, optimizer config) and returns a
function ``(params, opt_state, batch) -> (params, opt_state, metrics)``
suitable for ``jax.jit`` with the sharding trees from ``repro.dist``.

Scale features (DESIGN.md §8):
* gradient accumulation with bucketed mean (microbatch scan) so the
  backward of microbatch i overlaps the reduction of microbatch i-1 under
  XLA latency hiding;
* optional gradient compression for the cross-data-axis reduction: bf16,
  or int8 with error feedback (the residual is carried in opt_state).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from ..models import loss_fn
from ..models.config import ModelConfig
from .optimizer import AdamWConfig, adamw_init, adamw_update


@dataclass(frozen=True)
class TrainStepConfig:
    microbatches: int = 1
    remat: bool = True
    grad_compression: str = "none"  # none | bf16 | int8_ef
    # sequence-parallel residual stream: pays when the per-device
    # microbatch is big enough to amortize the gather transitions
    # (EXPERIMENTS.md §Perf it. 1.4/1.5); default off
    sp: bool = False


def _compress_decompress(g, residual=None, *, how: str):
    """Lossy-compress a gradient leaf; returns (g', new_residual)."""
    if how == "bf16":
        return g.astype(jnp.bfloat16).astype(jnp.float32), None
    if how == "int8_ef":
        gf = g.astype(jnp.float32)
        if residual is not None:
            gf = gf + residual
        scale = jnp.maximum(jnp.max(jnp.abs(gf)), 1e-12) / 127.0
        q = jnp.clip(jnp.round(gf / scale), -127, 127).astype(jnp.int8)
        deq = q.astype(jnp.float32) * scale
        return deq, gf - deq  # error feedback residual
    return g, residual


def make_train_step(
    cfg: ModelConfig,
    opt: AdamWConfig,
    ts: TrainStepConfig = TrainStepConfig(),
    grad_shardings=None,
):
    """Build the jittable train step.

    ``grad_shardings`` (a tree of NamedSharding matching the params) pins
    each gradient to the parameter's own Dmap layout, so GSPMD emits
    reduce-scatters into the FSDP shards instead of full all-reduces —
    measured 2.2× less link traffic on the gemma train cell.
    """

    def _pin(g_tree):
        if grad_shardings is None:
            return g_tree
        return jax.tree.map(
            jax.lax.with_sharding_constraint, g_tree, grad_shardings
        )

    def grads_of(params, batch):
        loss, g = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, batch, remat=ts.remat, sp=ts.sp)
        )(params)
        return loss, _pin(g)

    @jax.named_scope("train_step")
    def train_step(params, opt_state, batch):
        if ts.microbatches > 1:
            # unrolled gradient accumulation: each add updates the fp32
            # accumulator in place (a lax.scan carry would double-buffer
            # the full-parameter-sized accumulator — measured +3.7 GB/chip
            # on the 235B MoE cell), and the backward of microbatch i
            # overlaps the grad reduction of i-1 under XLA latency hiding
            from ..dist.hints import constrain

            def mb_slice(x, i):
                b = x.shape[0]
                # mrope positions carry a leading (3,) stream dim: slice
                # their batch axis (dim 1) instead
                axis = 1 if (x.ndim >= 2 and b == 3 and cfg.pos_embedding == "mrope") else 0
                per = x.shape[axis] // ts.microbatches
                out = jax.lax.dynamic_slice_in_dim(x, i * per, per, axis=axis)
                # keep the microbatch on the data axes: without this GSPMD
                # may replicate the slice
                return constrain(out, None, "dp") if axis else constrain(out, "dp")

            # bf16 compression moves the cast BEFORE the cross-data grad
            # reduction (XLA fuses the accumulate dtype into the combined
            # all-reduce, so fp32 accumulation doubles every wgrad AR —
            # measured 50G -> 25G/device on qwen2-vl-72b at probe scale)
            acc_t = (
                jnp.bfloat16 if ts.grad_compression == "bf16" else jnp.float32
            )
            loss = jnp.float32(0.0)
            grads = None
            p = params
            for i in range(ts.microbatches):
                mbatch = {k: mb_slice(v, i) for k, v in batch.items()}
                li, gi = grads_of(p, mbatch)
                loss = loss + li
                gi = jax.tree.map(lambda g: g.astype(acc_t), gi)
                grads = gi if grads is None else jax.tree.map(jnp.add, grads, gi)
                # thread params through a barrier so microbatch i+1 cannot
                # be scheduled before i's accumulation — otherwise the
                # scheduler interleaves all microbatches and keeps every
                # activation set alive at once (measured 44 GB/chip on the
                # 235B MoE cell vs ~13 GB sequential)
                p, grads, loss = jax.lax.optimization_barrier((p, grads, loss))
            inv = 1.0 / ts.microbatches
            loss = loss * inv
            grads = jax.tree.map(lambda g: g * inv, grads)
        else:
            loss, grads = grads_of(params, batch)

        if ts.grad_compression != "none":
            residuals = opt_state.get("ef_residual")
            if ts.grad_compression == "int8_ef" and residuals is None:
                residuals = jax.tree.map(
                    lambda g: jnp.zeros(g.shape, jnp.float32), grads
                )
            if residuals is not None:
                pairs = jax.tree.map(
                    partial(_compress_decompress, how=ts.grad_compression),
                    grads,
                    residuals,
                )
                grads = jax.tree.map(lambda t: t[0], pairs,
                                     is_leaf=lambda t: isinstance(t, tuple))
                residuals = jax.tree.map(lambda t: t[1], pairs,
                                         is_leaf=lambda t: isinstance(t, tuple))
            else:
                pairs = jax.tree.map(
                    lambda g: _compress_decompress(g, how=ts.grad_compression),
                    grads,
                )
                grads = jax.tree.map(lambda t: t[0], pairs,
                                     is_leaf=lambda t: isinstance(t, tuple))

        core_state = {k: v for k, v in opt_state.items() if k != "ef_residual"}
        params, core_state, aux = adamw_update(opt, params, grads, core_state)
        if ts.grad_compression == "int8_ef":
            core_state["ef_residual"] = residuals
        metrics = {"loss": loss, **aux}
        return params, core_state, metrics

    return train_step


def init_opt_state(cfg: ModelConfig, params, ts: TrainStepConfig = TrainStepConfig()):
    state = adamw_init(params)
    if ts.grad_compression == "int8_ef":
        state["ef_residual"] = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )
    return state
