"""Mamba2 SSD chunk scan as a Pallas TPU kernel.

The sequence is tiled into chunks; within a chunk the output is a masked
quadratic form (three MXU matmuls), and the (N×P) SSM state carries
across chunks in VMEM scratch — the chunk axis is the innermost
*sequential* grid dimension, exactly the flash-attention pattern applied
to a linear recurrence (DESIGN.md §7).

Per grid step (b, h, c):
    L        = exp(cs_i - cs_j) ⊙ tril          (Q×Q decay kernel)
    y_intra  = ((C Bᵀ) ⊙ L) · X                 (MXU)
    y_inter  = (C ⊙ exp(cs)) · state            (MXU)
    state'   = state · exp(cs_Q) + (B ⊙ exp(cs_Q - cs))ᵀ · X

Inputs are pre-scaled outside the kernel (X = x·dt, cs = cumsum(dt·A)
within each chunk) — those are O(S) elementwise passes; the kernel owns
the O(S·Q·(N+P)) matmul work.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(
    xd_ref,    # (1, 1, Q, P)  dt-scaled inputs for this (b, h, chunk)
    cs_ref,    # (1, 1, C, Q)  within-chunk cumulative log-decay, every chunk
    bm_ref,    # (1, Q, N)
    cm_ref,    # (1, Q, N)
    o_ref,     # (1, 1, Q, P)
    state_ref,  # VMEM scratch (N, P) fp32 — persists across the chunk axis
    *,
    q: int,
):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    xd = xd_ref[0, 0].astype(jnp.float32)          # (Q, P)
    bm = bm_ref[0].astype(jnp.float32)             # (Q, N)
    cm = cm_ref[0].astype(jnp.float32)             # (Q, N)
    # cs stays 2-D: this chunk's row (1, Q) and, from its diagonal, the
    # column (Q, 1) — Mosaic lowers neither a 1-D relayout nor a value index
    cs_row = cs_ref[0, 0, pl.ds(ci, 1), :].astype(jnp.float32)  # (1, Q)
    rows = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    cs_col = jnp.sum(
        jnp.where(rows == cols, cs_row, 0.0), axis=1, keepdims=True
    )                                              # (Q, 1)
    cs_end = jnp.sum(
        jnp.where(cols[:1] == q - 1, cs_row, 0.0), axis=1, keepdims=True
    )                                              # (1, 1) = cs[Q-1]

    # intra-chunk quadratic
    seg = cs_col - cs_row                          # (Q, Q) i - j
    L = jnp.where(rows >= cols, jnp.exp(seg), 0.0)
    S = jax.lax.dot_general(
        cm, bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (Q, Q)
    y = jax.lax.dot_general(
        S * L, xd, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (Q, P)

    # inter-chunk: contribution of the state entering this chunk
    c_in = cm * jnp.exp(cs_col)                    # (Q, N)
    y = y + jax.lax.dot_general(
        c_in, state_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    # state update: decay to chunk end, absorb this chunk's inputs
    b_w = bm * jnp.exp(cs_end - cs_col)            # (Q, N)
    new_state = state_ref[...] * jnp.exp(cs_end) + jax.lax.dot_general(
        b_w, xd, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (N, P)
    state_ref[...] = new_state
    o_ref[0, 0] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(
    xd: jax.Array,   # (B, H, S, P)  x pre-scaled by dt
    cs: jax.Array,   # (B, H, C, Q)  within-chunk cumulative log-decay
    bm: jax.Array,   # (B, S, N)
    cm: jax.Array,   # (B, S, N)
    *,
    chunk: int,
    interpret: bool = False,
) -> jax.Array:
    b, h, s, p = xd.shape
    n = bm.shape[-1]
    q = chunk
    if s % q:
        raise ValueError(f"seq {s} not divisible by chunk {q}")
    c = s // q
    return pl.pallas_call(
        functools.partial(_ssd_kernel, q=q),
        grid=(b, h, c),  # chunk axis innermost => sequential state carry
        in_specs=[
            pl.BlockSpec((1, 1, q, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            # the whole (C, Q) row block of one (b, h): a (1, Q) block would
            # break the TPU tiling rule (last two dims divisible by (8, 128)
            # or equal to the array's); it is fetched once per (b, h)
            pl.BlockSpec((1, 1, c, q), lambda bi, hi, ci: (bi, hi, 0, 0)),
            pl.BlockSpec((1, q, n), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, q, n), lambda bi, hi, ci: (bi, ci, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, q, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, p), xd.dtype),
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        interpret=interpret,
    )(xd, cs, bm, cm)
