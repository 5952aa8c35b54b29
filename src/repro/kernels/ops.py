"""Jitted user-facing wrappers around the Pallas kernels.

Handle layout/padding/GQA so callers use natural shapes.  Each wrapper
compiles for the TPU unless the caller passes ``interpret=True``, which
runs the kernel in the Pallas interpreter (how the CPU tests run it).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention as _flash
from .rmsnorm import rmsnorm as _rmsnorm
from .ssd_scan import ssd_scan as _ssd
from .stream_triad import LANES, stream_triad as _triad

__all__ = ["attention", "rmsnorm_op", "triad", "ssd"]


def attention(
    q: jax.Array,  # (B, S, H, D)
    k: jax.Array,  # (B, S, KH, D)
    v: jax.Array,
    *,
    causal: bool = True,
    blk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """GQA flash attention with natural (B, S, H, D) layout.

    KV heads are broadcast to H (free at HLO level), sequence padded to
    the block size with masked-out suffix keys."""
    b, s, h, d = q.shape
    kh = k.shape[2]
    if kh != h:
        k = jnp.repeat(k, h // kh, axis=2)
        v = jnp.repeat(v, h // kh, axis=2)
    pad = (-s) % blk
    if pad:
        qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    else:
        qp, kp, vp = q, k, v
    sp = s + pad
    # (B, S, H, D) -> (B*H, S, D)
    def fold(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, sp, d)

    out = _flash(
        fold(qp), fold(kp), fold(vp),
        causal=causal, blk_q=blk, blk_k=blk, interpret=interpret,
    )
    out = out.reshape(b, h, sp, d).transpose(0, 2, 1, 3)
    return out[:, :s]


def rmsnorm_op(x: jax.Array, w: jax.Array, eps: float = 1e-5,
               interpret: bool = False) -> jax.Array:
    """RMSNorm over the last dim of any (..., D) tensor."""
    shape = x.shape
    m = 1
    for sdim in shape[:-1]:
        m *= sdim
    flat = x.reshape(m, shape[-1])
    blk = 8
    pad = (-m) % blk
    if pad:
        flat = jnp.pad(flat, ((0, pad), (0, 0)))
    out = _rmsnorm(flat, w, eps=eps, blk_rows=blk, interpret=interpret)
    return out[:m].reshape(shape)


def triad(b: jax.Array, c: jax.Array, s: float = 3.0,
          interpret: bool = False) -> jax.Array:
    """STREAM triad over flat vectors of any length (padded internally)."""
    n = b.shape[0]
    blk_rows = 256
    tile = blk_rows * LANES
    pad = (-n) % tile
    bp = jnp.pad(b, (0, pad)).reshape(-1, LANES)
    cp = jnp.pad(c, (0, pad)).reshape(-1, LANES)
    out = _triad(bp, cp, s=s, blk_rows=blk_rows, interpret=interpret)
    return out.reshape(-1)[:n]


def ssd(x, dt, a_log, bm, cm, chunk: int = 64,
        interpret: bool = False):
    """Mamba2 SSD with natural layouts (drop-in for models.mamba2.ssd_chunked).

    x: (B, S, H, P); dt: (B, S, H); a_log: (H,); bm/cm: (B, S, N)."""
    b, s, h, p = x.shape
    # pre-scale outside the kernel (elementwise, bandwidth-light)
    xd = (x * dt[..., None]).transpose(0, 2, 1, 3)           # (B,H,S,P)
    A = -jnp.exp(a_log.astype(jnp.float32))
    dA = dt.astype(jnp.float32) * A                           # (B,S,H)
    c = s // chunk
    cs = jnp.cumsum(
        dA.transpose(0, 2, 1).reshape(b, h, c, chunk), axis=-1
    )
    out = _ssd(xd, cs, bm, cm, chunk=chunk, interpret=interpret)
    return out.transpose(0, 2, 1, 3)                          # (B,S,H,P)
