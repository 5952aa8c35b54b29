"""Shared neural layers (pure JAX, jnp reference implementations).

The Pallas kernels in ``repro.kernels`` are TPU-targeted drop-ins for the
hot paths here (attention, rmsnorm); these jnp forms are the oracles the
kernels are validated against and the bodies XLA sees during the dry-run.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .config import ModelConfig


def rms_norm(x, weight, eps: float):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps) * (1.0 + weight.astype(jnp.float32))
    return out.astype(dtype)


# ---------------------------------------------------------------------------
# Rotary embeddings (RoPE + Qwen2-VL M-RoPE)
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (B, S, H, Dh); positions: (B, S) int32."""
    half = x.shape[-1] // 2
    freqs = jnp.asarray(rope_freqs(x.shape[-1], theta), dtype=jnp.float32)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, half)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


def apply_mrope(x, positions3, theta: float, sections):
    """Qwen2-VL multimodal RoPE: three position streams (t, h, w) rotate
    disjoint sections of the head dim.  positions3: (3, B, S).

    The vision frontend that derives (t,h,w) ids from image grids is a stub
    (DESIGN.md §5); text-only inputs pass three identical streams, which
    reduces exactly to standard RoPE.
    """
    half = x.shape[-1] // 2
    freqs = jnp.asarray(rope_freqs(x.shape[-1], theta), dtype=jnp.float32)
    # (3, B, S, half) angles; each half-dim slot takes its section's stream
    ang = positions3[..., None].astype(jnp.float32) * freqs  # (3,B,S,half)
    sec = np.zeros(half, dtype=np.int32)
    s0, s1, s2 = sections
    sec[s0 : s0 + s1] = 1
    sec[s0 + s1 : s0 + s1 + s2] = 2
    sel = jnp.asarray(sec)
    ang = jnp.take_along_axis(
        ang, sel[None, None, None, :].astype(jnp.int32), axis=0
    )[0]  # (B,S,half) - pick stream per slot
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def positions_for(cfg: ModelConfig, batch: int, seq: int, offset=0):
    pos = jnp.arange(seq, dtype=jnp.int32)[None, :] + offset
    pos = jnp.broadcast_to(pos, (batch, seq))
    if cfg.pos_embedding == "mrope":
        return jnp.broadcast_to(pos[None], (3, batch, seq))
    return pos


def _rotate(cfg: ModelConfig, x, positions):
    if cfg.pos_embedding == "rope":
        return apply_rope(x, positions, cfg.rope_theta)
    if cfg.pos_embedding == "mrope":
        return apply_mrope(x, positions, cfg.rope_theta, cfg.mrope_sections)
    return x


# ---------------------------------------------------------------------------
# Attention (GQA / MQA) — jnp reference; flash kernel is the TPU drop-in
# ---------------------------------------------------------------------------


CHUNKED_ATTN_THRESHOLD = 8192  # seqs beyond this use the block-sparse path


def _qkv(cfg: ModelConfig, p, x, positions):
    """Projected + rotated q/k/v with KV repeated to full heads.

    The repeat-to-H formulation keeps one shardable head axis (H divides
    the model mesh axis for every assigned arch), so GSPMD propagates
    tensor parallelism through the attention einsums without resharding —
    the KV broadcast is free at the HLO level.
    """
    from ..dist.hints import constrain

    b, s, _ = x.shape
    h, kh, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].reshape(cfg.d_model, h, dh))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].reshape(cfg.d_model, kh, dh))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].reshape(cfg.d_model, kh, dh))
    if cfg.qkv_bias:
        q = q + p["bq"].reshape(h, dh)
        k = k + p["bk"].reshape(kh, dh)
        v = v + p["bv"].reshape(kh, dh)
    q = _rotate(cfg, q, positions)
    k = _rotate(cfg, k, positions)
    if kh != h:
        k = jnp.repeat(k, h // kh, axis=2)
        v = jnp.repeat(v, h // kh, axis=2)
    q = constrain(q, "dp", None, "model", None)
    k = constrain(k, "dp", None, "model", None)
    v = constrain(v, "dp", None, "model", None)
    return q, k, v


@jax.named_scope("attention")
def attention(cfg: ModelConfig, p, x, positions, mask=None):
    """Causal attention; switches to the chunked online-softmax path for
    long sequences (the jnp mirror of the Pallas flash kernel)."""
    b, s, _ = x.shape
    if s > CHUNKED_ATTN_THRESHOLD and mask is None:
        return attention_chunked(cfg, p, x, positions)
    h, dh = cfg.n_heads, cfg.head_dim
    q, k, v = _qkv(cfg, p, x, positions)
    logits = jnp.einsum("bshd,bthd->bhst", q, k) / np.sqrt(dh)
    if cfg.attn_logit_softcap:
        c = cfg.attn_logit_softcap
        logits = c * jnp.tanh(logits / c)
    causal = jnp.tril(jnp.ones((s, s), dtype=bool))
    if mask is not None:
        causal = causal & mask
    logits = jnp.where(causal, logits, jnp.finfo(logits.dtype).min)
    w = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(x.dtype)
    out = jnp.einsum("bhst,bthd->bshd", w, v).reshape(b, s, h * dh)
    return out @ p["wo"]


@jax.named_scope("attention")
def attention_prefill(cfg: ModelConfig, p, x, positions):
    """Causal attention that also returns the rotated *pre-repeat* K/V —
    exactly the rows ``attention_decode`` would have appended to its
    (B, S, KH, Dh) cache one token at a time.  This is the bulk-prefill
    unit: one forward seeds the whole KV cache for a request group."""
    from ..dist.hints import constrain

    b, s, _ = x.shape
    h, kh, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].reshape(cfg.d_model, h, dh))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].reshape(cfg.d_model, kh, dh))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].reshape(cfg.d_model, kh, dh))
    if cfg.qkv_bias:
        q = q + p["bq"].reshape(h, dh)
        k = k + p["bk"].reshape(kh, dh)
        v = v + p["bv"].reshape(kh, dh)
    q = _rotate(cfg, q, positions)
    k = _rotate(cfg, k, positions)
    kv_k, kv_v = k, v  # cache rows: rotated, pre-repeat (KH heads)
    if kh != h:
        k = jnp.repeat(k, h // kh, axis=2)
        v = jnp.repeat(v, h // kh, axis=2)
    q = constrain(q, "dp", None, "model", None)
    k = constrain(k, "dp", None, "model", None)
    v = constrain(v, "dp", None, "model", None)
    logits = jnp.einsum("bshd,bthd->bhst", q, k) / np.sqrt(dh)
    if cfg.attn_logit_softcap:
        c = cfg.attn_logit_softcap
        logits = c * jnp.tanh(logits / c)
    causal = jnp.tril(jnp.ones((s, s), dtype=bool))
    logits = jnp.where(causal, logits, jnp.finfo(logits.dtype).min)
    w = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(x.dtype)
    out = jnp.einsum("bhst,bthd->bshd", w, v).reshape(b, s, h * dh)
    return out @ p["wo"], kv_k, kv_v


def attention_chunked(cfg: ModelConfig, p, x, positions, blk: int = 2048):
    """Block-sparse causal attention with online softmax (flash-style).

    A static python loop emits only the lower-triangular (q-block,
    kv-block) pairs, so HLO FLOPs are the true causal count (no masked
    half) and peak memory is O(S·blk) instead of O(S²) — this is what the
    Pallas kernel does on TPU with its grid + VMEM tiles; here it is the
    XLA-visible mirror used by the 32k prefill cells.
    """
    b, s, _ = x.shape
    h, dh = cfg.n_heads, cfg.head_dim
    blk = min(blk, s)
    assert s % blk == 0, f"seq {s} not divisible by attention block {blk}"
    nb = s // blk
    q, k, v = _qkv(cfg, p, x, positions)
    scale = 1.0 / np.sqrt(dh)
    tri = jnp.tril(jnp.ones((blk, blk), dtype=bool))

    outs = []
    for qi in range(nb):
        if qi:  # chain q-blocks so the scheduler cannot co-materialize all
            # O(nb²/2) logit blocks at once (liveness, not a data dep)
            q, k, v, _ = jax.lax.optimization_barrier((q, k, v, outs[-1]))
        qb = q[:, qi * blk : (qi + 1) * blk] * scale  # (B,blk,H,Dh)
        m = jnp.full((b, h, blk), -jnp.inf, dtype=jnp.float32)
        l = jnp.zeros((b, h, blk), dtype=jnp.float32)
        acc = jnp.zeros((b, h, blk, dh), dtype=jnp.float32)
        for kj in range(qi + 1):
            kb = k[:, kj * blk : (kj + 1) * blk]
            vb = v[:, kj * blk : (kj + 1) * blk]
            logit = jnp.einsum("bshd,bthd->bhst", qb, kb).astype(jnp.float32)
            if cfg.attn_logit_softcap:
                c = cfg.attn_logit_softcap
                logit = c * jnp.tanh(logit / c)
            if kj == qi:  # diagonal block: triangular mask
                logit = jnp.where(tri[None, None], logit, -jnp.inf)
            m_new = jnp.maximum(m, logit.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            pexp = jnp.exp(logit - m_new[..., None])
            l = l * alpha + pexp.sum(axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bhst,bthd->bhsd", pexp, vb.astype(jnp.float32)
            )
            m = m_new
        outs.append((acc / l[..., None]).swapaxes(1, 2))  # (B,blk,H,Dh)
    out = jnp.concatenate(outs, axis=1).astype(x.dtype).reshape(b, s, h * dh)
    return out @ p["wo"]


@jax.named_scope("kv_write")
def write_kv_rows(cache, rows, pos):
    """This step's K/V rows written into a stacked (L, B, S_max, KH, Dh)
    cache at ``(l, b, pos[b])`` for every l; rows: (L, B, KH, Dh) in the
    cache dtype, pos: () one position for every row, or (B,) one per row.

    A scalar ``pos`` is one ``dynamic_update_slice`` over the whole
    batch; per-row positions take one per slot over all layers (B is
    static).  On a donated cache each updates in place, where a per-slot
    select would rewrite the whole cache.  A slot the serve engine has
    finished at ``max_seq`` keeps ``pos[b] == S_max``: the update's start
    is clamped, so it writes its own row at ``S_max - 1``.  Nothing reads
    a finished slot's cache, and a slot admitted again writes every
    position before it reads it, so that write is never seen."""
    pos = jnp.asarray(pos, dtype=jnp.int32)
    if pos.ndim == 0:
        return jax.lax.dynamic_update_slice(
            cache, rows[:, :, None], (0, 0, pos, 0, 0)
        )
    for b in range(cache.shape[1]):
        cache = jax.lax.dynamic_update_slice(
            cache, rows[:, b, None, None], (0, b, pos[b], 0, 0)
        )
    return cache


@jax.named_scope("attention")
def attention_decode(cfg: ModelConfig, p, x, cache_k, cache_v, pos):
    """One-token decode against a KV cache that it only reads.

    x: (B, 1, D); cache_k/v: (B, S_max, KH, Dh); pos: () current index, or
    (B,) per-row positions (continuous batching: every serve slot decodes
    at its own depth).  Each row's query attends over its cache at
    ``t < pos[b]`` plus this step's own K/V as one more column.  With
    the K/V rounded to the cache dtype this equals writing the row at
    ``pos[b]`` and attending over ``t <= pos[b]``, up to summation order.
    Returns (out, k, v): k/v are this step's (B, KH, Dh) rows in the cache
    dtype, which the caller writes once after its layer scan
    (``write_kv_rows``)."""
    b = x.shape[0]
    h, kh, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    f32 = jnp.float32
    pos = jnp.broadcast_to(jnp.asarray(pos, dtype=jnp.int32), (b,))
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].reshape(cfg.d_model, h, dh))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].reshape(cfg.d_model, kh, dh))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].reshape(cfg.d_model, kh, dh))
    if cfg.qkv_bias:
        q = q + p["bq"].reshape(h, dh)
        k = k + p["bk"].reshape(kh, dh)
        v = v + p["bv"].reshape(kh, dh)
    posb = pos[:, None]
    if cfg.pos_embedding == "mrope":
        posb = jnp.broadcast_to(posb[None], (3, b, 1))
    q = _rotate(cfg, q, posb)
    k = _rotate(cfg, k, posb)[:, 0].astype(cache_k.dtype)
    v = v[:, 0].astype(cache_v.dtype)

    # grouped-query einsum: the query's heads grouped per KV head contract
    # against the cache in its own head layout, with no repeated KV heads
    qg = q.reshape(b, 1, kh, h // kh, dh)
    logits = jnp.einsum("bskgd,btkd->bkgst", qg, cache_k) / np.sqrt(dh)
    own = jnp.einsum("bskgd,bkd->bkgs", qg, k)[..., None] / np.sqrt(dh)
    if cfg.attn_logit_softcap:
        c = cfg.attn_logit_softcap
        logits = c * jnp.tanh(logits / c)
        own = c * jnp.tanh(own / c)
    past = jnp.arange(cache_k.shape[1])[None, :] < pos[:, None]
    logits = jnp.where(
        past[:, None, None, None, :], logits, jnp.finfo(logits.dtype).min
    ).astype(f32)
    own = own.astype(f32)
    # the softmax over the cache's columns and the own column, as
    # jax.nn.softmax computes it over their concatenation
    m = jnp.maximum(logits.max(axis=-1, keepdims=True), own)
    e, e_own = jnp.exp(logits - m), jnp.exp(own - m)
    den = e.sum(axis=-1, keepdims=True) + e_own
    w, w_own = (e / den).astype(x.dtype), (e_own / den).astype(x.dtype)
    out = jnp.einsum(
        "bkgst,btkd->bskgd", w, cache_v, preferred_element_type=f32
    ) + jnp.einsum("bkgst,bkd->bskgd", w_own, v, preferred_element_type=f32)
    out = out.astype(jnp.result_type(x.dtype, cache_v.dtype))
    return out.reshape(b, 1, h * dh) @ p["wo"], k, v


# ---------------------------------------------------------------------------
# FFN variants
# ---------------------------------------------------------------------------


def _act(cfg_act: str, x):
    if cfg_act.startswith("silu"):
        return jax.nn.silu(x)
    if cfg_act.startswith("gelu"):
        return jax.nn.gelu(x, approximate=True)
    if cfg_act == "relu2":  # nemotron squared-ReLU
        r = jax.nn.relu(x)
        return r * r
    raise ValueError(f"unknown activation {cfg_act}")


@jax.named_scope("mlp")
def ffn(cfg: ModelConfig, p, x):
    """Gated (GLU) or plain FFN, by activation name."""
    if cfg.activation.endswith("_glu"):
        gate = _act(cfg.activation, x @ p["w_gate"])
        return (gate * (x @ p["w_up"])) @ p["w_down"]
    return _act(cfg.activation, x @ p["w_up"]) @ p["w_down"]


def ffn_param_shapes(cfg: ModelConfig, d_ff: int) -> dict:
    d = cfg.d_model
    if cfg.activation.endswith("_glu"):
        return {
            "w_gate": (d, d_ff),
            "w_up": (d, d_ff),
            "w_down": (d_ff, d),
        }
    return {"w_up": (d, d_ff), "w_down": (d_ff, d)}


def attn_param_shapes(cfg: ModelConfig) -> dict:
    d = cfg.d_model
    shapes = {
        "wq": (d, cfg.q_dim),
        "wk": (d, cfg.kv_dim),
        "wv": (d, cfg.kv_dim),
        "wo": (cfg.q_dim, d),
    }
    if cfg.qkv_bias:
        shapes.update(bq=(cfg.q_dim,), bk=(cfg.kv_dim,), bv=(cfg.kv_dim,))
    return shapes
