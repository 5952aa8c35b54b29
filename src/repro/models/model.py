"""Model assembly: param shapes/init, forward, loss, decode — all families.

Layers are *stacked* (leading L axis) and iterated with ``lax.scan`` so a
94-layer MoE compiles in seconds during the 40-cell dry-run; ``remat=True``
wraps the layer body in ``jax.checkpoint`` for training memory.  The
hybrid (Zamba2) family scans groups of Mamba2 blocks with one weight-shared
attention block applied between groups.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .config import ModelConfig
from .flags import scan_unroll
from .layers import (
    attention,
    attention_decode,
    attention_prefill,
    attn_param_shapes,
    ffn,
    ffn_param_shapes,
    positions_for,
    rms_norm,
    write_kv_rows,
)
from .mamba2 import (
    mamba2_block,
    mamba2_decode_state,  # noqa: F401  (re-exported: serve imports it here)
    mamba2_decode_step,
    mamba2_param_shapes,
    mamba2_prefill,
    CONV_K,
)
from .moe import moe_ffn, moe_param_shapes
from .rwkv6 import (
    rwkv6_channel_mix,
    rwkv6_channel_mix_step,
    rwkv6_param_shapes,
    rwkv6_time_mix,
    rwkv6_time_mix_step,
)

# ---------------------------------------------------------------------------
# Parameter shapes & init
# ---------------------------------------------------------------------------


def _layer_shapes(cfg: ModelConfig) -> dict:
    d = cfg.d_model
    if cfg.family == "dense":
        return {
            "ln1": (d,),
            "attn": attn_param_shapes(cfg),
            "ln2": (d,),
            "ffn": ffn_param_shapes(cfg, cfg.d_ff),
        }
    if cfg.family == "moe":
        return {
            "ln1": (d,),
            "attn": attn_param_shapes(cfg),
            "ln2": (d,),
            "moe": moe_param_shapes(cfg),
        }
    if cfg.family == "ssm":
        base = rwkv6_param_shapes(cfg)
        return {"ln1": (d,), "ln2": (d,), **base}
    if cfg.family == "hybrid":
        return {"ln": (d,), "mix": mamba2_param_shapes(cfg)}
    raise ValueError(f"unknown family {cfg.family}")


def _stack(shapes: dict, *lead: int) -> dict:
    return jax.tree.map(
        lambda s: (*lead, *s), shapes, is_leaf=lambda s: isinstance(s, tuple)
    )


def param_shapes(cfg: ModelConfig) -> dict:
    d = {"embed": (cfg.vocab_padded, cfg.d_model)}
    layer = _layer_shapes(cfg)
    if cfg.family == "hybrid":
        every = cfg.hybrid_attn_every
        if cfg.n_layers % every:
            raise ValueError(
                f"{cfg.name}: n_layers {cfg.n_layers} not divisible by "
                f"hybrid_attn_every {every}"
            )
        groups = cfg.n_layers // every
        d["layers"] = _stack(layer, groups, every)
        d["shared"] = {  # one weight-shared attention block (Zamba2)
            "ln1": (cfg.d_model,),
            "attn": attn_param_shapes(cfg),
            "ln2": (cfg.d_model,),
            "ffn": ffn_param_shapes(cfg, cfg.d_ff),
        }
    else:
        d["layers"] = _stack(layer, cfg.n_layers)
    d["final_norm"] = (cfg.d_model,)
    if not cfg.tie_embeddings:
        d["lm_head"] = (cfg.d_model, cfg.vocab_padded)
    return d


def _init_leaf(key, path: str, shape: tuple, dtype):
    """Name-based init rules (fan-in normal for matrices, special SSM/RWKV)."""
    name = path.split("/")[-1]
    if name in ("A_log",):  # shapes may carry stacked (L,...) leading dims
        base = jnp.log(jnp.linspace(1.0, 16.0, shape[-1], dtype=jnp.float32))
        return jnp.broadcast_to(base, shape)
    if name in ("dt_bias",):
        dt = np.exp(np.linspace(np.log(1e-3), np.log(1e-1), shape[-1]))
        return jnp.broadcast_to(
            jnp.asarray(np.log(np.expm1(dt)), dtype=jnp.float32), shape
        )
    if name in ("D_skip", "u"):
        return jnp.ones(shape, dtype=jnp.float32)
    if name.startswith("mu_"):
        return jnp.full(shape, 0.5, dtype=jnp.float32)
    if name == "w0":
        return jnp.full(shape, -5.0, dtype=jnp.float32)
    if name.startswith(("ln", "gate_norm", "final_norm")):
        return jnp.zeros(shape, dtype=jnp.float32)  # rms weight is 1 + w
    if name.startswith("b") or len(shape) == 1:
        return jnp.zeros(shape, dtype=dtype)
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    scale = 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, dtype=jnp.float32) * scale).astype(dtype)


def init_params(cfg: ModelConfig, key, dtype=jnp.bfloat16) -> dict:
    shapes = param_shapes(cfg)
    flat = []

    def walk(tree, prefix=""):
        for k in sorted(tree):
            v = tree[k]
            p = f"{prefix}/{k}"
            if isinstance(v, dict):
                walk(v, p)
            else:
                flat.append((p, v))

    walk(shapes)
    keys = jax.random.split(key, len(flat))
    leaves = {p: _init_leaf(kk, p, s, dtype) for kk, (p, s) in zip(keys, flat)}

    def build(tree, prefix=""):
        out = {}
        for k in sorted(tree):
            v = tree[k]
            p = f"{prefix}/{k}"
            out[k] = build(v, p) if isinstance(v, dict) else leaves[p]
        return out

    return build(shapes)


def abstract_params(cfg: ModelConfig, dtype=jnp.bfloat16) -> dict:
    """ShapeDtypeStruct tree for the dry-run (no allocation)."""
    def leaf(path, shape):
        name = path.split("/")[-1]
        f32 = name in (
            "A_log", "dt_bias", "D_skip", "u", "w0",
        ) or name.startswith(("mu_", "ln", "gate_norm", "final_norm"))
        return jax.ShapeDtypeStruct(shape, jnp.float32 if f32 else dtype)

    def walk(tree, prefix=""):
        return {
            k: (
                walk(v, f"{prefix}/{k}")
                if isinstance(v, dict)
                else leaf(f"{prefix}/{k}", v)
            )
            for k, v in tree.items()
        }

    return walk(param_shapes(cfg))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _dense_layer(cfg, lp, x, positions):
    h = x + attention(cfg, lp["attn"], rms_norm(x, lp["ln1"], cfg.norm_eps), positions)
    h = h + ffn(cfg, lp["ffn"], rms_norm(h, lp["ln2"], cfg.norm_eps))
    return h, jnp.float32(0.0)


def _moe_layer(cfg, lp, x, positions):
    h = x + attention(cfg, lp["attn"], rms_norm(x, lp["ln1"], cfg.norm_eps), positions)
    f, aux = moe_ffn(cfg, lp["moe"], rms_norm(h, lp["ln2"], cfg.norm_eps))
    return h + f, aux


def _ssm_layer(cfg, lp, x, positions):
    del positions
    h = x + rwkv6_time_mix(cfg, lp["tm"], rms_norm(x, lp["ln1"], cfg.norm_eps))
    h = h + rwkv6_channel_mix(cfg, lp["cm"], rms_norm(h, lp["ln2"], cfg.norm_eps))
    return h, jnp.float32(0.0)


def _mamba_layer(cfg, lp, x):
    return x + mamba2_block(cfg, lp["mix"], rms_norm(x, lp["ln"], cfg.norm_eps))


_LAYER = {"dense": _dense_layer, "moe": _moe_layer, "ssm": _ssm_layer}


def model_forward(
    cfg: ModelConfig,
    params: dict,
    tokens=None,
    inputs_embeds=None,
    positions=None,
    remat: bool = False,
    sp: bool = False,
):
    """Returns (logits (B,S,V) float32, moe aux loss scalar)."""
    if inputs_embeds is None:
        x = params["embed"][tokens]
    else:
        x = inputs_embeds.astype(params["embed"].dtype)
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), dtype=x.dtype)
    b, s = x.shape[:2]
    if positions is None:
        positions = positions_for(cfg, b, s)

    from ..dist.hints import constrain

    # sequence-parallel residual stream (Megatron SP), prefill only: with a
    # long sequence and a real per-device batch it halves link bytes and
    # HBM traffic; under train microbatching (per-device batch ~1) its
    # backward transposes force full-batch f32 gathers — measured 3.1x
    # MORE link traffic on qwen2-vl-72b (EXPERIMENTS.md §Perf it.3)
    seq_ax = "model" if sp else None
    x = constrain(x, "dp", seq_ax)

    if cfg.family == "hybrid":
        x = _hybrid_forward(cfg, params, x, positions, remat, sp)
        aux = jnp.float32(0.0)
    else:
        layer_fn = _LAYER[cfg.family]

        def body(carry, lp):
            h, acc = carry
            h, aux = layer_fn(cfg, lp, h, positions)
            h = constrain(h, "dp", seq_ax)
            return (h, acc + aux), None

        if remat:
            body = jax.checkpoint(body)
        (x, aux), _ = jax.lax.scan(
            body, (x, jnp.float32(0.0)), params["layers"], unroll=scan_unroll()
        )
        aux = aux / cfg.n_layers

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = (
        params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    )
    with jax.named_scope("lm_head"):
        logits = (x @ head).astype(jnp.float32)
    return logits, aux


def _hybrid_forward(cfg, params, x, positions, remat, sp: bool = False):
    from ..dist.hints import constrain

    seq_ax = "model" if sp else None
    shared = params["shared"]

    def shared_block(h):
        h = h + attention(
            cfg, shared["attn"], rms_norm(h, shared["ln1"], cfg.norm_eps), positions
        )
        return h + ffn(cfg, shared["ffn"], rms_norm(h, shared["ln2"], cfg.norm_eps))

    def group(h, gp):
        def inner(h2, lp):
            return constrain(_mamba_layer(cfg, lp, h2), "dp", seq_ax), None

        h, _ = jax.lax.scan(inner, h, gp, unroll=scan_unroll())
        return constrain(shared_block(h), "dp", seq_ax), None

    if remat:
        group = jax.checkpoint(group)
    x, _ = jax.lax.scan(group, x, params["layers"], unroll=scan_unroll())
    return x


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------


def loss_fn(cfg: ModelConfig, params, batch, remat: bool = True,
            sp: bool = False):
    """Causal LM cross-entropy (+ router aux).  batch: tokens/labels or
    inputs_embeds/labels.  ``sp`` = sequence-parallel residual stream
    (regime-dependent; see EXPERIMENTS.md §Perf it. 1.5)."""
    logits, aux = model_forward(
        cfg,
        params,
        tokens=batch.get("tokens"),
        inputs_embeds=batch.get("inputs_embeds"),
        positions=batch.get("positions"),
        remat=remat,
        sp=sp,
    )
    labels = batch["labels"]
    if cfg.vocab_padded != cfg.vocab:
        pad_mask = jnp.arange(cfg.vocab_padded) >= cfg.vocab
        logits = jnp.where(pad_mask, -1e30, logits)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    ce = jnp.mean(logz - gold)
    if cfg.router_aux_loss:
        ce = ce + cfg.router_aux_loss * aux
    return ce


# ---------------------------------------------------------------------------
# Decode (serve_step substrate)
# ---------------------------------------------------------------------------


def init_decode_state(cfg: ModelConfig, batch: int, max_seq: int, dtype=jnp.bfloat16):
    if cfg.family in ("dense", "moe"):
        kv = (cfg.n_layers, batch, max_seq, cfg.n_kv_heads, cfg.head_dim)
        return {"k": jnp.zeros(kv, dtype=dtype), "v": jnp.zeros(kv, dtype=dtype)}
    if cfg.family == "ssm":
        d = cfg.d_model
        h = d // cfg.rwkv_head_dim
        kk = cfg.rwkv_head_dim
        L = cfg.n_layers
        return {
            "tm_shift": jnp.zeros((L, batch, d), jnp.float32),
            "cm_shift": jnp.zeros((L, batch, d), jnp.float32),
            "wkv": jnp.zeros((L, batch, h, kk, kk), jnp.float32),
        }
    if cfg.family == "hybrid":
        g = cfg.n_layers // cfg.hybrid_attn_every
        e = cfg.hybrid_attn_every
        ph = cfg.d_inner // cfg.ssm_heads
        kv = (g, batch, max_seq, cfg.n_kv_heads, cfg.head_dim)
        return {
            "conv": jnp.zeros((g, e, batch, CONV_K - 1, cfg.d_inner), dtype),
            "ssm": jnp.zeros(
                (g, e, batch, cfg.ssm_heads, ph, cfg.ssm_state), jnp.float32
            ),
            "k": jnp.zeros(kv, dtype=dtype),
            "v": jnp.zeros(kv, dtype=dtype),
        }
    raise ValueError(cfg.family)


def abstract_decode_state(cfg: ModelConfig, batch: int, max_seq: int, dtype=jnp.bfloat16):
    return jax.eval_shape(
        partial(init_decode_state, cfg, batch, max_seq, dtype)
    )


def decode_state_batch_dims(cfg: ModelConfig) -> dict:
    """Index of the per-request batch axis in each decode-state leaf — the
    axis the serve engine scatters admitted rows along."""
    if cfg.family in ("dense", "moe"):
        return {"k": 1, "v": 1}
    if cfg.family == "ssm":
        return {"tm_shift": 1, "cm_shift": 1, "wkv": 1}
    if cfg.family == "hybrid":
        return {"conv": 2, "ssm": 2, "k": 1, "v": 1}
    raise ValueError(cfg.family)


def prefill_forward(cfg: ModelConfig, params, tokens, lengths,
                    state_dtype=jnp.bfloat16):
    """Bulk prefill: one forward over a right-padded request group.

    tokens: (B, S) int32 right-padded; lengths: (B,) int32 real lengths
    (>= 1).  Returns (last-token logits (B, V) float32, decode-state tree
    whose seq dimension — where one exists — is S).  Row i's state is the
    state a token-by-token decode would hold after its ``lengths[i]`` real
    tokens: pads contribute identity to every recurrence (masked k/w/dt),
    pad KV rows sit beyond the decode validity mask, and shift/conv tails
    are gathered per row at ``lengths - 1``.  Rows are computed
    independently, so a request's output does not depend on its batch
    companions (the scheduler-equivalence property)."""
    b, s = tokens.shape
    x = params["embed"][tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), dtype=x.dtype)
    positions = positions_for(cfg, b, s)
    valid = jnp.arange(s, dtype=jnp.int32)[None, :] < lengths[:, None]
    last = (lengths - 1).astype(jnp.int32)

    def row_last(a):  # (B, S, D) -> (B, D) at each row's final real token
        return jnp.take_along_axis(a, last[:, None, None], axis=1)[:, 0]

    if cfg.family in ("dense", "moe"):
        cap = b * s * cfg.moe_top_k if cfg.family == "moe" else None

        def body(h, lp):
            a, ck, cv = attention_prefill(
                cfg, lp["attn"], rms_norm(h, lp["ln1"], cfg.norm_eps), positions
            )
            h = h + a
            if cfg.family == "moe":
                f, _ = moe_ffn(
                    cfg, lp["moe"], rms_norm(h, lp["ln2"], cfg.norm_eps), cap=cap
                )
            else:
                f = ffn(cfg, lp["ffn"], rms_norm(h, lp["ln2"], cfg.norm_eps))
            return h + f, (ck.astype(state_dtype), cv.astype(state_dtype))

        x, (nk, nv) = jax.lax.scan(body, x, params["layers"], unroll=scan_unroll())
        state = {"k": nk, "v": nv}

    elif cfg.family == "ssm":

        def body(h, lp):
            xn1 = rms_norm(h, lp["ln1"], cfg.norm_eps)
            out, wkv = rwkv6_time_mix(
                cfg, lp["tm"], xn1, valid=valid, return_state=True
            )
            h = h + out
            xn2 = rms_norm(h, lp["ln2"], cfg.norm_eps)
            h = h + rwkv6_channel_mix(cfg, lp["cm"], xn2)
            return h, (
                row_last(xn1).astype(jnp.float32),
                row_last(xn2).astype(jnp.float32),
                wkv,
            )

        x, (tms, cms, wkv) = jax.lax.scan(
            body, x, params["layers"], unroll=scan_unroll()
        )
        state = {"tm_shift": tms, "cm_shift": cms, "wkv": wkv}

    elif cfg.family == "hybrid":
        shared = params["shared"]

        def group(h, gp):
            def inner(h2, lp):
                out, st = mamba2_prefill(
                    cfg, lp["mix"], rms_norm(h2, lp["ln"], cfg.norm_eps),
                    valid, lengths, state_dtype=state_dtype,
                )
                return h2 + out, (st["conv"], st["ssm"])

            h, (nconv, nssm) = jax.lax.scan(inner, h, gp, unroll=scan_unroll())
            a, ck, cv = attention_prefill(
                cfg, shared["attn"], rms_norm(h, shared["ln1"], cfg.norm_eps),
                positions,
            )
            h = h + a
            h = h + ffn(cfg, shared["ffn"], rms_norm(h, shared["ln2"], cfg.norm_eps))
            return h, (nconv, nssm, ck.astype(state_dtype), cv.astype(state_dtype))

        x, (nconv, nssm, nk, nv) = jax.lax.scan(
            group, x, params["layers"], unroll=scan_unroll()
        )
        state = {"conv": nconv, "ssm": nssm, "k": nk, "v": nv}
    else:
        raise ValueError(cfg.family)

    xl = rms_norm(row_last(x), params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    with jax.named_scope("lm_head"):
        logits = (xl @ head).astype(jnp.float32)
        if cfg.vocab_padded != cfg.vocab:
            pad_mask = jnp.arange(cfg.vocab_padded) >= cfg.vocab
            logits = jnp.where(pad_mask, -1e30, logits)
    return logits, state


def decode_step(cfg: ModelConfig, params, state, tokens, pos, moe_cap=None):
    """One decode step.  tokens: (B, 1) int32; pos: () int32 current index
    or (B,) per-slot positions (continuous batching).  ``moe_cap``
    overrides MoE expert capacity (serving passes drop-free B*k).  The
    layer scan only reads the stacked KV caches and returns each layer's
    new rows, written in place after it (``write_kv_rows``).
    Returns (logits (B, V) float32, new state)."""
    x = params["embed"][tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), dtype=x.dtype)

    if cfg.family in ("dense", "moe"):

        def body(h, scanned):
            lp, ck, cv = scanned
            a, nk, nv = attention_decode(
                cfg, lp["attn"], rms_norm(h, lp["ln1"], cfg.norm_eps), ck, cv, pos
            )
            h = h + a
            if cfg.family == "moe":
                f, _ = moe_ffn(
                    cfg, lp["moe"], rms_norm(h, lp["ln2"], cfg.norm_eps),
                    cap=moe_cap,
                )
            else:
                f = ffn(cfg, lp["ffn"], rms_norm(h, lp["ln2"], cfg.norm_eps))
            return h + f, (nk, nv)

        x, (nk, nv) = jax.lax.scan(
            body, x, (params["layers"], state["k"], state["v"]), unroll=scan_unroll()
        )
        new_state = {"k": write_kv_rows(state["k"], nk, pos),
                     "v": write_kv_rows(state["v"], nv, pos)}

    elif cfg.family == "ssm":

        def body(h, scanned):
            lp, tms, cms, wkv = scanned
            ht = h[:, 0]
            out, new_tms, new_wkv = rwkv6_time_mix_step(
                cfg, lp["tm"], {"tm_shift": tms, "wkv": wkv},
                rms_norm(ht, lp["ln1"], cfg.norm_eps),
            )
            ht = ht + out
            out, new_cms = rwkv6_channel_mix_step(
                cfg, lp["cm"], cms, rms_norm(ht, lp["ln2"], cfg.norm_eps)
            )
            ht = ht + out
            return ht[:, None, :], (new_tms, new_cms, new_wkv)

        x, (tms, cms, wkv) = jax.lax.scan(
            body, x,
            (params["layers"], state["tm_shift"], state["cm_shift"], state["wkv"]),
            unroll=scan_unroll(),
        )
        new_state = {"tm_shift": tms, "cm_shift": cms, "wkv": wkv}

    elif cfg.family == "hybrid":
        shared = params["shared"]

        def group_body(h, scanned):
            gp, conv, ssm, ck, cv = scanned

            def inner(h2, s2):
                lp, cv2, sv2 = s2
                out, ns = mamba2_decode_step(
                    cfg, lp["mix"], {"conv": cv2, "ssm": sv2},
                    rms_norm(h2, lp["ln"], cfg.norm_eps),
                )
                return h2 + out, (ns["conv"], ns["ssm"])

            h, (nconv, nssm) = jax.lax.scan(inner, h, (gp, conv, ssm), unroll=scan_unroll())
            a, nk, nv = attention_decode(
                cfg, shared["attn"], rms_norm(h, shared["ln1"], cfg.norm_eps),
                ck, cv, pos,
            )
            h = h + a
            h = h + ffn(cfg, shared["ffn"], rms_norm(h, shared["ln2"], cfg.norm_eps))
            return h, (nconv, nssm, nk, nv)

        x, (nconv, nssm, nk, nv) = jax.lax.scan(
            group_body,
            x,
            (params["layers"], state["conv"], state["ssm"], state["k"], state["v"]),
            unroll=scan_unroll(),
        )
        new_state = {"conv": nconv, "ssm": nssm,
                     "k": write_kv_rows(state["k"], nk, pos),
                     "v": write_kv_rows(state["v"], nv, pos)}
    else:
        raise ValueError(cfg.family)

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    with jax.named_scope("lm_head"):
        logits = (x[:, 0] @ head).astype(jnp.float32)
        if cfg.vocab_padded != cfg.vocab:
            pad_mask = jnp.arange(cfg.vocab_padded) >= cfg.vocab
            logits = jnp.where(pad_mask, -1e30, logits)
    return logits, new_state
