"""Plain float32 reference of a pre-norm dense decoder (Qwen2, MiniCPM).

Written from the published description, not from the program:
RMSNorm with gain ``1 + w``, rotary embeddings on the two halves of each
head (the Hugging Face ``rotate_half`` form), grouped-query causal
softmax attention with optional q/k/v biases, a SiLU-gated MLP, and a
head that is the embedding table when the configuration ties them.
Logits cover the ``vocab`` real ids only.  Every matrix product runs at
``highest`` precision, so on a TPU it is float32 and not bfloat16.

It imports nothing of the program.  Weights come from
``benchmarks/chip/weights.py``, one layer at a time, so a model that
fills the chip in bf16 can be rebuilt in float32 beside nothing else.

``control=True`` is the same computation with every matrix rounded to
float8 (e4m3, one scale per matrix): the nearest precision below the
bf16 the configurations serve in, which a served token must not reach.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .. import weights

HIGHEST = jax.lax.Precision.HIGHEST


def _fp8(w):
    s = jnp.max(jnp.abs(w)) / 448.0
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _f32(tree: dict, control: bool) -> dict:
    out = {}
    for k, v in tree.items():
        v = v.astype(jnp.float32)
        out[k] = _fp8(v) if control and v.ndim == 2 else v
    return out


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def _rope(x, pos, theta):
    dh = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, dh, 2) / dh)
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


@partial(jax.jit, static_argnums=(0,))
def _layer(mkey: tuple, w: dict, x):
    """One decoder layer over one sequence x (T, d), float32."""
    m = dict(mkey)
    T = x.shape[0]
    H, KH, dh = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    eps = m.get("norm_eps", 1e-5)
    pos = jnp.arange(T)
    h = _rms(x, w["ln1"], eps)
    q, k, v = _mm(h, w["attn/wq"]), _mm(h, w["attn/wk"]), _mm(h, w["attn/wv"])
    if m.get("qkv_bias"):
        q, k, v = q + w["attn/bq"], k + w["attn/bk"], v + w["attn/bv"]
    q = _rope(q.reshape(T, H, dh), pos, m["rope_theta"])
    k = _rope(k.reshape(T, KH, dh), pos, m["rope_theta"])
    v = v.reshape(T, KH, dh)
    k, v = jnp.repeat(k, H // KH, axis=1), jnp.repeat(v, H // KH, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k, precision=HIGHEST) / np.sqrt(dh)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hts,shd->thd", p, v, precision=HIGHEST).reshape(T, H * dh)
    x = x + _mm(o, w["attn/wo"])
    h = _rms(x, w["ln2"], eps)
    g = _mm(h, w["ffn/w_gate"])
    return x + _mm(jax.nn.silu(g) * _mm(h, w["ffn/w_up"]), w["ffn/w_down"])


@partial(jax.jit, static_argnums=(0,))
def _logits(mkey: tuple, top: dict, rows):
    """Head over the real vocabulary for final-layer rows (R, d)."""
    m = dict(mkey)
    V = m["vocab"]
    h = _rms(rows, top["final_norm"], m.get("norm_eps", 1e-5))
    head = top["embed"][:V].T if m.get("tie_embeddings") else top["lm_head"][:, :V]
    return _mm(h, head)


@jax.jit
def _gaps(ref, chosen_by, served):
    """For each row: how far the reference logit of the token that
    ``chosen_by`` puts first (or of the served token, where ``served``
    is given) lies below the reference's best."""
    pick = jnp.where(served >= 0, served, jnp.argmax(chosen_by, -1))
    return jnp.max(ref, -1) - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]


def _mkey(m: dict) -> tuple:
    keys = ("d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff", "vocab",
            "n_layers", "qkv_bias", "tie_embeddings", "rope_theta", "norm_eps",
            "pad_vocab_multiple")
    return tuple((k, m[k]) for k in keys if k in m)


def final_rows(m: dict, seed: int, seqs: list[list[int]], spans: list[tuple[int, int]],
               length: int, control: bool = False):
    """Run every sequence (padded at the end to ``length``, which causal
    attention leaves without effect) through all layers, one layer's
    weights at a time, and return the final-layer rows ``[a, b)`` of each
    sequence, stacked."""
    mk = _mkey(m)
    top = _f32(weights.top_weights(m, seed), control)
    xs = []
    for s in seqs:
        ids = np.zeros(length, np.int32)
        ids[: len(s)] = s
        xs.append(top["embed"][jnp.asarray(ids)])
    for layer in range(m["n_layers"]):
        w = _f32(weights.layer_weights(m, seed, layer), control)
        xs = [_layer(mk, w, x) for x in xs]
        del w
    rows = jnp.concatenate([x[a:b] for x, (a, b) in zip(xs, spans)])
    return mk, top, rows


def served_gaps(m: dict, seed: int, seqs, spans, served, length: int,
                control: bool = False, chunk: int = 256) -> np.ndarray:
    """The gap of each served token below the reference's best logit at
    its position.  With ``control``, the token is instead the one a
    float8 copy of the reference puts first at that position."""
    mk, top, rows = final_rows(m, seed, seqs, spans, length)
    if control:
        _, ctop, crows = final_rows(m, seed, seqs, spans, length, control=True)
    n = rows.shape[0]
    pad = -n % chunk  # one compiled head for every sample size
    served = np.concatenate([np.asarray(served, np.int32), np.zeros(pad, np.int32)])
    rows = jnp.pad(rows, ((0, pad), (0, 0)))
    if control:
        crows = jnp.pad(crows, ((0, pad), (0, 0)))
    out = []
    for i in range(0, n + pad, chunk):
        ref = _logits(mk, top, rows[i:i + chunk])
        if control:
            by = _logits(mk, ctop, crows[i:i + chunk])
            sv = jnp.full((ref.shape[0],), -1, jnp.int32)
        else:
            by, sv = ref, jnp.asarray(served[i:i + chunk])
        out.append(np.asarray(_gaps(ref, by, sv)))
    return np.concatenate(out)[:n]


# ---------------------------------------------------------------------------
# Training: loss, gradients and AdamW, all float32 at highest precision
# ---------------------------------------------------------------------------


def _stack_layers(m: dict, seed: int, dtype, control: bool) -> dict:
    ws = [_f32(weights.layer_weights(m, seed, layer, dtype), control)
          for layer in range(m["n_layers"])]
    return {k: jnp.stack([w[k] for w in ws]) for k in ws[0]}


def train_params(m: dict, seed: int, dtype=jnp.float32, control: bool = False) -> dict:
    """``{"top": {...}, "layers": {path: (L, ...)}}``, float32, from the
    benchmark's weights (the values the program was given)."""
    return {"top": _f32(weights.top_weights(m, seed, dtype), control),
            "layers": _stack_layers(m, seed, dtype, control)}


def _loss(mkey: tuple, p: dict, tokens, labels, low):
    """Mean next-token cross-entropy over the real vocabulary.  ``low``
    rounds every matrix product's inputs to bfloat16 (with the weights
    held in bfloat16, the control)."""
    m = dict(mkey)
    V = m["vocab"]

    def mm(a, b):
        if low:
            a, b = a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)
            return jnp.matmul(a, b, preferred_element_type=jnp.float32)
        return _mm(a, b)

    def row(carry, xy):
        tok, lab = xy
        x = p["top"]["embed"][tok]
        for layer in range(m["n_layers"]):
            w = {k: v[layer] for k, v in p["layers"].items()}
            x = _layer_body(m, w, x, mm)
        h = _rms(x, p["top"]["final_norm"], m.get("norm_eps", 1e-5))
        head = p["top"]["embed"][:V].T if m.get("tie_embeddings") else p["top"]["lm_head"][:, :V]
        logits = mm(h, head)
        lz = jax.scipy.special.logsumexp(logits, -1)
        gold = jnp.take_along_axis(logits, lab[:, None], -1)[:, 0]
        return carry + jnp.sum(lz - gold), None

    total, _ = jax.lax.scan(jax.checkpoint(row), jnp.float32(0.0), (tokens, labels))
    return total / tokens.size


def _layer_body(m, w, x, mm):
    T = x.shape[0]
    H, KH, dh = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    eps = m.get("norm_eps", 1e-5)
    pos = jnp.arange(T)
    h = _rms(x, w["ln1"], eps)
    q, k, v = mm(h, w["attn/wq"]), mm(h, w["attn/wk"]), mm(h, w["attn/wv"])
    if m.get("qkv_bias"):
        q, k, v = q + w["attn/bq"], k + w["attn/bk"], v + w["attn/bv"]
    q = _rope(q.reshape(T, H, dh), pos, m["rope_theta"])
    k = _rope(k.reshape(T, KH, dh), pos, m["rope_theta"])
    v = v.reshape(T, KH, dh)
    k, v = jnp.repeat(k, H // KH, axis=1), jnp.repeat(v, H // KH, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k, precision=HIGHEST) / np.sqrt(dh)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v,
                   precision=HIGHEST).reshape(T, H * dh)
    x = x + mm(o, w["attn/wo"])
    h = _rms(x, w["ln2"], eps)
    return x + mm(jax.nn.silu(mm(h, w["ffn/w_gate"])) * mm(h, w["ffn/w_up"]),
                  w["ffn/w_down"])


@partial(jax.jit, static_argnums=(0, 4))
def loss_and_grad(mkey: tuple, p: dict, tokens, labels, low: bool = False):
    return jax.value_and_grad(lambda q: _loss(mkey, q, tokens, labels, low))(p)


def lr_at(opt: dict, t: int) -> float:
    """The configuration's schedule at update ``t`` (1-based): linear
    warm-up to ``lr`` over ``warmup_steps`` (``min(1, (t + 1) / warmup)``),
    then flat until the last ``wsd_decay_frac`` of ``total_steps``."""
    warm = min(1.0, (t + 1) / opt["warmup_steps"])
    start = opt["total_steps"] * (1 - opt["wsd_decay_frac"])
    decay = np.exp(np.log(1e-2) * max(0.0, t - start)
                   / max(opt["total_steps"] * opt["wsd_decay_frac"], 1.0))
    return opt["lr"] * warm * float(decay)


@partial(jax.jit, static_argnums=(0, 7), donate_argnums=(1, 2, 3, 4))
def _adamw(hyper: tuple, p, g, mstate, vstate, t, lr, low: bool = False):
    """AdamW with global-norm clipping; decay on weight matrices only
    (embedding, head, projections), never on norms or biases.  ``low``
    holds the weights in bfloat16 (the control)."""
    h = dict(hyper)
    gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    scale = jnp.minimum(1.0, h["grad_clip"] / (gnorm + 1e-12))
    g = jax.tree.map(lambda x: x * scale, g)
    mstate = jax.tree.map(lambda a, b: h["b1"] * a + (1 - h["b1"]) * b, mstate, g)
    vstate = jax.tree.map(lambda a, b: h["b2"] * a + (1 - h["b2"]) * b * b, vstate, g)
    c1, c2 = 1 - h["b1"] ** t, 1 - h["b2"] ** t

    def upd(path, x, a, b):
        key = jax.tree_util.keystr(path)
        is_matrix = x.ndim - (1 if "layers" in key else 0) >= 2
        d = (a / c1) / (jnp.sqrt(b / c2) + h["eps"])
        if is_matrix:
            d = d + h["weight_decay"] * x
        x = x - lr * d
        return x.astype(jnp.bfloat16).astype(jnp.float32) if low else x

    p = jax.tree_util.tree_map_with_path(upd, p, mstate, vstate)
    return p, mstate, vstate, g


def train_steps(m: dict, seed: int, batches: list[dict], opt: dict, control: bool = False):
    """Run len(batches) AdamW steps from the seeded weights (with
    ``control``, weights held and multiplied in bfloat16).  Returns
    the loss of each step, the per-leaf norms of the first (clipped)
    gradient as the optimizer took it, and the per-leaf norms of the
    parameters' change after the last step, each keyed by the
    program's leaf path."""
    mk = _mkey(m)
    hyper = tuple(sorted((k, opt[k]) for k in ("b1", "b2", "eps", "weight_decay", "grad_clip")))
    p = train_params(m, seed, jnp.float32)
    if control:
        p = jax.tree.map(lambda x: x.astype(jnp.bfloat16).astype(jnp.float32), p)
    ms = jax.tree.map(jnp.zeros_like, p)
    vs = jax.tree.map(jnp.zeros_like, p)
    losses, g1 = [], None
    for t, b in enumerate(batches, start=1):
        loss, g = loss_and_grad(mk, p, jnp.asarray(b["tokens"]), jnp.asarray(b["labels"]),
                                control)
        p, ms, vs, g = _adamw(hyper, p, g, ms, vs, jnp.float32(t),
                              jnp.float32(lr_at(opt, t)), control)
        losses.append(float(loss))
        if g1 is None:
            g1 = leaf_norms(g)
        del g
    del ms, vs  # the weights again, to measure the change, with room for them
    dp = leaf_norms(jax.tree.map(jnp.subtract, p, train_params(m, seed, jnp.float32)))
    return losses, g1, dp


def leaf_norms(tree) -> dict:
    """``{program path: norm}`` for a reference tree."""
    out = {}
    for k, v in tree["top"].items():
        out[k] = float(jnp.linalg.norm(v.ravel()))
    for k, v in tree["layers"].items():
        out["layers/" + k] = float(jnp.linalg.norm(v.ravel()))
    return out
