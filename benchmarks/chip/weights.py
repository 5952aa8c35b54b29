"""Seeded weights for a dense decoder, made on the device.

The benchmark, not the program, makes the weights: ``make_params`` is one
jitted call that fills the program's parameter tree (its layout:
stacked layers, norms stored as ``w`` in ``1 + w``) from the seed, in
the dtype they are served or trained in.  ``layer_weights`` makes one
layer's leaves again, bit for bit the same values, so the reference can
rebuild the model a layer at a time without taking anything the
program holds.

Scales: matrices N(0, 1/fan_in); the embedding and head N(0, 1/d_model);
biases and norm offsets N(0, 0.1^2).
"""

from __future__ import annotations

import zlib
from functools import partial

import jax
import jax.numpy as jnp


def vocab_padded(m: dict) -> int:
    k = m.get("pad_vocab_multiple", 256)
    return -(-m["vocab"] // k) * k


def layer_leaves(m: dict, dtype) -> dict:
    """``{path: (shape, dtype)}`` of one decoder layer."""
    d, q, kv, ff = (m["d_model"], m["n_heads"] * m["head_dim"],
                    m["n_kv_heads"] * m["head_dim"], m["d_ff"])
    out = {
        "ln1": ((d,), jnp.float32), "ln2": ((d,), jnp.float32),
        "attn/wq": ((d, q), dtype), "attn/wk": ((d, kv), dtype),
        "attn/wv": ((d, kv), dtype), "attn/wo": ((q, d), dtype),
        "ffn/w_gate": ((d, ff), dtype), "ffn/w_up": ((d, ff), dtype),
        "ffn/w_down": ((ff, d), dtype),
    }
    if m.get("qkv_bias"):
        out.update({"attn/bq": ((q,), dtype), "attn/bk": ((kv,), dtype),
                    "attn/bv": ((kv,), dtype)})
    return out


def top_leaves(m: dict, dtype) -> dict:
    d, vp = m["d_model"], vocab_padded(m)
    out = {"embed": ((vp, d), dtype), "final_norm": ((d,), jnp.float32)}
    if not m.get("tie_embeddings"):
        out["lm_head"] = ((d, vp), dtype)
    return out


def _scale(path: str, shape: tuple, m: dict) -> float:
    name = path.rsplit("/", 1)[-1]
    if name in ("embed", "lm_head"):
        return m["d_model"] ** -0.5
    if len(shape) == 1:
        return 0.1
    return shape[0] ** -0.5


def _leaf(key, path: str, shape: tuple, dtype, m: dict, layer=None):
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    x = jax.random.normal(k, shape, jnp.float32) * _scale(path, shape, m)
    return x.astype(dtype)


def seed_key(seed: int):
    """Key arguments for any seed up to 64 bits, as device data, so one
    compiled program serves every seed."""
    return jnp.asarray([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], jnp.uint32)


def _base(seed_words):
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, seed_words[0])
    return jax.random.fold_in(key, seed_words[1])


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = v
    return out


@partial(jax.jit, static_argnums=(0, 1))
def _make(mkey: tuple, dtype, seed_words):
    m = dict(mkey)
    key = _base(seed_words)
    flat = {p: _leaf(key, p, s, dt, m) for p, (s, dt) in top_leaves(m, dtype).items()}
    layers = jnp.arange(m["n_layers"])
    for p, (s, dt) in layer_leaves(m, dtype).items():
        flat["layers/" + p] = jax.lax.map(
            lambda l, p=p, s=s, dt=dt: _leaf(key, "layers/" + p, s, dt, m, l), layers)
    return _nest(flat)


def _mkey(m: dict) -> tuple:
    keys = ("d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff", "vocab",
            "n_layers", "qkv_bias", "tie_embeddings", "pad_vocab_multiple")
    return tuple((k, m[k]) for k in keys if k in m)


def make_params(m: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """The whole parameter tree, in one jitted call on the default device."""
    return _make(_mkey(m), dtype, seed_key(seed))


@partial(jax.jit, static_argnums=(0, 1))
def _layer(mkey: tuple, dtype, seed_words, layer):
    m = dict(mkey)
    key = _base(seed_words)
    return {p: _leaf(key, "layers/" + p, s, dt, m, layer)
            for p, (s, dt) in layer_leaves(m, dtype).items()}


def layer_weights(m: dict, seed: int, layer: int, dtype=jnp.bfloat16) -> dict:
    """Layer ``layer``'s leaves, flat ``{"attn/wq": ...}``, the same values
    ``make_params`` puts in the stack."""
    return _layer(_mkey(m), dtype, seed_key(seed), jnp.int32(layer))


@partial(jax.jit, static_argnums=(0, 1))
def _top(mkey: tuple, dtype, seed_words):
    m = dict(mkey)
    key = _base(seed_words)
    return {p: _leaf(key, p, s, dt, m) for p, (s, dt) in top_leaves(m, dtype).items()}


def top_weights(m: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    return _top(_mkey(m), dtype, seed_key(seed))
