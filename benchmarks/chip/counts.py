"""Operations and bytes that the work needs, computed from shapes.

A multiply-add counts as 2 FLOPs.  Only the work the model requires is
counted: padding rows, masked attention halves and recomputation are
not, so a share of a peak computed from these counts is what the chip
spent on useful work.  ``m`` is a configuration dict with the keys of
``benchmarks/chip/configs/*.json`` (``d_model``, ``n_heads``,
``n_kv_heads``, ``head_dim``, ``d_ff``, ``vocab``, ``n_layers``,
``tie_embeddings``).
"""

from __future__ import annotations

BF16 = 2


def layer_matmul_params(m: dict) -> int:
    """Weights one token multiplies by in one decoder layer: q, k, v, o
    projections and a gated (three-matrix) MLP."""
    d, q, kv = m["d_model"], m["n_heads"] * m["head_dim"], m["n_kv_heads"] * m["head_dim"]
    return d * q + 2 * d * kv + q * d + 3 * d * m["d_ff"]


def head_params(m: dict) -> int:
    return m["d_model"] * m["vocab"]


def attn_pair_flops(m: dict) -> int:
    """FLOPs of one (query, key) pair in one layer: q.k and p.v over
    every query head."""
    return 4 * m["n_heads"] * m["head_dim"]


def causal_pairs(n: int) -> int:
    return n * (n + 1) // 2


def decode_flops(m: dict, ctx: int) -> int:
    """One decode token whose query attends to ``ctx`` cached positions
    (itself included)."""
    L = m["n_layers"]
    return (2 * L * layer_matmul_params(m) + L * attn_pair_flops(m) * ctx
            + 2 * head_params(m))


def kv_bytes(m: dict, ctx: int, itemsize: int = BF16) -> int:
    """Keys and values of ``ctx`` cached positions, all layers."""
    return 2 * m["n_layers"] * ctx * m["n_kv_heads"] * m["head_dim"] * itemsize


def weight_bytes(m: dict, itemsize: int = BF16) -> int:
    """Weights a decode step reads: every layer's matrices and the head
    (with tied embeddings, the embedding table is the head)."""
    return (m["n_layers"] * layer_matmul_params(m) + head_params(m)) * itemsize


def decode_step_bound_s(m: dict, ctxs, peaks: dict) -> tuple[float, str]:
    """The least time one decode step over rows with cache lengths
    ``ctxs`` could take on a chip: the larger of its FLOPs at peak and
    its bytes (weights once, each live row's cache) at HBM bandwidth."""
    flops = sum(decode_flops(m, c) for c in ctxs)
    nbytes = weight_bytes(m) + sum(kv_bytes(m, c) for c in ctxs)
    t_f = flops / peaks["bf16_flops"]
    t_b = nbytes / peaks["hbm_bytes_per_s"]
    return (t_b, "bytes") if t_b >= t_f else (t_f, "flops")


def train_flops_per_token(m: dict, seq: int) -> float:
    """Forward and backward FLOPs per trained token (3x the forward),
    recomputation not counted: all layer matrices, causal attention
    over a sequence of ``seq``, and the head at every position."""
    L = m["n_layers"]
    fwd = (2 * L * layer_matmul_params(m)
           + L * attn_pair_flops(m) * causal_pairs(seq) / seq
           + 2 * head_params(m))
    return 3.0 * fwd

