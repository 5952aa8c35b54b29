"""Serving correctness: decode path must reproduce the training forward.

For every family, stepping the decode state token-by-token must produce
the same logits as the full-sequence forward at each position — this is
the invariant that validates KV caches (dense/moe), recurrent WKV state
(ssm), conv+SSD state (hybrid), and the chunked training-time formulations
against their sequential decode twins.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.models import (
    decode_step,
    init_decode_state,
    init_params,
    model_forward,
)
from repro.serve import (
    ContinuousBatchingEngine,
    QueueFull,
    ServeEngine,
    make_prefill_step,
)

FAMILY_REP = {
    "dense": "qwen2-7b",        # GQA + qkv bias + rope
    "moe": "deepseek-moe-16b",  # shared + routed experts
    "ssm": "rwkv6-1.6b",
    "hybrid": "zamba2-2.7b",
}


@pytest.mark.parametrize("arch", sorted(FAMILY_REP.values()))
def test_decode_matches_forward(arch):
    cfg = get_config(arch).reduced()
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    B, S = 2, 8
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)

    full_logits, _ = model_forward(cfg, params, tokens=tokens)

    state = init_decode_state(cfg, B, max_seq=S, dtype=jnp.float32)
    step = jax.jit(lambda p, st, t, i: decode_step(cfg, p, st, t, i))
    for t in range(S):
        logits, state = step(params, state, tokens[:, t : t + 1], jnp.int32(t))
        np.testing.assert_allclose(
            logits,
            full_logits[:, t],
            rtol=2e-4,
            atol=2e-4,
            err_msg=f"{arch}: decode diverges from forward at position {t}",
        )


def test_prefill_last_only_matches_forward():
    cfg = get_config("gemma-2b").reduced()
    params = init_params(cfg, jax.random.PRNGKey(2), dtype=jnp.float32)
    B, S = 2, 12
    tokens = jax.random.randint(jax.random.PRNGKey(3), (B, S), 0, cfg.vocab)
    full_logits, _ = model_forward(cfg, params, tokens=tokens)
    # forward returns padded-vocab logits unmasked; mask like prefill does
    pad_mask = jnp.arange(cfg.vocab_padded) >= cfg.vocab
    want = jnp.where(pad_mask, -1e30, full_logits[:, -1])
    prefill = make_prefill_step(cfg, last_only=True)
    got = prefill(params, {"tokens": tokens})
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_engine_greedy_deterministic():
    cfg = get_config("musicgen-medium").reduced()
    params = init_params(cfg, jax.random.PRNGKey(4), dtype=jnp.float32)
    eng = ServeEngine(cfg, params, max_seq=64)
    prompts = [[1, 2, 3], [4, 5]]
    a = eng.generate(prompts, max_new=6)
    b = eng.generate(prompts, max_new=6)
    assert a == b
    assert all(len(s) == len(p) + 6 for s, p in zip(a, prompts))
    assert all(0 <= t < cfg.vocab for s in a for t in s)  # padded ids masked


def test_engine_temperature_sampling_valid():
    cfg = get_config("gemma-2b").reduced()
    params = init_params(cfg, jax.random.PRNGKey(5), dtype=jnp.float32)
    eng = ServeEngine(cfg, params, max_seq=64)
    out = eng.generate([[7, 8]], max_new=5, temperature=1.0, seed=3)
    assert len(out[0]) == 7
    assert all(0 <= t < cfg.vocab for t in out[0])


# ---------------------------------------------------------------------------
# Continuous-batching scheduler
# ---------------------------------------------------------------------------

_GEO = dict(slots=2, max_seq=32, prefill_pad=8, state_dtype=jnp.float32)

_REQS = [
    {"prompt": [1, 5, 9], "max_new": 7, "seed": 0, "temperature": 0.0},
    {"prompt": [2, 4, 6, 8, 10], "max_new": 5, "seed": 1, "temperature": 1.0},
    {"prompt": [3], "max_new": 6, "seed": 2, "temperature": 0.0},
    {"prompt": [11, 13], "max_new": 4, "seed": 3, "temperature": 0.7},
]


def _submit(eng, r):
    return eng.submit(r["prompt"], max_new=r["max_new"],
                      temperature=r["temperature"], seed=r["seed"])


@pytest.mark.parametrize("arch", sorted(FAMILY_REP.values()))
def test_scheduled_bitwise_matches_isolated(arch):
    """Admitting and evicting requests mid-decode must not perturb other
    slots: each request's tokens are bitwise-identical to generating it
    alone on an engine with the same geometry.  This is the invariant
    that makes continuous batching a pure throughput optimization."""
    cfg = get_config(arch).reduced()
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)

    eng = ContinuousBatchingEngine(cfg, params, **_GEO)
    live = [_submit(eng, r) for r in _REQS[:2]]
    pending, steps = _REQS[2:], 0
    while not eng.sched.idle:
        eng.step()
        steps += 1
        if steps == 3 and pending:  # two more arrive mid-decode
            live += [_submit(eng, r) for r in pending]
            pending = []
    scheduled = [r.tokens for r in live]
    assert eng.serve_stats()["admitted"] == len(_REQS)
    assert eng.serve_stats()["retired"] == len(_REQS)

    iso = ContinuousBatchingEngine(cfg, params, **_GEO)
    for want, r in zip(scheduled, _REQS):
        _submit(iso, r)
        (req,) = iso.run()
        assert req.tokens == want, (
            f"{arch}: scheduled tokens diverge from isolated generation"
        )


def test_slot_reuse_after_retirement():
    cfg = get_config("gemma-2b").reduced()
    params = init_params(cfg, jax.random.PRNGKey(6), dtype=jnp.float32)
    eng = ContinuousBatchingEngine(cfg, params, **_GEO)
    reqs = [eng.submit([i + 1, i + 2], max_new=3 + i % 3, seed=i)
            for i in range(5)]
    done = eng.run()
    assert len(done) == 5 and all(r.done for r in reqs)
    assert all(len(r.tokens) == r.max_new for r in reqs)
    stats = eng.serve_stats()
    assert stats["admitted"] == stats["retired"] == 5  # rows were recycled
    assert eng.sched.free_slots() == list(range(_GEO["slots"]))


def test_queue_overflow_backpressure():
    cfg = get_config("gemma-2b").reduced()
    params = init_params(cfg, jax.random.PRNGKey(7), dtype=jnp.float32)
    eng = ContinuousBatchingEngine(cfg, params, slots=1, max_seq=32,
                                   prefill_pad=8, max_queue=2,
                                   state_dtype=jnp.float32)
    eng.submit([1], max_new=2)
    eng.submit([2], max_new=2)
    with pytest.raises(QueueFull):
        eng.submit([3], max_new=2)
    assert eng.serve_stats()["rejected"] == 1
    assert len(eng.run()) == 2  # queued work unharmed by the rejection
    eng.submit([3], max_new=2)  # capacity is back after draining
    assert len(eng.run()) == 1


def test_decode_state_donation():
    """donate_argnums must actually consume the previous carry (in-place
    update, no per-step state copy) without corrupting generation."""
    cfg = get_config("gemma-2b").reduced()
    params = init_params(cfg, jax.random.PRNGKey(8), dtype=jnp.float32)
    eng = ContinuousBatchingEngine(cfg, params, **_GEO)
    req = eng.submit([1, 2, 3], max_new=8)
    eng.step()  # admit + first decode
    old = jax.tree_util.tree_leaves(eng._carry)
    eng.step()
    assert all(leaf.is_deleted() for leaf in old), (
        "previous carry buffers survived the step: donation fell back "
        "to copying"
    )
    eng.run()
    assert len(req.tokens) == 8
    assert all(0 <= t < cfg.vocab for t in req.tokens)


def test_per_token_stamps_and_itl():
    """Every generated token carries the time its step synced: one stamp
    per token, the first at ``first_token_t``, never decreasing; the
    engine's ITL percentiles are read from their differences."""
    cfg = get_config("gemma-2b").reduced()
    params = init_params(cfg, jax.random.PRNGKey(9), dtype=jnp.float32)
    eng = ContinuousBatchingEngine(cfg, params, **_GEO)
    reqs = [_submit(eng, r) for r in _REQS]
    eng.run()
    gaps = []
    for r in reqs:
        assert len(r.token_t) == len(r.tokens) == r.max_new
        assert r.token_t[0] == r.first_token_t
        assert all(a <= b for a, b in zip(r.token_t, r.token_t[1:]))
        gaps += [b - a for a, b in zip(r.token_t, r.token_t[1:])]
    stats = eng.serve_stats()
    assert stats["itl_p50_ms"] == pytest.approx(np.percentile(gaps, 50) * 1e3)
    assert stats["itl_p95_ms"] == pytest.approx(np.percentile(gaps, 95) * 1e3)
    assert not any(k.startswith("tpot") for k in stats)
