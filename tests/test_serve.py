"""Serving correctness: decode path must reproduce the training forward.

For every family, stepping the decode state token-by-token must produce
the same logits as the full-sequence forward at each position — this is
the invariant that validates KV caches (dense/moe), recurrent WKV state
(ssm), conv+SSD state (hybrid), and the chunked training-time formulations
against their sequential decode twins.
"""

import os
import subprocess
import sys
from pathlib import Path

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
from repro.models.model import decode_state_batch_dims
from repro.serve import (
    ContinuousBatchingEngine,
    QueueFull,
    ServeEngine,
    make_prefill_step,
)
from repro.serve.engine import init_carry, make_decode_step

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


# the serve path's configurations: each family, the chat cell's model
# (multi-head, kh == h, as published) and a softcapped variant
_PER_ROW = {
    **{a: get_config(a).reduced() for a in FAMILY_REP.values()},
    "minicpm-2b": get_config("minicpm-2b").reduced(n_kv_heads=4),
    "qwen2-7b-softcap": get_config("qwen2-7b").reduced(attn_logit_softcap=5.0),
}


@pytest.mark.parametrize("name", sorted(_PER_ROW))
def test_per_row_decode_matches_forward(name):
    """The serve engine's path: one position per slot, the slots at
    staggered depths.  Each slot is first stepped alone to its depth, the
    four states are joined along the batch axis, and then every step
    decodes all slots at once; each slot's logits must match the forward
    pass at its own position."""
    cfg = _PER_ROW[name]
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    offsets = np.array([0, 1, 3, 5])
    B, max_seq = len(offsets), 16  # the chunked scans take multiples of 8
    S = max_seq - int(offsets.max())
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, max_seq), 0, cfg.vocab)
    # one forward per row: a row alone never overflows an expert's capacity
    full_logits = jnp.concatenate(
        [model_forward(cfg, params, tokens=tokens[b:b + 1])[0] for b in range(B)]
    )
    step = jax.jit(lambda p, st, t, i: decode_step(cfg, p, st, t, i))

    def check(logits, rows, at):
        np.testing.assert_allclose(
            logits, full_logits[rows, at], rtol=2e-4, atol=2e-4,
            err_msg=f"{name}: per-row decode diverges from forward at {at}",
        )

    alone = []
    for b, off in enumerate(offsets):
        st = init_decode_state(cfg, 1, max_seq=max_seq, dtype=jnp.float32)
        for t in range(off):
            logits, st = step(params, st, tokens[b:b + 1, t:t + 1],
                              jnp.array([t], jnp.int32))
            check(logits, [b], [t])
        alone.append(st)
    dims = decode_state_batch_dims(cfg)
    state = {n: jnp.concatenate([st[n] for st in alone], axis=dims[n])
             for n in dims}
    rows = np.arange(B)
    for t in range(S):
        pos = offsets + t
        logits, state = step(params, state, tokens[rows, pos][:, None],
                             jnp.asarray(pos, jnp.int32))
        check(logits, rows, pos)


@pytest.mark.parametrize("arch", ["minicpm-2b", "qwen2-7b", "zamba2-2.7b"])
def test_decode_step_writes_one_kv_row_per_slot(arch):
    """One jitted, donated engine decode step changes the K/V stacks at
    ``(l, b, pos[b])`` for every layer l and slot b, and nowhere else:
    not at a live slot's earlier positions, not past the last position
    (a slot at ``max_seq - 1``), and a finished slot writes only its own
    row, also one finished at ``max_seq``, whose write is clamped onto
    its last row."""
    cfg = get_config(arch).reduced()
    params = init_params(cfg, jax.random.PRNGKey(10), dtype=jnp.float32)
    slots, max_seq = 5, 16
    carry = init_carry(cfg, slots, max_seq, jnp.float32)
    keys = iter(jax.random.split(jax.random.PRNGKey(11), 8))
    carry["state"] = {
        n: jax.random.normal(next(keys), a.shape, a.dtype)
        for n, a in carry["state"].items()
    }
    pos = np.array([5, max_seq - 1, 9, 0, max_seq], np.int32)
    # slot 2 has finished, slot 4 finished at max_seq
    active = np.array([True, True, False, True, False])
    carry.update(
        tokens=jnp.array([[3], [7], [11], [2], [5]], jnp.int32),
        pos=jnp.asarray(pos), active=jnp.asarray(active),
        budget=jnp.full((slots,), 100, jnp.int32),
    )
    before = {n: np.asarray(carry["state"][n]) for n in ("k", "v")}
    step = jax.jit(make_decode_step(cfg, slots, max_seq), donate_argnums=(1,))
    new, out = step(params, carry)
    assert np.asarray(out)[1].tolist() == active.astype(int).tolist()

    written = np.zeros(before["k"].shape[:3], bool)  # (L, B, S)
    written[:, np.arange(slots), np.minimum(pos, max_seq - 1)] = True
    for n in ("k", "v"):
        changed = np.any(np.asarray(new["state"][n]) != before[n], axis=(3, 4))
        np.testing.assert_array_equal(
            changed, written, err_msg=f"{arch}: {n} changed off (l, b, pos[b])"
        )
        for b in np.flatnonzero(active):
            assert not changed[:, b, : pos[b]].any(), (
                f"{arch}: slot {b}'s earlier {n} positions changed"
            )


# compiled in a child: the host device count is fixed when the CPU
# backend starts
_MESH_DECODE = """
import sys
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.dist.sharding import param_shardings, serve_carry_shardings
from repro.launch.dryrun import collective_link_bytes
from repro.launch.mesh import make_local_mesh
from repro.models.model import abstract_params
from repro.serve.engine import init_carry, make_decode_step

cfg = get_config(sys.argv[1]).reduced()
mesh, slots, max_seq = make_local_mesh(data=4), 4, 64
carry = jax.eval_shape(lambda: init_carry(cfg, slots, max_seq))
csh = serve_carry_shardings(cfg, mesh, slots, max_seq)
step = jax.jit(make_decode_step(cfg, slots, max_seq),
               in_shardings=(param_shardings(cfg, mesh), csh),
               out_shardings=(csh, None), donate_argnums=(1,))
hlo = step.lower(abstract_params(cfg, jnp.bfloat16), carry).compile().as_text()
print(collective_link_bytes(hlo, 4)["bytes"]["total"])
"""


@pytest.mark.parametrize("arch", ["minicpm-2b", "qwen2-7b", "zamba2-2.7b"])
def test_mesh_decode_moves_only_kv_rows(arch):
    """The engine's decode step on four host devices with the carry
    batch-sharded (``serve_carry_shardings``): the per-slot row writes
    move each slot's new K/V rows and position between devices, and
    nothing the size of a cache.  Bound: three times the step's K and V
    rows at 4 bytes a value (the CPU moves them in float32; 2.3 times
    when this test was written), under a tenth of the stacked caches."""
    cfg = get_config(arch).reduced()
    k = jax.eval_shape(lambda: init_decode_state(cfg, 4, 64))["k"]
    L, B, S, KH, Dh = k.shape
    rows = 2 * L * B * KH * Dh * 4
    src = str(Path(__file__).resolve().parents[1] / "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [src, *filter(None, [os.environ.get("PYTHONPATH")])]))
    out = subprocess.run([sys.executable, "-c", _MESH_DECODE, arch], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    moved = float(out.stdout.split()[-1])
    assert moved <= 3 * rows, (
        f"{arch}: decode moves {moved:.0f} link bytes a device, "
        f"over 3x the step's K/V rows ({rows} bytes)"
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
