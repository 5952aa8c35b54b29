"""Continuous-batching serve engine.

Three pieces:

* ``make_prefill_step`` / ``make_serve_step`` — the jittable units the
  dry-run cells lower (full-sequence forward; one-token decode).  With
  ``with_state=True`` the prefill step also returns the decode-state tree
  after each row's real tokens — the bulk-prefill unit.
  ``make_admit_step`` / ``make_decode_step`` are the engine's two jitted
  units over its carry (``init_carry``); a compile rehearsal lowers them
  from shapes alone.
* ``ContinuousBatchingEngine`` — fixed decode slots over a persistent
  batched decode state.  New requests are admitted into freed rows
  mid-decode by one bulk prefill forward (not ``plen`` decode steps);
  finished rows retire without stalling the batch.  Both jitted steps
  donate the carry (``jax.jit(donate_argnums=...)``) so the state is
  updated in place, and sampling runs *inside* the step (argmax /
  categorical + finished mask on device) so each step costs one small
  host transfer — three (slots,)-vectors — instead of per-request
  ``int()`` pulls.
* ``ServeEngine`` — the original batch API, now a thin wrapper that runs
  each ``generate`` call through the continuous engine.

Bitwise scheduler-equivalence: all per-slot compute (attention with
per-row positions, recurrent scans with pad masking, drop-free MoE
capacity, per-row PRNG chains) is row-independent at fixed shapes, so a
request's tokens do not depend on which slot it lands in or who its
batch companions are — admitting/evicting mid-decode reproduces isolated
generation exactly (``tests/test_serve.py``).
"""

from __future__ import annotations

import itertools
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from ..models import decode_step, init_decode_state, model_forward
from ..models.config import ModelConfig
from ..models.model import prefill_forward
from ..obs import metrics as _metrics
from ..obs import trace as _trace
from .scheduler import Request, Scheduler

_NO_EOS = -1  # sentinel: sampled ids are always >= 0, so -1 never matches

# distinguishes each engine's metrics in the process-wide registry
_ENGINE_IDS = itertools.count()

_COUNTER_NAMES = (
    "prefill_steps",
    "decode_steps",
    "slot_steps_total",
    "slot_steps_active",
    "tokens_generated",
)


def make_prefill_step(cfg: ModelConfig, remat: bool = False,
                      last_only: bool = True, with_state: bool = False,
                      state_dtype=jnp.bfloat16):
    """Full-sequence forward (the prefill_* cells).

    ``last_only`` (serving semantics) runs the LM head on the final
    position only — the (B, S, V) logits tensor at 32k × 152k vocab would
    be hundreds of GB and is never needed to start decoding.

    ``with_state`` returns ``(logits, decode_state)`` for a right-padded
    request group (batch carries ``tokens`` (B, S) and ``lengths`` (B,)):
    row i's logits are at its last real token and its state is exactly
    what token-by-token decode would hold after ``lengths[i]`` tokens —
    the engine scatters it into freed slots (bulk prefill)."""
    if with_state:

        def prefill_state_step(params, batch):
            return prefill_forward(
                cfg, params, batch["tokens"], batch["lengths"],
                state_dtype=state_dtype,
            )

        return prefill_state_step

    from ..models.layers import rms_norm
    import math as _math

    def prefill_step(params, batch):
        if not last_only:
            logits, _ = model_forward(
                cfg,
                params,
                tokens=batch.get("tokens"),
                inputs_embeds=batch.get("inputs_embeds"),
                positions=batch.get("positions"),
                remat=remat,
            )
            return logits
        # run the backbone, then head on the last position only
        from ..models import model as _m

        tokens = batch.get("tokens")
        embeds = batch.get("inputs_embeds")
        x = params["embed"][tokens] if embeds is None else embeds.astype(
            params["embed"].dtype
        )
        if cfg.embed_scale:
            x = x * jnp.asarray(_math.sqrt(cfg.d_model), dtype=x.dtype)
        b, s = x.shape[:2]
        positions = batch.get("positions")
        if positions is None:
            from ..models.layers import positions_for

            positions = positions_for(cfg, b, s)
        from ..dist.hints import constrain

        # SP on: prefill is the regime where sequence sharding pays
        # (EXPERIMENTS.md §Perf it.3)
        x = constrain(x, "dp", "model")
        if cfg.family == "hybrid":
            x = _m._hybrid_forward(cfg, params, x, positions, remat, sp=True)
        else:
            layer_fn = _m._LAYER[cfg.family]

            def body(carry, lp):
                h, acc = carry
                h, aux = layer_fn(cfg, lp, h, positions)
                h = constrain(h, "dp", "model")
                return (h, acc + aux), None

            from ..models.flags import scan_unroll

            (x, _), _ = jax.lax.scan(
                body, (x, jnp.float32(0.0)), params["layers"],
                unroll=scan_unroll(),
            )
        x = rms_norm(x[:, -1], params["final_norm"], cfg.norm_eps)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = (x @ head).astype(jnp.float32)
        if cfg.vocab_padded != cfg.vocab:
            pad_mask = jnp.arange(cfg.vocab_padded) >= cfg.vocab
            logits = jnp.where(pad_mask, -1e30, logits)
        return logits

    return prefill_step


def make_serve_step(cfg: ModelConfig):
    """One-token decode: (params, state, tokens (B,1), pos) -> (logits, state)."""

    def serve_step(params, state, tokens, pos):
        return decode_step(cfg, params, state, tokens, pos)

    return serve_step


def prefill_pad_for(cfg: ModelConfig, n: int) -> int:
    """Smallest legal prefill width >= n: the chunked SSM/WKV scans need
    the padded length divisible by their chunk (once it exceeds one)."""
    n = max(1, n)
    if cfg.family == "hybrid":
        c = cfg.ssm_chunk
        return -(-n // c) * c
    if cfg.family == "ssm":
        c = cfg.ssm_chunk or 64
        return n if n <= c else -(-n // c) * c
    return n


def _sample(logits, temps, subkeys):
    """Per-row greedy/temperature sampling. logits (B, V) f32, temps (B,),
    subkeys (B, 2) — vmapped categorical so each row consumes only its own
    key (slot-independent chains)."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.where(temps > 0.0, temps, 1.0)[:, None]
    sampled = jax.vmap(jax.random.categorical)(subkeys, scaled).astype(jnp.int32)
    return jnp.where(temps > 0.0, sampled, greedy)


def init_carry(cfg: ModelConfig, slots: int, max_seq: int,
               state_dtype=jnp.bfloat16) -> dict:
    """The engine's persistent device state: decode state plus per-slot
    position, budget, sampling and finished-mask vectors."""
    return {
        "state": init_decode_state(cfg, slots, max_seq, dtype=state_dtype),
        "tokens": jnp.zeros((slots, 1), jnp.int32),
        "pos": jnp.zeros((slots,), jnp.int32),
        "active": jnp.zeros((slots,), bool),
        "gen": jnp.zeros((slots,), jnp.int32),
        "budget": jnp.ones((slots,), jnp.int32),
        "temp": jnp.zeros((slots,), jnp.float32),
        "key": jnp.zeros((slots, 2), jnp.uint32),
        "eos": jnp.full((slots,), _NO_EOS, jnp.int32),
    }


def make_admit_step(cfg: ModelConfig, slots: int, state_dtype=jnp.bfloat16):
    """The engine's admission step: one bulk prefill of the masked rows,
    merged into the carry, plus each row's first sampled token."""
    from ..models.model import decode_state_batch_dims

    prefill = make_prefill_step(cfg, with_state=True, state_dtype=state_dtype)
    bdims = decode_state_batch_dims(cfg)

    @jax.named_scope("admit")
    def admit(params, carry, ptoks, plens, mask, budget, temps, keys, eos):
        logits, pstate = prefill(
            params, {"tokens": ptoks, "lengths": plens}
        )
        splits = jax.vmap(jax.random.split)(keys)  # (B, 2, 2)
        new_keys, subs = splits[:, 0], splits[:, 1]
        first = _sample(logits, temps, subs)
        done0 = (first == eos) | (budget <= 1)

        def merge(name, live, new):
            new = new.astype(live.dtype)
            if live.shape != new.shape:  # KV caches: seq pad < max_seq
                new = jax.lax.dynamic_update_slice(
                    live, new, (0,) * live.ndim
                )
            shape = [1] * live.ndim
            shape[bdims[name]] = slots
            return jnp.where(mask.reshape(shape), new, live)

        with jax.named_scope("kv_write"):
            state = {
                n: merge(n, carry["state"][n], pstate[n]) for n in pstate
            }
        return {
            "state": state,
            "tokens": jnp.where(mask, first, carry["tokens"][:, 0])[:, None],
            "pos": jnp.where(mask, plens, carry["pos"]),
            "active": jnp.where(mask, ~done0, carry["active"]),
            "gen": jnp.where(mask, 1, carry["gen"]),
            "budget": jnp.where(mask, budget, carry["budget"]),
            "temp": jnp.where(mask, temps, carry["temp"]),
            "key": jnp.where(mask[:, None], new_keys, carry["key"]),
            "eos": jnp.where(mask, eos, carry["eos"]),
        }, jnp.stack([first, done0.astype(jnp.int32)])  # one host pull

    return admit


def make_decode_step(cfg: ModelConfig, slots: int, max_seq: int):
    """The engine's decode step: ``(params, carry) -> (carry, (3, slots))``
    — one token for every live row, sampled on device."""
    moe_cap = slots * cfg.moe_top_k if cfg.family == "moe" else None

    @jax.named_scope("decode")
    def decode(params, carry):
        logits, state = decode_step(
            cfg, params, carry["state"], carry["tokens"], carry["pos"],
            moe_cap=moe_cap,
        )
        splits = jax.vmap(jax.random.split)(carry["key"])
        new_keys, subs = splits[:, 0], splits[:, 1]
        tok = _sample(logits, carry["temp"], subs)
        was = carry["active"]
        gen = carry["gen"] + was
        pos = carry["pos"] + was
        done = was & (
            (tok == carry["eos"]) | (gen >= carry["budget"]) | (pos >= max_seq)
        )
        # the step's single host transfer: (3, B) int32
        out = jnp.stack(
            [tok, was.astype(jnp.int32), done.astype(jnp.int32)]
        )
        return {
            "state": state,
            "tokens": tok[:, None],
            "pos": pos,
            "active": was & ~done,
            "gen": gen,
            "budget": carry["budget"],
            "temp": carry["temp"],
            "key": new_keys,
            "eos": carry["eos"],
        }, out

    return decode


class ContinuousBatchingEngine:
    """Request-level continuous batching over a fixed slot batch.

    ``submit`` enqueues (bounded queue — raises ``QueueFull``); ``step``
    runs one engine step: an admission bulk-prefill if slots are free and
    requests are queued, then one batched decode step for every live row.
    ``run`` drains to idle.  See module docstring for the device/host
    split.
    """

    def __init__(self, cfg: ModelConfig, params, slots: int = 4,
                 max_seq: int = 512, prefill_pad: int = 64,
                 max_queue: int = 256, min_admit: int = 1,
                 state_dtype=jnp.bfloat16, mesh=None,
                 clock=time.perf_counter):
        self.cfg = cfg
        self.params = params
        self.slots = slots
        self.max_seq = max_seq
        self.prefill_pad = prefill_pad_for(cfg, prefill_pad)
        self.state_dtype = state_dtype
        self.clock = clock
        self.sched = Scheduler(slots, max_queue=max_queue, min_admit=min_admit)
        self._rid = itertools.count()
        self._key_cache: dict[int, np.ndarray] = {}
        # counters/latency histograms live in the obs.metrics registry
        # under a per-engine scope; serve_stats() is a view over them,
        # and metrics.reset() clears them via the registered hook
        scope = f"serve.e{next(_ENGINE_IDS)}."
        self._ttft = _metrics.histogram(scope + "ttft_s")
        self._itl = _metrics.histogram(scope + "itl_s")
        self._counters = {
            name: _metrics.counter(scope + name) for name in _COUNTER_NAMES
        }
        _metrics.on_reset(self.reset_stats)

        self._carry = init_carry(cfg, slots, max_seq, state_dtype)
        if mesh is not None:
            from ..dist.sharding import serve_carry_shardings

            self._carry = jax.device_put(
                self._carry,
                serve_carry_shardings(cfg, mesh, slots, max_seq),
            )

        self._admit_fn = jax.jit(
            make_admit_step(cfg, slots, state_dtype), donate_argnums=(1,)
        )
        self._decode_fn = jax.jit(
            make_decode_step(cfg, slots, max_seq), donate_argnums=(1,)
        )

    # -- host control loop -------------------------------------------------

    def submit(self, prompt, max_new: int = 16, temperature: float = 0.0,
               seed: int = 0, eos_id: int | None = None,
               arrival_t: float | None = None) -> Request:
        """Enqueue a request.  Raises ``QueueFull`` when the admission
        queue is at capacity (backpressure) and ``ValueError`` for
        requests that cannot fit the engine geometry."""
        prompt = list(prompt)
        if not prompt:
            raise ValueError("empty prompt")
        if len(prompt) > self.prefill_pad:
            raise ValueError(
                f"prompt length {len(prompt)} exceeds prefill_pad "
                f"{self.prefill_pad}"
            )
        if len(prompt) + max_new > self.max_seq:
            raise ValueError(
                f"prompt {len(prompt)} + max_new {max_new} exceeds "
                f"max_seq {self.max_seq}"
            )
        req = Request(
            rid=next(self._rid), prompt=prompt, max_new=max_new,
            temperature=temperature, seed=seed, eos_id=eos_id,
            arrival_t=self.clock() if arrival_t is None else arrival_t,
        )
        self.sched.submit(req)  # may raise QueueFull
        return req

    def _do_admit(self, plan, finished):
        B, P = self.slots, self.prefill_pad
        ptoks = np.zeros((B, P), np.int32)
        plens = np.ones((B,), np.int32)
        mask = np.zeros((B,), bool)
        budget = np.ones((B,), np.int32)
        temps = np.zeros((B,), np.float32)
        keys = np.zeros((B, 2), np.uint32)
        eos = np.full((B,), _NO_EOS, np.int32)
        for s, req in plan:
            ptoks[s, : len(req.prompt)] = req.prompt
            plens[s] = len(req.prompt)
            mask[s] = True
            budget[s] = req.max_new
            temps[s] = req.temperature
            keys[s] = self._seed_key(req.seed)
            eos[s] = _NO_EOS if req.eos_id is None else req.eos_id
        t0 = self.clock()
        with _trace.span("serve.prefill", rows=len(plan), pad=P,
                         rids=[req.rid for _, req in plan]):
            self._carry, packed = self._admit_fn(
                self.params, self._carry, ptoks, plens, mask, budget, temps,
                keys, eos,
            )
            with _trace.span("serve.prefill.wait"):
                packed = np.asarray(packed)  # one sync
        first, done0 = packed[0], packed[1].astype(bool)
        t1 = self.clock()
        self._counters["prefill_steps"].inc()
        for s, req in plan:
            self.sched.admit(s, req)
            req.admit_t = t0
            req.first_token_t = t1
            req.tokens.append(int(first[s]))
            req.token_t.append(t1)
            self._counters["tokens_generated"].inc()
            self._ttft.observe(t1 - req.arrival_t)
            # TTFT = serve.queue + serve.prefill
            _trace.complete("serve.queue", req.arrival_t, t0 - req.arrival_t,
                            rid=req.rid)
            if done0[s]:
                req.finish_t = t1
                finished.append(self.sched.retire(s))

    def _seed_key(self, seed: int) -> np.ndarray:
        """Host-cached PRNG key material (avoids a device call per submit)."""
        k = self._key_cache.get(seed)
        if k is None:
            k = np.asarray(jax.random.PRNGKey(seed), np.uint32)
            self._key_cache[seed] = k
        return k

    def _do_decode(self, finished):
        with _trace.span("serve.decode", slots=self.slots) as sp:
            self._carry, packed = self._decode_fn(self.params, self._carry)
            with _trace.span("serve.decode.wait"):
                packed = np.asarray(packed)  # one sync
            tok, was, done = (packed[0], packed[1].astype(bool),
                              packed[2].astype(bool))
            sp.set(active=int(was.sum()))
        t1 = self.clock()
        n_active = 0
        for s in range(self.slots):
            if not was[s]:
                continue
            n_active += 1
            req = self.sched.slots[s]
            req.tokens.append(int(tok[s]))
            self._itl.observe(t1 - req.token_t[-1])
            req.token_t.append(t1)
            self._counters["tokens_generated"].inc()
            if done[s]:
                req.finish_t = t1
                finished.append(self.sched.retire(s))
        self._counters["decode_steps"].inc()
        self._counters["slot_steps_total"].inc(self.slots)
        self._counters["slot_steps_active"].inc(n_active)

    def step(self) -> list[Request]:
        """One engine step: admission prefill (if warranted) then one
        batched decode step.  Returns requests that finished.

        Traced (``repro.obs``), ``serve.step`` spans the whole step; its
        time outside ``serve.prefill.wait`` and ``serve.decode.wait`` is
        the host's own (planning, packing, token bookkeeping)."""
        finished: list[Request] = []
        with _trace.span("serve.step"):
            with _trace.span("serve.plan") as sp:
                plan = self.sched.plan_admissions()
                sp.set(rows=len(plan), queued=len(self.sched.queue))
            if plan:
                self._do_admit(plan, finished)
            if self.sched.active_slots():
                self._do_decode(finished)
        return finished

    def run(self) -> list[Request]:
        """Drain queue and slots to idle; returns all finished requests."""
        out: list[Request] = []
        while not self.sched.idle:
            out.extend(self.step())
        return out

    def reset_stats(self) -> None:
        """Zero this engine's counters and latency histograms (e.g.
        after a warm-up request has triggered compilation); live slots
        are untouched.  Also runs as an ``obs.metrics.reset()`` hook so
        one registry-wide reset clears engine state too."""
        self._ttft.reset()
        self._itl.reset()
        for c in self._counters.values():
            c.reset()
        for k in self.sched.counters:
            self.sched.counters[k] = 0

    def serve_stats(self) -> dict:
        """Counters + latency summaries for the run so far — a view
        over this engine's scope in the ``repro.obs.metrics`` registry
        (plus the scheduler's admission counters)."""
        stats = dict(self.sched.counters)
        stats.update({k: c.value for k, c in self._counters.items()})
        total = max(1, stats["slot_steps_total"])
        stats["padded_slot_waste"] = 1.0 - stats["slot_steps_active"] / total
        for name, h in (("ttft", self._ttft), ("itl", self._itl)):
            xs = h.samples()
            if xs:
                stats[f"{name}_p50_ms"] = float(np.percentile(xs, 50) * 1e3)
                stats[f"{name}_p95_ms"] = float(np.percentile(xs, 95) * 1e3)
                stats[f"{name}_mean_ms"] = float(np.mean(xs) * 1e3)
        return stats


@dataclass
class ServeEngine:
    """Batch generation API (back-compat): each ``generate`` call runs its
    prompts through a ``ContinuousBatchingEngine`` sized to the batch —
    prefill is one bulk forward per batch, never token-by-token decode.
    """

    cfg: ModelConfig
    params: dict
    max_seq: int = 512
    _engines: dict = field(default_factory=dict, repr=False)

    def generate(self, prompts: list[list[int]], max_new: int = 16,
                 temperature: float = 0.0, seed: int = 0) -> list[list[int]]:
        b = len(prompts)
        pad = prefill_pad_for(self.cfg, max(len(p) for p in prompts))
        eng = self._engines.get((b, pad))
        if eng is None:
            eng = ContinuousBatchingEngine(
                self.cfg, self.params, slots=b, max_seq=self.max_seq,
                prefill_pad=pad,
            )
            self._engines[(b, pad)] = eng
        reqs = [
            eng.submit(p, max_new=max_new, temperature=temperature,
                       seed=seed + i)
            for i, p in enumerate(prompts)
        ]
        eng.run()
        return [list(p) + r.tokens for p, r in zip(prompts, reqs)]
