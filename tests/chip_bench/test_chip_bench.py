"""CPU tests of the chip benchmark's harness, yardstick and checks.

Run with ``PYTHONPATH=src python -m pytest tests/chip_bench -q``.
Cells run here at toy sizes on the CPU (``require_chip=False``); no
number from these runs is a device metric.
"""

from __future__ import annotations

import hashlib
import json
import shutil
import statistics
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from benchmarks.chip import counts, generate, harness, readers, stats, tracereduce, weights  # noqa: E402
from benchmarks.chip.references import dense_decoder  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
CHIP = ROOT / "benchmarks" / "chip"

TINY = {"name": "tiny", "family": "dense", "n_layers": 2, "d_model": 64, "n_heads": 4,
        "n_kv_heads": 2, "head_dim": 16, "d_ff": 128, "vocab": 250,
        "activation": "silu_glu", "qkv_bias": True, "rope_theta": 10000.0,
        "norm_eps": 1e-6, "tie_embeddings": False, "dtype": "bfloat16",
        "reference": "dense_decoder", "check": {"logit_gap_max": 0.05}}
TINY_TRAIN = dict(TINY, name="tinytrain", dtype="float32", tie_embeddings=True,
                  qkv_bias=False, wsd_schedule=True,
                  check={"loss_rel": 3e-5, "grad_norm_gap": 3e-5, "update_norm_gap": 0.01})
MIX = {"driver": "serve_open", "engine": {"slots": 2, "prefill_pad": 32, "max_seq": 48},
       "rate_per_s": 4, "prompt": {"median": 16, "sigma": 0.6, "min": 4, "max": 32},
       "output": {"median": 6, "sigma": 0.8, "min": 2, "max": 16}, "drain_cap_s": 30,
       "check_tokens": 40, "check_requests": 8}
TRAIN_MIX = dict(json.loads((CHIP / "traffic" / "train.json").read_text()), batch=2, seq=16)


def make_root(tmp: Path) -> Path:
    """A checkout-shaped directory with the toy cells and the real
    metric readers."""
    (tmp / "benchmarks" / "chip" / "traffic").mkdir(parents=True)
    (tmp / "benchmarks" / "chip" / "configs").mkdir()
    shutil.copytree(CHIP / "metrics", tmp / "benchmarks" / "chip" / "metrics")
    for name, obj in (("configs/tiny.json", TINY), ("configs/tinytrain.json", TINY_TRAIN),
                      ("traffic/mix.json", MIX), ("traffic/steps.json", TRAIN_MIX)):
        (tmp / "benchmarks" / "chip" / name).write_text(json.dumps(obj))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": n, "file": f"benchmarks/chip/configs/{n}.json"}
                        for n in ("tiny", "tinytrain")]
    bench["workloads"] = [
        {"name": "tiny.mix", "config": "tiny", "traffic": "mix", "chips": 1},
        {"name": "tinytrain.steps", "config": "tinytrain", "traffic": "steps", "chips": 1}]
    cells = {"minicpm-2b.chat": "tiny.mix",
             "minicpm-2b-l4.train": "tinytrain.steps"}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = sorted({cells[w] for w in m["workloads"]})
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


@pytest.fixture
def root(tmp_path):
    return make_root(tmp_path)


def run(root, cell, seed=7, seconds=2.0, trace=False):
    return harness.run_cell(root, cell, seed, seconds, trace, time.perf_counter(),
                            require_chip=False)


# -- traffic ----------------------------------------------------------------


def test_serve_requests_exact_count_clipped_and_seeded():
    a = generate.serve_requests(MIX, 5, 10.0, 250)
    assert len(a) == generate.request_count(MIX, 10.0) == 40
    dues = [r["due"] for r in a]
    assert dues == sorted(dues) and 0 < dues[0] and dues[-1] < 10.0
    for r in a:
        assert MIX["prompt"]["min"] <= len(r["prompt"]) <= MIX["prompt"]["max"]
        assert MIX["output"]["min"] <= r["max_new"] <= MIX["output"]["max"]
        assert all(0 <= t < 250 for t in r["prompt"])
    assert a == generate.serve_requests(MIX, 5, 10.0, 250)
    b = generate.serve_requests(MIX, 2**31 + 11, 10.0, 250)
    # every seed offers the same lengths, at other times and in another order
    assert [r["due"] for r in b] != dues
    assert sorted(len(r["prompt"]) for r in a) == sorted(len(r["prompt"]) for r in b)
    assert sorted(r["max_new"] for r in a) == sorted(r["max_new"] for r in b)
    assert [r["max_new"] for r in a] != [r["max_new"] for r in b]


def test_arrivals_are_sorted_uniform_times_drawn_from_the_seed():
    """Over many seeds the arrival times are uniform on the window and
    the gaps exponential (a Poisson stream given its count)."""
    dues = np.array([[r["due"] for r in generate.serve_requests(MIX, 2**32 + s, 10.0, 250)]
                     for s in range(200)])
    assert np.histogram(dues, bins=5, range=(0, 10))[0] == pytest.approx([1600] * 5, rel=0.08)
    gaps = np.diff(dues, axis=1).ravel()
    # an exponential's coefficient of variation is 1
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, abs=0.06)


def test_quantile_lengths_follow_the_lognormal():
    x = generate._quantile_lengths({"median": 100, "sigma": 0.5, "min": 1, "max": 10**6}, 101)
    assert x[50] == 100 and x.min() >= 1
    assert abs(np.median(np.log(x)) - np.log(100)) < 1e-9


def test_train_batches_differ_by_step_and_repeat_by_seed():
    a0 = generate.train_batch(TRAIN_MIX, 3, 0, 250)
    a1 = generate.train_batch(TRAIN_MIX, 3, 1, 250)
    assert a0["tokens"].shape == (2, 16)
    assert np.array_equal(a0["tokens"][:, 1:], a0["labels"][:, :-1])
    assert not np.array_equal(a0["tokens"], a1["tokens"])
    assert np.array_equal(a0["tokens"], generate.train_batch(TRAIN_MIX, 3, 0, 250)["tokens"])
    rows = a0["tokens"]
    assert len({r.tobytes() for r in rows}) == len(rows)


# -- arithmetic -------------------------------------------------------------


@pytest.mark.parametrize("q", [50, 90, 95, 99])
def test_percentile_over_all_values_matches_numpy(q):
    x = np.random.default_rng(q).lognormal(size=997)
    assert stats.percentile(x.tolist(), q) == pytest.approx(np.percentile(x, q), rel=1e-12)
    # not a median of chunk percentiles
    chunks = np.median([np.percentile(c, q) for c in np.array_split(x, 7)])
    assert stats.percentile(x.tolist(), q) != pytest.approx(chunks, rel=1e-6)


def test_spread_and_rate():
    v = [10.0, 11.0, 12.0, 13.0, 14.0, 100.0]
    q1, med, q3 = statistics.quantiles(v, n=4)
    assert stats.spread(v) == (q3 - q1) / med
    assert stats.rate(30, 10.0) == 3.0
    with pytest.raises(ValueError):
        stats.rate(1, 0)


def test_flop_and_byte_counts_by_hand_for_one_layer():
    m = dict(TINY, n_layers=1, vocab=10)
    # q 64x64, k and v 64x32, o 64x64, gate/up 64x128, down 128x64
    assert counts.layer_matmul_params(m) == 4096 + 2 * 2048 + 4096 + 3 * 8192
    per_tok = 2 * (4096 + 4096 + 4096 + 24576)
    pair = 4 * 4 * 16
    assert counts.decode_flops(m, 5) == per_tok + pair * 5 + 2 * 64 * 10
    assert counts.kv_bytes(m, 5) == 2 * 5 * 2 * 16 * 2
    assert counts.weight_bytes(m) == (36864 + 640) * 2
    t, bound = counts.decode_step_bound_s(m, [5], {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9})
    assert bound == "bytes" and t == pytest.approx((75008 + 640) / 1e9)
    assert counts.train_flops_per_token(m, 4) == pytest.approx(
        3 * (per_tok + pair * 10 / 4 + 2 * 640))


def test_peaks_table_refuses_unknown_devices():
    from benchmarks.chip.peaks import peaks_for

    assert peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks_for("cpu")


# -- trace reduction --------------------------------------------------------


def test_trace_reduction_on_a_recorded_trace():
    """A slice of a real v5e trace of the chat cell (one chip's XLA Ops
    and the harness's host spans), reduced, against the same quantities
    counted by brute force on a 1 us grid."""
    rec = json.loads((Path(__file__).parent / "recorded_trace.json").read_text())
    device = {int(k): [tuple(e) for e in v] for k, v in rec["device"].items()}
    host = [tuple(e) for e in rec["host"]]
    out = tracereduce.reduce(device, host)
    (w0, w1), = [(s, s + d) for n, s, d in host if n == "bench.window"]
    us = 1000
    grid = np.zeros((w1 - w0) // us + 1, bool)
    for _, s, d in device[0]:
        if s < w1 and s + d > w0:  # every operation marks at least its first us
            a = (max(s, w0) - w0) // us
            grid[a: max((min(s + d, w1) - w0) // us, a + 1)] = True
    n = len(device[0])
    assert out["window_s"] == pytest.approx((w1 - w0) / 1e9)
    assert out["busy_s"] == pytest.approx(grid.sum() * 1e-6, abs=2 * n * 1e-6)
    per_op = {}
    for name, s, d in device[0]:
        short = tracereduce.op_name(name)
        if not short.startswith(("while", "conditional", "call")):
            per_op[short] = per_op.get(short, 0) + min(s + d, w1) - max(s, w0)
    top = max(per_op, key=per_op.get)
    assert out["device_ops"][0] == [top, pytest.approx(per_op[top] / 1e9)]
    assert len(out["device_ops"]) == min(10, len(per_op))
    # the longest idle stretch on the grid is the first gap
    idle, run_len, best = ~grid, 0, 0
    for v in idle:
        run_len = run_len + 1 if v else 0
        best = max(best, run_len)
    assert out["idle_gaps"][0][1] == pytest.approx(best * 1e-6, abs=3e-6)
    assert {g[0] for g in out["idle_gaps"]} <= {h[0] for h in host} | {"host"}


def test_trace_reduction_busy_union_and_named_gaps():
    ms = 1_000_000
    host = [("bench.window", 0, 100 * ms), ("bench.engine_step", 0, 60 * ms),
            ("bench.wait_arrival", 60 * ms, 40 * ms)]
    device = {0: [("fusion.1", 5 * ms, 20 * ms), ("fusion.2", 10 * ms, 30 * ms),
                  ("dot.3", 50 * ms, 5 * ms), ("dot.3", 90 * ms, 20 * ms)],
              1: [("fusion.1", 0, 50 * ms)]}
    out = tracereduce.reduce(device, host)
    assert out["window_s"] == pytest.approx(0.1)
    assert out["busy_s"] == pytest.approx((35 + 5 + 10 + 50) / 2 / 1e3)
    assert out["device_ops"][0] == ["fusion.1", pytest.approx(0.035)]
    assert out["idle_gaps"][0] == ["bench.wait_arrival", pytest.approx(0.035)]
    assert out["idle_gaps"][1] == ["bench.engine_step", pytest.approx(0.010)]
    with pytest.raises(ValueError):
        tracereduce.reduce({}, host)


# -- weights and references -------------------------------------------------


def test_layer_weights_equal_the_stacked_tree():
    p = weights.make_params(TINY, 2**33 + 5)
    for layer in range(TINY["n_layers"]):
        w = weights.layer_weights(TINY, 2**33 + 5, layer)
        assert np.array_equal(np.asarray(p["layers"]["ffn"]["w_up"][layer]),
                              np.asarray(w["ffn/w_up"]))
        assert np.array_equal(np.asarray(p["layers"]["attn"]["bq"][layer]),
                              np.asarray(w["attn/bq"]))
    assert p["embed"].shape == (256, 64) and p["embed"].dtype == jnp.bfloat16
    assert p["layers"]["ln1"].dtype == jnp.float32
    other = weights.make_params(TINY, 5)
    assert not np.array_equal(np.asarray(p["embed"]), np.asarray(other["embed"]))


def test_program_forward_matches_the_reference_in_float32():
    from repro.models.model import prefill_forward

    from benchmarks.chip.drivers.serve_open import model_config

    cfg = model_config(TINY)
    params = jax.tree.map(lambda x: x.astype(jnp.float32), weights.make_params(TINY, 3))
    toks = np.random.default_rng(0).integers(0, 250, (1, 12)).astype(np.int32)
    logits, _ = prefill_forward(cfg, params, jnp.asarray(toks), jnp.asarray([12]))
    mk, top, rows = dense_decoder.final_rows(TINY, 3, [toks[0].tolist()], [(11, 12)], 16)
    ref = dense_decoder._logits(mk, top, rows)
    np.testing.assert_allclose(np.asarray(logits)[0, :250], np.asarray(ref)[0], atol=2e-4)


# -- cells end to end, and the checks that must fail ------------------------


def test_serve_cell_runs_and_is_correct(root):
    out = run(root, "tiny.mix")
    assert out["correct"] and out["failed"] == 0 and out["attempted"] == 8
    assert set(out["metrics"]) == {"itl_p95_ms", "setup_s"}
    assert list(out)[-1] == "checks"
    assert out["checks"]["logit_gap_max"]["value"] <= TINY["check"]["logit_gap_max"]
    line = harness.result_line(out)
    assert json.loads(line) == out


def test_serve_per_layer_metrics_with_trace(root):
    out = run(root, "tiny.mix", trace=True)
    names = set(out["metrics"])
    assert "decode_step_ms.chat" in names
    # no chip: nothing traced, so no share of a peak and no idle share
    assert not names & {"decode_mfu.chat", "device_idle_share.chat"}


def test_serve_control_fails_the_limit():
    """The float8 reference in the program's place reads a gap far above
    what the program reads, and above the limit."""
    from benchmarks.chip.drivers import serve_open as so
    import contextlib

    server = so.Server(TINY, MIX, 11)
    w = so.window(lambda n: contextlib.nullcontext(), server,
                  generate.serve_requests(MIX, 11, 3.0, 250), 3.0)
    server.free()
    chosen = so.sample(w, 11, 40, 8)
    prog = so.compare(TINY, 11, chosen, 48).max()
    ctrl = so.compare(TINY, 11, chosen, 48, control=True).max()
    assert prog <= TINY["check"]["logit_gap_max"] < ctrl
    assert ctrl >= 3 * max(prog, 1e-3)


def test_served_token_altered_fails(root, monkeypatch):
    from repro.serve import engine

    real = engine.make_decode_step

    def altered(cfg, slots, max_seq):
        step = real(cfg, slots, max_seq)

        def decode(params, carry):
            carry, out = step(params, carry)
            return carry, out.at[0].set((out[0] + 1) % cfg.vocab)
        return decode

    monkeypatch.setattr(engine, "make_decode_step", altered)
    out = run(root, "tiny.mix")
    assert not out["correct"]
    assert out["checks"]["logit_gap_max"]["value"] > TINY["check"]["logit_gap_max"]


def test_train_cell_runs_and_is_correct(root):
    out = run(root, "tinytrain.steps")
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}


def test_train_step_that_leaves_the_state_unchanged_fails(root, monkeypatch):
    from benchmarks.chip.drivers import train_steps

    real = train_steps.Trainer.step

    def unchanged(self):
        keep = jax.tree.map(jnp.copy, (self.params, self.opt_state))
        loss = real(self)
        self.params, self.opt_state = keep
        return loss

    monkeypatch.setattr(train_steps.Trainer, "step", unchanged)
    out = run(root, "tinytrain.steps")
    assert not out["correct"]
    assert out["checks"]["update_norm_gap"]["value"] >= 0.99


def test_train_step_on_half_the_batch_fails(root, monkeypatch):
    from benchmarks.chip.drivers import train_steps

    real = train_steps.Trainer.__init__

    def half(self, config, mix, seed):
        real(self, config, mix, seed, half_batch=True)

    monkeypatch.setattr(train_steps.Trainer, "__init__", half)
    out = run(root, "tinytrain.steps")
    assert not out["correct"]
    assert out["checks"]["loss_rel"]["value"] > TINY_TRAIN["check"]["loss_rel"]


def test_train_control_fails_a_limit():
    from benchmarks.chip.drivers import train_steps as ts

    tr = ts.Trainer(TINY_TRAIN, TRAIN_MIX, 13)
    tr.free()
    prog, ctrl = ts.compare(tr), ts.compare(tr, control=True)
    lim = TINY_TRAIN["check"]
    assert all(prog[k] <= lim[k] for k in lim)
    assert any(ctrl[k] > lim[k] for k in lim)


# -- driven by data -----------------------------------------------------------


def _digest(path: Path) -> dict:
    return {p.relative_to(path).as_posix(): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(path.rglob("*")) if p.is_file() and "__pycache__" not in p.parts}


def test_new_config_traffic_and_metric_are_found_by_name(root):
    """A cell is added by new files and a workloads entry: nothing that
    was there before changes."""
    before = _digest(CHIP)
    chip = root / "benchmarks" / "chip"
    (chip / "configs" / "tiny-l1.json").write_text(json.dumps(dict(TINY, n_layers=1)))
    (chip / "traffic" / "burst.json").write_text(json.dumps(dict(MIX, rate_per_s=6)))
    (chip / "metrics" / "steps_per_request.burst.py").write_text(
        "def read(run):\n    s = run.data['summary']\n    return s['steps'] / s['attempted']\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-l1", "file": "benchmarks/chip/configs/tiny-l1.json"})
    bench["workloads"].append({"name": "tiny-l1.burst", "config": "tiny-l1",
                               "traffic": "burst", "chips": 1})
    bench["per_layer"].append({"name": "steps_per_request.burst", "unit": "1",
                               "moves": "itl_p95_ms", "workloads": ["tiny-l1.burst"]})
    for m in bench["end_to_end"]:
        if m["name"] == "itl_p95_ms":
            m["workloads"].append("tiny-l1.burst")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    out = run(root, "tiny-l1.burst", trace=True)
    assert out["attempted"] == 12 and out["correct"]
    assert out["metrics"]["steps_per_request.burst"]["value"] > 1
    assert _digest(CHIP) == before


def test_no_chip_exits_nonzero_without_a_result(tmp_path):
    """On the CPU the command refuses to run: exit 3, nothing on stdout."""
    r = subprocess.run([sys.executable, str(CHIP / "run.py"), "--workload",
                        "minicpm-2b.chat", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], capture_output=True, text=True, timeout=300,
                       env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
                            "HOME": str(tmp_path)})
    assert r.returncode == 3 and r.stdout == ""


def test_readers_find_nothing_without_data():
    empty = harness.Run(correct=True, attempted=0, failed=0, e2e={}, checks={},
                        memory_peak_bytes=0, data={})
    for f in (readers.idle_share, readers.decode_roofline, readers.train_mfu):
        assert f(empty) is None
