"""The per-layer readers of the program's own spans: the engine's host
share, Python GC time and compilations in the chat cell's traced run.

Run with ``PYTHONPATH=src python -m pytest tests/chip_bench -q``.  The
toy cell runs on the CPU; no number from it is a device metric.
"""

from __future__ import annotations

from pathlib import Path

import pytest

pytest.importorskip("jax")

from benchmarks.chip import harness  # noqa: E402
from test_chip_bench import make_root, run  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
NAMES = ("host_share.chat", "gc_ms.chat", "compiles.chat")

# two engine steps: an admit and a decode, then a decode alone
SPANS = [
    ("serve.step", 0.000, 0.100),
    ("serve.plan", 0.000, 0.001),
    ("serve.prefill", 0.001, 0.060),
    ("serve.prefill.wait", 0.010, 0.050),
    ("serve.decode", 0.062, 0.036),
    ("serve.decode.wait", 0.063, 0.034),
    ("py.gc", 0.100, 0.002),
    ("serve.step", 0.200, 0.050),
    ("serve.plan", 0.200, 0.001),
    ("serve.decode", 0.201, 0.045),
    ("serve.decode.wait", 0.202, 0.040),
    ("py.gc", 0.300, 0.0125),
    ("jax.trace", 0.400, 0.1),
    ("jax.lower", 0.500, 0.1),
    ("jax.compile", 0.600, 0.5),
]


def _run(spans) -> harness.Run:
    return harness.Run(correct=True, attempted=0, failed=0, e2e={}, checks={},
                       memory_peak_bytes=0, data={"spans": spans})


def _read(name: str, r: harness.Run):
    return harness.metric_reader(ROOT, name)(r)


def test_readers_on_hand_built_spans():
    r = _run(SPANS)
    # steps 0.150 s; waits 0.050 + 0.034 + 0.040 = 0.124 s; host 0.026 s
    assert _read("host_share.chat", r) == pytest.approx(100 * 0.026 / 0.150)
    assert _read("gc_ms.chat", r) == pytest.approx(2.0 + 12.5)
    # tracing and lowering are not compilations
    assert _read("compiles.chat", r) == 1


@pytest.mark.parametrize("name", NAMES)
def test_readers_find_nothing_without_engine_spans(name):
    assert _read(name, _run([])) is None
    assert _read(name, harness.Run(correct=True, attempted=0, failed=0, e2e={},
                                   checks={}, memory_peak_bytes=0, data={})) is None
    # a program that records only its prefill and decode steps
    older = [("serve.prefill", 0.0, 0.06), ("serve.decode", 0.06, 0.04)]
    assert _read(name, _run(older)) is None


def test_tiny_serve_cell_reports_the_program_span_metrics(tmp_path):
    out = run(make_root(tmp_path), "tiny.mix", trace=True)
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert {"decode_step_ms.chat", *NAMES} <= set(m)
    assert 0.0 < m["host_share.chat"] < 100.0
    assert m["gc_ms.chat"] >= 0.0
    assert m["compiles.chat"] == 0 and isinstance(m["compiles.chat"], int)
    assert out["metrics"]["compiles.chat"]["unit"] == "count"
