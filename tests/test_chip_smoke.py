"""``chip_smoke.py`` off the chip: it refuses any backend but a TPU, and
its phases pass here at reduced widths (the same code the chip runs)."""

import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

import chip_smoke
from repro.configs import get_config
from repro.obs import trace

ROOT = Path(chip_smoke.__file__).resolve().parent


@pytest.fixture
def rep():
    was = trace.enabled
    r = chip_smoke.Report("cpu")
    r.listen()
    yield r
    if not was:
        trace.disable_trace()
    trace.reset_trace()


def _tiny():
    return get_config(chip_smoke.ARCH).reduced()


def test_refuses_cpu_without_ok_line(capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_serve_phase_tiny(rep, capsys):
    stats = chip_smoke.serve_phase(rep, _tiny(), seed=0, slots=4, max_seq=64,
                                   prefill_pad=32, new_range=(10, 16))
    assert stats["prefill_steps"] >= 2 and stats["decode_steps"] >= 16
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    steps = {x["step"]: x for x in lines if "step" in x}
    assert set(steps) == {"admit", "decode"}
    assert all(steps[k]["compile_s"] > 0 for k in steps)
    assert all(x["device_kind"] == "cpu" for x in lines)


def test_compile_seconds_join_every_stage_of_one_function(rep):
    trace.reset_trace()
    for name in chip_smoke._COMPILE_SPANS:
        trace.complete(name, 0.0, 1.0, fun_name="jit(admit)")
    trace.complete(chip_smoke._COMPILE_SPANS[0], 0.0, 1.0, fun_name="admit")
    trace.complete("jax.compile", 0.0, 1.0, fun_name="jit(decode)", cache="hit")
    rep.collect()
    assert rep.compile_s["admit"] == 4.0
    assert rep.cache_events == {"cache_hits": 1}


def test_train_phase_tiny(rep):
    cfg = dataclasses.replace(_tiny(), n_layers=2)
    losses = chip_smoke.train_phase(rep, cfg, seed=0, batch=2, seq=16, steps=3)
    assert len(losses) == 3


def test_four_device_phases_on_host_devices():
    """The --chips 4 phases on four forced host devices, in a child: the
    device count is fixed when the CPU backend starts."""
    code = (
        "import dataclasses, chip_smoke as c\n"
        "rep = c.Report('cpu')\n"
        "c.bridge_phase(rep, 0, 4, 64)\n"
        "cfg = dataclasses.replace(c.get_config(c.ARCH).reduced(), n_layers=2)\n"
        "c.dp_train_phase(rep, cfg, 0, 4, 16, 3, 4)\n"
        "print('FOUR_DEVICE_OK')\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "FOUR_DEVICE_OK" in out.stdout
