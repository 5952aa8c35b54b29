"""Compile the main path for a described TPU v5e chip, with no chip.

The TPU compiler refuses what interpret mode accepts: a block shape off
the (8, 128) tiling, too much VMEM, a program larger than HBM.  These
tests compile the Pallas kernels at real widths and the serve engine's
two jitted steps for minicpm-2b at full size, so such a fault fails here
instead of on the chip.

Only one process at a time may load the TPU library, and it keeps it
until it exits; so the topology is described in a fixture of this one
file (never while a module is imported), and every compile runs in the
test's own process.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import attention, rmsnorm_op, ssd, triad
from repro.models.model import abstract_params
from repro.serve.engine import init_carry, make_admit_step, make_decode_step

HBM_BYTES = 16 * 10**9  # one v5e chip
SLOTS, MAX_SEQ, PREFILL_PAD = 4, 2048, 512  # chip_smoke.py's serve geometry


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep the cache off
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding), tree
    )


def _kernel_args(name):
    """Shapes at the widths the configurations use (minicpm-2b attention
    and norm, zamba2's SSD heads)."""
    S = jax.ShapeDtypeStruct
    bf16, f32 = jnp.bfloat16, jnp.float32
    if name == "flash_attention":  # 36 heads x 2048 x 64
        qkv = S((1, 2048, 36, 64), bf16)
        return (lambda q, k, v: attention(q, k, v)), (qkv, qkv, qkv)
    if name == "rmsnorm":
        return (lambda x, w: rmsnorm_op(x, w)), (
            S((4096, 2304), bf16), S((2304,), f32))
    if name == "stream_triad":
        return (lambda b, c: triad(b, c, s=3.0)), (
            S((1 << 24,), f32), S((1 << 24,), f32))
    if name == "ssd_scan":  # zamba2: 80 heads, P=64, N=64, chunk 64
        b, s, h, p, n = 1, 2048, 80, 64, 64
        return (lambda x, dt, a, bm, cm: ssd(x, dt, a, bm, cm, chunk=64)), (
            S((b, s, h, p), f32), S((b, s, h), f32), S((h,), f32),
            S((b, s, n), f32), S((b, s, n), f32))
    raise KeyError(name)


@pytest.mark.parametrize(
    "name", ["flash_attention", "rmsnorm", "stream_triad", "ssd_scan"]
)
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args = _kernel_args(name)
    compiled = jax.jit(fn).lower(*_on(one_chip, args)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _serve_step_args(cfg, step):
    S = jax.ShapeDtypeStruct
    params = abstract_params(cfg, jnp.bfloat16)
    carry = jax.eval_shape(lambda: init_carry(cfg, SLOTS, MAX_SEQ))
    if step == "decode":
        return make_decode_step(cfg, SLOTS, MAX_SEQ), (params, carry)
    vec = lambda dt: S((SLOTS,), dt)  # noqa: E731
    return make_admit_step(cfg, SLOTS), (
        params, carry, S((SLOTS, PREFILL_PAD), jnp.int32), vec(jnp.int32),
        vec(jnp.bool_), vec(jnp.int32), vec(jnp.float32),
        S((SLOTS, 2), jnp.uint32), vec(jnp.int32),
    )


@pytest.mark.parametrize("step", ["admit", "decode"])
def test_minicpm_serve_step_fits_one_chip(one_chip, step):
    """Full-width minicpm-2b (bf16) at slots=4, max_seq=2048: the program
    and its temporaries fit one chip's HBM."""
    cfg = get_config("minicpm-2b")
    fn, args = _serve_step_args(cfg, step)
    compiled = (
        jax.jit(fn, donate_argnums=(1,)).lower(*_on(one_chip, args)).compile()
    )
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert used <= HBM_BYTES, f"{step}: {used / 1e9:.2f} GB > 16 GB ({m})"


def _top_level_ops(hlo: str):
    """(name, opcode, shapes) of every instruction outside a fusion's
    body, what the chip runs as one operation each; shapes holds the dims
    of each output (several for a tuple)."""
    fused = set(re.findall(r"calls=%([\w.-]+)", hlo))
    inst = re.compile(r"^\s*(?:ROOT )?%([\w.-]+) = (\(.*?\)|\S+) ([\w-]+)\(")
    comp, out = None, []
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.-]+) ", line)
        if head:
            comp = head.group(1)
        elif comp not in fused and (m := inst.match(line)):
            name, shape, op = m.groups()
            shapes = [tuple(int(d) for d in dims.split(",") if d)
                      for dims in re.findall(r"\[([\d,]*)\]", shape)]
            out.append((name, op, shapes))
    return out


def test_minicpm_decode_writes_kv_rows_in_place(one_chip):
    """The engine's decode step at the chat cell's geometry writes one KV
    row per slot into the donated cache: no second cache among its
    temporaries, and no select or copy of a layer's or the stack's cache
    (the per-slot rewrite and its layout copies cost about 60 of a 94 ms
    step on a v5e)."""
    cfg = get_config("minicpm-2b")
    fn, args = _serve_step_args(cfg, "decode")
    compiled = (
        jax.jit(fn, donate_argnums=(1,)).lower(*_on(one_chip, args)).compile()
    )
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 64 * 2**20, f"decode temporaries {temp / 1e9:.2f} GB"
    row = (SLOTS, MAX_SEQ, cfg.n_kv_heads, cfg.head_dim)
    rewrites = [
        (name, op) for name, op, shapes in _top_level_ops(compiled.as_text())
        if any(dims[-4:] == row for dims in shapes)
        and any(w in f"{name} {op}" for w in ("select", "copy"))
    ]
    assert not rewrites, f"the KV cache is rewritten or copied: {rewrites}"
