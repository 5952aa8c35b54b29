"""Shared set-up of the hand-run tools (``sweep.py``, ``calibrate.py``):
paths, the compilation cache and the chip check, as ``run.py`` has them."""

from __future__ import annotations

import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def setup(require_chip: bool = True):
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if require_chip and jax.devices()[0].platform != "tpu":
        raise SystemExit("needs a TPU")
    return ROOT
