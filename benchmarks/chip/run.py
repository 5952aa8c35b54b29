#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and metrics are found by name
through ``BENCHMARK.json`` (see ``harness.py``).  Diagnostics go to
stdout as ``{"diag": ...}`` lines, each compared number with its limit
to stderr, and the last line of stdout is the result object.  With no
TPU, or fewer chips than the cell asks for, it exits 3 and prints no
result.  JAX's persistent compilation cache lives in
``JAX_COMPILATION_CACHE_DIR`` where that is set, else in
``<checkout>/.jax_cache``.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax

    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    from benchmarks.chip.harness import NoChip, result_line, run_cell

    try:
        out = run_cell(ROOT, args.workload, args.seed, args.seconds,
                       bool(args.trace), T_START)
    except NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 3
    print(result_line(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
